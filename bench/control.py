"""Readings that set the limits of ``correct``: the program's, the
control's and a planted fault's, on several seeds in one process (set-up
is long).

    python bench/control.py --workload ts1-fp32.mlt-closed --seeds 11,12,13 --seconds 30 [--fault-seeds 14,15,16]

Each seed is one run of the cell as ``bench/run.py`` makes it, with the
control put in the program's place after the window (``harness.run``'s
``control``): its answers at three bfloat16 passes and its assignment of
documents at float8 are judged instead of the program's, and the program's
own readings of the same run are kept beside them. Each fault seed is one
more run with the build's FPF skipped (its centers drawn at random from
the sample), which ``leader_gap`` has to catch. One JSON line per run, then
one with the largest sound reading (the lower end of each limit) and the
smallest control or fault reading (its upper end). The benchmark's own
runs never compute the control.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def plant_random_leaders() -> None:
    """The build's FPF rounds skipped: K centers drawn at random from the
    sample, the rest of the build as it is."""
    import jax

    from repro.core import cluster

    def centers(self, xs, k, key):
        return jax.random.permutation(key, xs.shape[0])[:k]

    cluster.FPFClusterer._centers = centers
    cluster.FusedFPFClusterer._centers = centers


def _seeds(text: str) -> list[int]:
    return [int(x) for x in text.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    lower: dict = {}
    upper: dict = {}

    def note(into, name, value, pick):
        into[name] = pick(into.get(name, value), value)

    for seed in _seeds(args.seeds):
        res = harness.run(args.workload, seed, args.seconds, False,
                          t_start=time.perf_counter(), control=True)
        checks = {k: c["value"] for k, c in res["checks"].items()}
        print(json.dumps({"seed": seed, "control": True,
                          "correct": res["correct"], "checks": checks,
                          "program": res["program"],
                          "metrics": res["metrics"],
                          "device": res["device"]}), flush=True)
        for name, value in res["program"].items():
            note(lower, name, value, max)
            note(upper, name, checks[name], min)
        note(lower, "leader_gap", checks["leader_gap"], max)
    if args.fault_seeds:
        plant_random_leaders()
    for seed in _seeds(args.fault_seeds):
        res = harness.run(args.workload, seed, args.seconds, False,
                          t_start=time.perf_counter())
        checks = {k: c["value"] for k, c in res["checks"].items()}
        print(json.dumps({"seed": seed, "fault": "leaders_at_random",
                          "correct": res["correct"], "checks": checks}),
              flush=True)
        note(upper, "leader_gap", checks["leader_gap"], min)
    print(json.dumps({"workload": args.workload, "lower": lower,
                      "upper": upper}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
