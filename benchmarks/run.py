"""Benchmark harness entry point — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--scale quick|ts1|ts2]

table1  preprocessing time/space (clusterer seam + FPF vs k-means vs PODS07)
fig1    query time + distance computations vs visited clusters
table2  recall + NAG over the paper's 7 weight sets
throughput  serving QPS vs batch size per backend (query-tiled fused path)
loadtest async serving tier under load (closed/open loop, micro-batching)
kernels Pallas-vs-oracle agreement + VMEM working sets
roofline the dry-run roofline table (requires results/dryrun/)

Results are persisted next to the repo root as ``BENCH_preprocess.json``
(table1: build-side wall clock per clusterer and per algorithm) and
``BENCH_query.json`` (fig1 + table2: query-side latency / cost / quality),
so every benchmark run leaves a machine-readable artifact and the perf
trajectory accumulates in version control instead of scrolling away in CI
logs.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

_REPO_ROOT = Path(__file__).resolve().parent.parent


def _persist(path: Path, payload: dict) -> None:
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True, default=str)
    print(f"# wrote {path}")


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    scale = "quick"
    if "--scale" in sys.argv:
        scale = sys.argv[sys.argv.index("--scale") + 1]
    t0 = time.time()

    from . import fig1_querytime, kernels_bench, loadtest, roofline_report
    from . import table1_preprocessing, table2_quality, throughput

    pre = table1_preprocessing.run(scale)
    fig1 = fig1_querytime.run(scale)
    table2 = table2_quality.run(scale)
    thr = throughput.run(scale)
    serving = loadtest.run(scale)
    kernels_bench.run()
    roofline_report.run()

    stamp = time.strftime("%Y-%m-%dT%H:%M:%S")
    _persist(_REPO_ROOT / "BENCH_preprocess.json",
             {"generated": stamp, **pre})
    _persist(_REPO_ROOT / "BENCH_query.json", {
        "generated": stamp,
        "scale": scale,
        # fig1 keys are probe budgets (-> tuples) and "backend:<name>" rows
        "fig1": {str(k): list(v) for k, v in fig1.items()},
        # table2 keys are (weight_set, algorithm) tuples
        "table2": {
            f"{w}/{a}": {"recall": rec, "nag": nag}
            for (w, a), (rec, nag) in table2.items()
        },
        # serving throughput: fully labelled entries (backend, batch,
        # pack_dtype, query_tile, rescore -> qps / ms_per_query), one per
        # measured configuration — the fused backend sweeps fp32/bf16/int8
        "throughput": thr,
        # async serving tier under load: sequential baseline + closed-loop
        # (fixed concurrency) + open-loop (fixed arrival rate) entries with
        # QPS and p50/p99 latency split into queue_wait vs compute, plus a
        # final server_stats snapshot (batch-size histogram, shed/expired)
        "serving": serving,
    })
    print(f"\n# benchmarks done in {time.time() - t0:.1f}s (scale={scale})")


if __name__ == "__main__":
    main()
