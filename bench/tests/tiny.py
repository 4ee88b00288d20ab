"""A cell small enough for the CPU, built from the committed ones: the
configuration's widths and corpus generator with 2,000 documents and 40
leaders per clustering, on the ``reference`` backend. At the real widths
the committed limits part sound runs from faults here as on the chip."""

from __future__ import annotations

import copy
import time

from bench import harness, spec


def cell(workload: str = "ts1-fp32.mlt-closed", n_docs: int = 2000,
         **index) -> dict:
    w = dict(spec.workload(workload))
    cfg = copy.deepcopy(spec.config(w["config"]))
    cfg["corpus"].update(n_docs=n_docs)
    cfg["index"].update({"k_clusters": 40, "backend": "reference", **index})
    tr = copy.deepcopy(spec.traffic(w["traffic"]))
    tr.update(loop="closed", clients=64, ramp_s=0.3)
    return {"workload": w, "config": cfg, "traffic": tr}


def run(c: dict, seed: int = 2**31 + 5, seconds: float = 3.0, **kw) -> dict:
    return harness.run(c["workload"]["name"], seed, seconds, False,
                       t_start=time.perf_counter(), require_chip=False,
                       cell=c, **kw)
