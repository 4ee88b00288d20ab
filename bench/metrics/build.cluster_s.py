"""Seconds of the build's clusterings (``core/cluster.py``,
``kernels/fpf_iter``): the program's ``repro.build.cluster`` spans that
start inside the span ``build_s`` times. None where the build's trace holds
none of them."""

import numpy as np

SPANS = ("repro.build.cluster",)


def read(r):
    if r.build_trace is None:
        return None
    names, starts, ends = r.build_trace.host
    t0, t1 = r.build_ns
    sel = np.asarray([n.split(": ", 1)[-1] in SPANS for n in names], bool)
    sel &= (starts >= t0) & (starts < t1)
    if not sel.any():
        return None
    return float(np.sum(ends[sel] - starts[sel])) / 1e9
