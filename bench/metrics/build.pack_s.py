"""Seconds of the bucket-major pack (``core/index.py``): the program's
``repro.index.pack`` spans that start inside the span ``build_s`` times,
the pack the first search makes where the build deferred it included.
None where the build's trace holds none of them."""

import numpy as np

SPANS = ("repro.index.pack",)


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
