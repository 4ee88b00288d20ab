"""Host milliseconds per dispatch blocked on the device and copying the
results back (``core/api.py``): the seconds of the program's
``repro.search.wait`` and ``repro.search.fetch`` spans that start in the
window, over the window's dispatches. None where the trace holds none of
them."""

import numpy as np

SPANS = ("repro.search.wait", "repro.search.fetch")


def read(r):
    names, starts, ends = r.trace.host
    t0, t1 = r.window_ns
    sel = np.asarray([n.split(": ", 1)[-1] in SPANS for n in names], bool)
    sel &= (starts >= t0) & (starts < t1)
    if not sel.any() or r.dispatches <= 0:
        return None
    return float(np.sum(ends[sel] - starts[sel])) / 1e6 / r.dispatches
