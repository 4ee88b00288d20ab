"""Host milliseconds per dispatch in navigation, the probe schedule and the
kernel's launch (``core/engine.py``, ``kernels/bucket_score/ops.py``): the
seconds of the program's ``repro.engine.navigate``, ``.schedule`` and
``.score`` spans that start in the window, over the window's dispatches.
Device work is asynchronous, so this is the host's enqueue time. None where
the trace holds none of them."""

import numpy as np

SPANS = ("repro.engine.navigate", "repro.engine.schedule",
         "repro.engine.score")


def read(r):
    names, starts, ends = r.trace.host
    t0, t1 = r.window_ns
    sel = np.asarray([n.split(": ", 1)[-1] in SPANS for n in names], bool)
    sel &= (starts >= t0) & (starts < t1)
    if not sel.any() or r.dispatches <= 0:
        return None
    return float(np.sum(ends[sel] - starts[sel])) / 1e6 / r.dispatches
