"""The work the scoring kernel has to do, from what the algorithm needs.

Counted from the requests a dispatch served and the index, never from the
kernel's own shapes, so that a kernel that reads less padding shows up as
a higher share of its roofline:

- operations: two per coordinate of every bucket member a request scores,
  ``2 * D * (n_scored - T * K)`` (the program's ``n_scored`` counts each
  member of a probed bucket, and the ``T * K`` leader comparisons of
  navigation, which run outside the kernel);
- bytes: for each dispatch, the live documents of the union of its
  requests' probed buckets at the pack's item size, each bucket read once,
  plus the float32 queries.
"""

from __future__ import annotations

import numpy as np


def kernel_work(dispatches, counts, d: int, itemsize: int,
                n_leaders: int) -> tuple[float, float]:
    """``(operations, bytes)`` for ``dispatches``: one list per dispatch of
    ``(probes, n_scored)`` per request, ``probes`` the flat bucket ids
    (``t * K + cluster``) it probed. ``counts`` holds the live members of
    every bucket, flat in the same order."""
    counts = np.asarray(counts).reshape(-1)
    ops = 0.0
    nbytes = 0.0
    for requests in dispatches:
        union = np.unique(np.concatenate([np.asarray(p) for p, _ in requests]))
        nbytes += float(counts[union].sum()) * d * itemsize
        nbytes += float(len(requests)) * d * 4
        ops += sum(2.0 * d * (int(n) - n_leaders) for _, n in requests)
    return ops, nbytes


def roofline(ops: float, nbytes: float, seconds: float, peaks: dict,
             n_devices: int = 1) -> tuple[float, str]:
    """``(share in %, bound)``: the least time ``n_devices`` chips could take,
    the larger of operations over peak FLOP/s and bytes over peak bytes/s,
    over ``seconds`` of kernel time averaged over those chips."""
    t_ops = ops / (peaks["flops_per_s"] * n_devices)
    t_bytes = nbytes / (peaks["bytes_per_s"] * n_devices)
    bound = "bytes" if t_bytes >= t_ops else "operations"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound
