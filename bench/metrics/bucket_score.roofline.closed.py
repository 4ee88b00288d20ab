"""Share of its roofline the scoring kernel reached in the window: the
least time the chips need for the work the algorithm needs
(``bench/work.py``) over the device time of the kernel's events."""

import re
import sys

from bench import work

KERNEL = re.compile(r"bucket_score_tiled(\.\d+)?")


def read(r):
    seconds = r.trace.op_seconds(KERNEL.fullmatch, *r.window_ns)
    if seconds <= 0 or not r.dispatch_work:
        return None
    ops, nbytes = work.kernel_work(r.dispatch_work, r.counts, r.d,
                                   r.itemsize, r.n_leaders)
    share, bound = work.roofline(ops, nbytes, seconds, r.peaks, r.n_devices)
    print(f"bucket_score: {ops:.6g} operations, {nbytes:.6g} bytes in "
          f"{seconds:.6f} s of kernel time per chip: {share:.6g}% of the "
          f"roofline, bound by {bound}", file=sys.stderr)
    return share
