"""Device milliseconds per dispatch of the exchange between chips
(``core/distributed.py``: the per-shard top-k ``all_gather`` before the
merge): the collective operations that start in the window, averaged over
the cell's chips, over the window's dispatches. None where the trace holds
no collective (one chip)."""

import re

COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|all-to-all|"
                        r"collective-permute")


def read(r):
    seconds = r.trace.op_seconds(COLLECTIVE.search, *r.window_ns)
    if seconds <= 0 or r.dispatches <= 0:
        return None
    return seconds * 1e3 / r.dispatches
