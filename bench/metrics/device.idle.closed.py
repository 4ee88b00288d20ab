"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips (closed-loop cells)."""


def read(r):
    if r.window_s <= 0 or not r.trace.devices:
        return None
    return 1.0 - r.trace.busy_s(*r.window_ns) / r.window_s
