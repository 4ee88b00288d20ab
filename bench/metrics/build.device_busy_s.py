"""Seconds in which some operation ran on the device during the span that
``build_s`` times (build to first answer), averaged over the cell's
chips. Compiles add nothing to it."""


def read(r):
    if r.build_trace is None or not r.build_trace.devices:
        return None
    return r.build_trace.busy_s(*r.build_ns)
