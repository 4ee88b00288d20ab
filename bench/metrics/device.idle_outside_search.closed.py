"""Share of the window in which the device idles while no
``repro.search.batch`` span is open on any thread: the time the serving
tier and the load generator (``serving/server.py``, the closed loop) keep
work from the chip, averaged over the cell's chips. None where the trace
holds no such span (a program without spans).

Also prints to standard error the window's device idle seconds by the
innermost ``repro.*`` span open at each gap's midpoint ("outside" where
none is), averaged over the chips, and the window's ``repro.compile``
markers by the innermost span of their thread that encloses them."""

import sys

import numpy as np

from bench.xplane import union_ns

BATCH = "repro.search.batch"
COMPILE = "repro.compile"
PREFIX = "repro."


def read(r):
    names, starts, ends = r.trace.host
    t0, t1 = r.window_ns
    spans = [n.split(": ", 1)[-1] for n in names]
    batch = np.asarray([s == BATCH for s in spans], bool)
    devices = sorted(r.trace.ops)
    if not batch.any() or not devices or t1 <= t0:
        return None
    outside = []
    for dev in devices:
        _, s, e = r.trace.ops[dev]
        cover = union_ns(np.r_[s, starts[batch]], np.r_[e, ends[batch]],
                         t0, t1)
        outside.append((t1 - t0) - float(np.sum(cover[:, 1] - cover[:, 0])))
    _report(r, spans, devices)
    return float(np.mean(outside)) / (t1 - t0)


def _report(r, spans, devices):
    names, starts, ends = r.trace.host
    t0, t1 = r.window_ns
    ours = np.flatnonzero([s.startswith(PREFIX) and s != COMPILE
                           for s in spans])
    inner_first = ours[np.argsort(ends[ours] - starts[ours], kind="stable")]
    idle: dict = {}
    for dev in devices:
        _, s, e = r.trace.ops[dev]
        edges = np.concatenate([[t0], union_ns(s, e, t0, t1).ravel(), [t1]])
        gaps = edges.reshape(-1, 2)
        gaps = gaps[gaps[:, 1] > gaps[:, 0]]
        mids = gaps.sum(axis=1) / 2
        label = np.full(len(gaps), -1)
        for i in inner_first:
            label[(label < 0) & (starts[i] <= mids) & (mids < ends[i])] = i
        for (a, b), i in zip(gaps, label):
            key = spans[i] if i >= 0 else "outside"
            idle[key] = idle.get(key, 0.0) + (b - a) / 1e9 / len(devices)
    thread = [n.split(": ", 1)[0] for n in names]
    compiles: dict = {}
    for m, s in enumerate(spans):
        if s != COMPILE or not t0 <= starts[m] < t1:
            continue
        around = [i for i in inner_first if thread[i] == thread[m]
                  and starts[i] <= starts[m] and ends[m] <= ends[i]]
        key = spans[around[0]] if around else "outside"
        compiles[key] = compiles.get(key, 0) + 1
    ranked = sorted(idle.items(), key=lambda kv: -kv[1])
    print("device idle seconds in the window by program span: "
          + ", ".join(f"{k} {v:.6f}" for k, v in ranked)
          + "; compiles in the window by span: "
          + (", ".join(f"{k} {v}" for k, v in sorted(compiles.items()))
             or "none"), file=sys.stderr)
