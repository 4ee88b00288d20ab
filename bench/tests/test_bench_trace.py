"""The trace reduction, on a small trace recorded on one v5e chip
(``data/fused1.xplane.pb``, made by ``data/record_trace.py``) and on
hand-made events."""

import re
from pathlib import Path

import numpy as np
import pytest

from bench import xplane

TRACE = Path(__file__).resolve().parent / "data" / "fused1.xplane.pb"
KERNEL = re.compile(r"bucket_score_tiled(\.\d+)?")
COLLECTIVE = re.compile(r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
                        r"collective-permute)")


def test_union_by_hand():
    iv = xplane.union_ns([0, 2, 3, 10, 11], [4, 3, 5, 12, 11.5], 1, 11)
    assert iv.tolist() == [[1, 5], [10, 11]]
    assert xplane.union_ns([], [], 0, 1).shape == (0, 2)


def test_collective_time_by_hand():
    ops = {f"/device:TPU:{i}": (
        ["%all-gather-start.1 = x", "%fusion.2 = y", "%all-gather-done.1 = z"],
        np.array([0.0, 10.0, 30.0]) + i, np.array([5.0, 30.0, 32.0]) + i)
        for i in range(4)}
    tr = xplane.Trace(ops, {}, ([], np.zeros(0), np.zeros(0)))
    assert tr.op_seconds(COLLECTIVE.search, 0, 100) == pytest.approx(7e-9)
    assert tr.op_seconds(COLLECTIVE.search, 0, 29) == pytest.approx(5e-9)


def test_idle_gaps_by_hand():
    ops = {"/device:TPU:0": (["%a = x", "%b = y"], np.array([0.0, 6e8]),
                             np.array([2e8, 1e9]))}
    host = (["t: wait", "t: outer", "t: prep"],
            np.array([2e8, 0.0, 3e8]), np.array([6e8, 1e9, 3.5e8]))
    tr = xplane.Trace(ops, {}, host)
    assert tr.busy_s(0, 1e9) == pytest.approx(0.6)
    # one gap [2e8, 6e8), midpoint 4e8: open spans "outer" and "wait"
    assert tr.idle_gaps(0, 1e9) == [["t: wait", pytest.approx(0.4)]]


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    return xplane.Trace.from_file(TRACE), ProfileData.from_file(str(TRACE))


def _device_events(data, line_name):
    out = {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == line_name:
                    out[plane.name] = [(e.name, e.start_ns, e.duration_ns)
                                       for e in line.events]
    return out


def _union_by_walk(events, t0, t1):
    """Busy nanoseconds by walking events in start order."""
    busy, edge = 0.0, t0
    for _, s, d in sorted(events, key=lambda e: e[1]):
        s, e = max(s, edge), min(s + d, t1)
        if e > s:
            busy += e - s
            edge = e
    return busy


def test_recorded_trace_reduces_as_walked(recorded):
    trace, data = recorded
    t0, t1 = trace.span("bench.window")
    ops = _device_events(data, "XLA Ops")
    assert sorted(ops) == ["/device:TPU:0"] == trace.devices
    walked = np.mean([_union_by_walk(ev, t0, t1) for ev in ops.values()])
    assert trace.busy_s(t0, t1) == pytest.approx(walked / 1e9)
    assert 0 < trace.busy_s(t0, t1) < (t1 - t0) / 1e9

    def summed(pattern, match):
        per = [sum(d for n, s, d in ev
                   if match(pattern, xplane.op_name(n)) and t0 <= s < t1)
               for ev in ops.values()]
        return np.mean(per) / 1e9

    kernel = trace.op_seconds(KERNEL.fullmatch, t0, t1)
    assert kernel > 0
    assert kernel == pytest.approx(summed(KERNEL, re.fullmatch))
    # one chip: the reduction finds no collective where the walk finds none
    assert trace.op_seconds(COLLECTIVE.search, t0, t1) == \
        summed(COLLECTIVE, re.search) == 0


def test_recorded_breakdown(recorded):
    trace, _ = recorded
    t0, t1 = trace.span("bench.window")
    top = trace.top_modules(t0, t1)
    assert 0 < len(top) <= 10 and "(" not in top[0][0]
    assert top[0][0] == "jit_bucket_score_tiled"
    gaps = trace.idle_gaps(t0, t1)
    assert 0 < len(gaps) <= 10
    idle = (t1 - t0) / 1e9 - trace.busy_ns(trace.devices[0], t0, t1) / 1e9
    assert sum(v for _, v in trace.idle_gaps(t0, t1, n=10**6)) == \
        pytest.approx(idle)
