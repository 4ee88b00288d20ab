"""The reader of ``exchange.device_ms.closed``: on hand-made events, on the
trace recorded on four v5e chips (``data/sharded4.xplane.pb``, two searches
of 64 requests on the ``sharded`` backend, made by ``data/record_trace.py``),
where it reads a positive number, and on the one-chip trace
(``data/fused1.xplane.pb``), which holds no collective, where it reads
None."""

import types
from pathlib import Path

import numpy as np
import pytest

from bench import spec, xplane

DATA = Path(__file__).resolve().parent / "data"
NAME = "exchange.device_ms.closed"


def _read(trace, window, dispatches):
    return spec.metric_reader(NAME)(types.SimpleNamespace(
        trace=trace, window_ns=window, dispatches=dispatches))


def test_by_hand():
    ops = {f"/device:TPU:{i}": (
        ["%all-gather-start.1 = x", "%fusion.2 = y", "%all-gather-done.1 = z"],
        np.array([0.0, 1e6, 3e6]) + i, np.array([5e5, 3e6, 3.2e6]) + i)
        for i in range(4)}
    tr = xplane.Trace(ops, {}, ([], np.zeros(0), np.zeros(0)))
    # 0.7 ms of collectives a chip, over two dispatches
    assert _read(tr, (0, 1e7), 2) == pytest.approx(0.35)
    assert _read(tr, (0, 1e7), 0) is None
    assert _read(tr, (4e6, 1e7), 2) is None


@pytest.mark.parametrize("fixture, chips, found", [
    ("sharded4.xplane.pb", 4, True),
    ("fused1.xplane.pb", 1, False),
])
def test_recorded(fixture, chips, found):
    trace = xplane.Trace.from_file(DATA / fixture)
    assert len(trace.devices) == chips
    value = _read(trace, trace.span("bench.window"), 2)
    if found:
        assert value is not None and 0 < value < 1e3
    else:
        assert value is None
