"""The readers of the program's spans (``bench/metrics``, source
``program_span``): on hand-made events, on the chip trace recorded before
the program had spans (``data/fused1.xplane.pb``), where each reads None,
and on one recorded with them (``data/fused1_spans.xplane.pb``, made by
``data/record_spans.py``), where each reads a finite number."""

import importlib.util
import math
import re
import types
from pathlib import Path

import numpy as np
import pytest

from bench import spec, xplane

DATA = Path(__file__).resolve().parent / "data"
PER_DISPATCH = ("search.prepare_ms.closed", "engine.enqueue_ms.closed",
                "search.device_wait_ms.closed", "search.assemble_ms.closed")
BUILD = ("build.cluster_s", "build.pack_s")
IDLE = "device.idle_outside_search.closed"
READERS = PER_DISPATCH + (IDLE,) + BUILD
KERNEL = re.compile(r"bucket_score_tiled(\.\d+)?")


def _module(name):
    loader = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", spec.metric_file(name))
    module = importlib.util.module_from_spec(loader)
    loader.loader.exec_module(module)
    return module


def _reading(trace, window, dispatches, build_trace=None, build_ns=None):
    return types.SimpleNamespace(trace=trace, window_ns=window,
                                 dispatches=dispatches,
                                 build_trace=build_trace, build_ns=build_ns)


def _host(*events):
    """``(thread, name, start, end)`` events -> ``Trace.host``."""
    return ([f"{t}: {n}" for t, n, _, _ in events],
            np.asarray([s for _, _, s, _ in events], np.float64),
            np.asarray([e for _, _, _, e in events], np.float64))


def test_every_reader_is_in_the_benchmark():
    listed = {m["name"]: m for m in spec.benchmark()["per_layer"]}
    for name in READERS:
        assert listed[name]["source"] == "program_span"
        assert listed[name]["workloads"] == ["ts1-fp32.mlt-closed"]


def test_span_names_are_the_programs():
    from repro import tracing

    assert _module("search.prepare_ms.closed").SPANS == (
        tracing.SEARCH_PREPARE,)
    assert _module("engine.enqueue_ms.closed").SPANS == (
        tracing.ENGINE_NAVIGATE, tracing.ENGINE_SCHEDULE,
        tracing.ENGINE_SCORE)
    assert _module("search.device_wait_ms.closed").SPANS == (
        tracing.SEARCH_WAIT, tracing.SEARCH_FETCH)
    assert _module("search.assemble_ms.closed").SPANS == (
        tracing.SEARCH_ASSEMBLE,)
    assert _module("build.cluster_s").SPANS == (tracing.BUILD_CLUSTER,)
    assert _module("build.pack_s").SPANS == (tracing.INDEX_PACK,)
    idle = _module(IDLE)
    assert (idle.BATCH, idle.COMPILE) == (tracing.SEARCH_BATCH,
                                          tracing.COMPILE)
    assert all(v.startswith(idle.PREFIX) for k, v in vars(tracing).items()
               if k.isupper() and k[0] != "_" and isinstance(v, str))


@pytest.mark.parametrize("name,spans,ms", [
    ("search.prepare_ms.closed", ["repro.search.prepare"], 3.0),
    ("engine.enqueue_ms.closed",
     ["repro.engine.navigate", "repro.engine.schedule", "repro.engine.score"],
     9.0),
    ("search.device_wait_ms.closed",
     ["repro.search.wait", "repro.search.fetch"], 6.0),
    ("search.assemble_ms.closed", ["repro.search.assemble"], 3.0),
])
def test_per_dispatch_readers_by_hand(name, spans, ms):
    # each span 2 ms, then 4 ms, in the window; one more starts before it
    # and one at its end, and a span of another name: none of those count
    events = [("t", s, 0.0, 2e6) for s in spans]
    events += [("u", s, 5e6, 9e6) for s in spans]
    events += [("t", spans[0], -3e6, 1e6), ("t", spans[0], 20e6, 21e6),
               ("t", "repro.search.batch", 0.0, 20e6)]
    trace = xplane.Trace({}, {}, _host(*events))
    read = spec.metric_reader(name)
    assert read(_reading(trace, (0.0, 20e6), dispatches=2)) == \
        pytest.approx(ms)
    assert read(_reading(trace, (0.0, 20e6), dispatches=0)) is None
    assert read(_reading(trace, (30e6, 40e6), dispatches=2)) is None


@pytest.mark.parametrize("name,span", [
    ("build.cluster_s", "repro.build.cluster"),
    ("build.pack_s", "repro.index.pack"),
])
def test_build_readers_by_hand(name, span):
    build = xplane.Trace({}, {}, _host(
        ("t", "bench.build", 0.0, 10e9), ("t", span, 1e9, 3e9),
        ("t", span, 4e9, 4.5e9), ("t", span, 11e9, 12e9),
        ("t", "repro.build.buckets", 3e9, 4e9)))
    read = spec.metric_reader(name)
    window = xplane.Trace({}, {}, _host())
    assert read(_reading(window, (0, 1), 1, build, (0.0, 10e9))) == \
        pytest.approx(2.5)
    assert read(_reading(window, (0, 1), 1, None, None)) is None
    assert read(_reading(build, (0, 1), 1, window, (0.0, 10e9))) is None


def test_idle_outside_search_by_hand(capsys):
    # window [0, 10) s: device busy [0, 2) and [6, 7); a batch open [1, 5)
    # on one thread, a flush [8, 9) on another. Idle outside any batch:
    # [5, 6) and [7, 10), 4 of 10.
    s = 1e9
    ops = {"/device:TPU:0": (["%a = x", "%b = y"], np.array([0.0, 6 * s]),
                             np.array([2 * s, 7 * s]))}
    host = _host(("loop", "repro.serve.flush", 8 * s, 9 * s),
                 ("exec", "repro.search.batch", 1 * s, 5 * s),
                 ("exec", "repro.search.wait", 2 * s, 4 * s),
                 ("exec", "repro.compile", 3 * s, 3 * s),
                 ("exec", "other", 7 * s, 9.5 * s))
    trace = xplane.Trace(ops, {}, host)
    read = spec.metric_reader(IDLE)
    window = (0.0, 10 * s)
    assert read(_reading(trace, window, 1)) == pytest.approx(0.4)
    err = capsys.readouterr().err
    # gap [2, 6): at its midpoint 4 the wait has ended, the batch holds it;
    # gap [7, 10): midpoint 8.5 in the flush ("other" is not the program's)
    assert "repro.search.batch 4.000000, repro.serve.flush 3.000000;" in err
    assert err.rstrip().endswith(
        "compiles in the window by span: repro.search.wait 1")
    # a second chip idle all window long: the share is averaged over chips
    ops["/device:TPU:1"] = ([], np.zeros(0), np.zeros(0))
    two = xplane.Trace(ops, {}, host)
    assert read(_reading(two, window, 1)) == pytest.approx((0.4 + 0.6) / 2)
    assert read(_reading(xplane.Trace(ops, {}, _host()), window, 1)) \
        is None


@pytest.fixture(scope="module")
def before():
    return xplane.Trace.from_file(DATA / "fused1.xplane.pb")


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_before_the_program_had_spans(before, name):
    window = before.span("bench.window")
    reading = _reading(before, window, 2, before, window)
    assert spec.metric_reader(name)(reading) is None


@pytest.fixture(scope="module")
def recorded():
    path = DATA / "fused1_spans.xplane.pb"
    assert path.stat().st_size <= 2_000_000
    trace = xplane.Trace.from_file(path)
    window = trace.span("bench.window")
    names, starts, _ = trace.host
    calls = sum(n.endswith(": repro.serve.call")
                and window[0] <= s < window[1]
                for n, s in zip(names, starts))
    return _reading(trace, window, calls, trace, trace.span("bench.build"))


@pytest.mark.parametrize("name", READERS)
def test_readers_read_the_recorded_spans(recorded, name):
    assert recorded.dispatches == 2
    value = spec.metric_reader(name)(recorded)
    assert value is not None and math.isfinite(value) and value > 0


def test_recorded_kernels_keep_their_names(recorded):
    # the kernels' stable names: what bucket_score.roofline matches, and
    # fpf_iter in the build
    trace = recorded.trace
    assert trace.op_seconds(KERNEL.fullmatch, *recorded.window_ns) > 0
    fpf = re.compile(r"fpf_iter(\.\d+)?")
    assert trace.op_seconds(fpf.fullmatch, *recorded.build_ns) > 0
