"""The program's layer spans (``repro.tracing``) read back from a profiler
trace: every span is recorded, the spans nest as the layers call each
other, a dispatch's spans on the event loop and on the executor thread
carry the same number, a compile leaves its marker inside the span that
compiled, and the backend pick leaves its marker with what it chose by."""

import asyncio
import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.core import FieldSpec, Retriever, SearchRequest, normalize_fields
from repro.kernels import schedule_length
from repro.serving import SearchServer

ROOT = Path(__file__).resolve().parents[1]

SPANS = {v for k, v in vars(tracing).items()
         if k.isupper() and isinstance(v, str) and v.startswith("repro.")}
FORCED = "test.forced_compile"
SERVE = "test.serve"


def _requests(rng, n, names, **kw):
    return [SearchRequest(like=int(i), weights=dict(zip(names, map(float, w))),
                          k=5, probes=4, **kw)
            for i, w in zip(rng.integers(0, 400, n),
                            rng.dirichlet([1.0] * len(names), size=n))]


@pytest.fixture(scope="module")
def events():
    """``[(line, name, start, end, stats)]`` of the host plane of a trace
    holding a build, a synchronous batch with a rescored group, a forced
    compile and one ``SearchServer`` dispatch."""
    from jax.profiler import ProfileData

    spec = FieldSpec(names=("title", "authors", "abstract"), dims=(16, 16, 32))
    docs = normalize_fields(
        jax.random.normal(jax.random.PRNGKey(0), (400, spec.total_dim)), spec)
    rng = np.random.default_rng(0)
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        r = Retriever.build(docs, spec, 6, backend="fused",
                            key=jax.random.PRNGKey(1))
        Retriever(r.index)                  # "auto": leaves the pick marker
        r.search(_requests(rng, 6, spec.names)
                 + _requests(rng, 2, spec.names, rescore=8))
        with tracing.span(FORCED):
            jax.jit(lambda x: x * 3.0 + 1.0)(jnp.arange(7.0)).block_until_ready()

        async def serve():
            async with SearchServer(r, window_s=0.005) as server:
                return await asyncio.gather(*(
                    server.submit(q) for q in _requests(rng, 3, spec.names)))

        with tracing.span(SERVE):
            answers = asyncio.run(serve())
    finally:
        jax.profiler.stop_trace()
    assert len(answers) == 3 and all(a.hits for a in answers)
    found = sorted(Path(d).rglob("*.xplane.pb"))
    data = ProfileData.from_file(str(found[-1]))
    out = []
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for li, line in enumerate(plane.lines):
                for e in line.events:
                    out.append((li, e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def _named(events, name):
    return [e for e in events if e[1] == name]


def _inside(inner, outers):
    """The outer spans on ``inner``'s thread that contain it."""
    return [o for o in outers
            if o[0] == inner[0] and o[2] <= inner[2] and inner[3] <= o[3]]


def test_every_span_is_recorded(events):
    names = {e[1] for e in events}
    assert len(SPANS) == 20
    assert SPANS <= names, sorted(SPANS - names)


def test_search_spans_nest_in_the_batch_and_the_batch_in_the_call(events):
    batches = _named(events, tracing.SEARCH_BATCH)
    inner = [e for e in events
             if e[1].startswith(("repro.search.", "repro.engine."))
             and e[1] not in (tracing.SEARCH_BATCH, tracing.ENGINE_PICK)]
    assert inner and batches
    for e in inner:
        assert _inside(e, batches), e[:4]
    assert all(b[4]["n"] >= 1 and b[4]["groups"] >= 1 for b in batches)
    # the synchronous batch held the rescored requests as a second group
    assert max(b[4]["groups"] for b in batches) == 2
    serve, = _named(events, SERVE)
    calls = _named(events, tracing.SERVE_CALL)
    served = [b for b in batches if serve[2] <= b[2] < serve[3]]
    assert served and calls
    for b in served:
        assert _inside(b, calls), b[:4]
    # the executor thread is not the event loop's
    assert {c[0] for c in calls}.isdisjoint(
        {e[0] for e in _named(events, tracing.SERVE_FLUSH)})


def test_call_and_respond_carry_the_dispatch(events):
    calls = _named(events, tracing.SERVE_CALL)
    responds = _named(events, tracing.SERVE_RESPOND)
    assert {c[4]["dispatch"] for c in calls} == \
        {r[4]["dispatch"] for r in responds} != set()
    assert sum(c[4]["n"] for c in calls) == 3
    for c in calls:
        assert c[4]["replica"] == 0
        r, = [r for r in responds if r[4]["dispatch"] == c[4]["dispatch"]]
        assert r[2] >= c[3]
    flush = _named(events, tracing.SERVE_FLUSH)
    assert flush and flush[0][2] <= calls[0][2]


def test_compile_marker_lands_in_the_span_that_compiled(events):
    marks = _named(events, tracing.COMPILE)
    assert marks and all(m[4]["seconds"] > 0 for m in marks)
    assert any(_inside(m, _named(events, FORCED)) for m in marks)
    # the first fused search compiled the kernel under the score span
    assert any(_inside(m, _named(events, tracing.ENGINE_SCORE))
               for m in marks)


def test_pick_marker_carries_what_it_chose_by(events):
    pick, = _named(events, tracing.ENGINE_PICK)
    stats = pick[4]
    assert set(stats) == {"backend", "pack_bytes", "bytes_limit", "devices"}
    assert stats["backend"] == "reference" and stats["devices"] == 1
    # the 400-document index: T=3 clusterings of 6 buckets, 64 dims, fp32
    assert stats["pack_bytes"] > 0 and stats["pack_bytes"] % (3 * 6 * 64 * 4) == 0
    assert stats["bytes_limit"] == 0          # the CPU reports no limit


def test_score_span_carries_the_grid_steps_launched(events):
    """``slots`` on the fused engine's score span is the kernel's grid,
    ``n_tiles · S``: every search of the trace is one tile of 8 queries
    with 4 probes over the 3·6 buckets."""
    scores = _named(events, tracing.ENGINE_SCORE)
    assert scores
    for e in scores:
        assert "shards" not in e[4]
        assert e[4]["slots"] == 1 * schedule_length(8, 4, 3 * 6), e[4]


SHARDED = r"""
import json, tempfile
from pathlib import Path
import jax
from jax.profiler import ProfileData
from repro import tracing
from repro.core import FieldSpec, get_engine, normalize_fields
from repro.core.index import ClusterPruneIndex

assert jax.device_count() == 4
spec = FieldSpec(names=("a", "b", "c"), dims=(16, 16, 32))
docs = normalize_fields(
    jax.random.normal(jax.random.PRNGKey(0), (300, spec.total_dim)), spec)
index = ClusterPruneIndex.build(docs, spec, 6, key=jax.random.PRNGKey(1))
engine = get_engine(index, "sharded")
d = tempfile.mkdtemp()
jax.profiler.start_trace(d)
try:
    s, _, _ = engine.search(docs[:19], probes=4, k=5)
    s.block_until_ready()
finally:
    jax.profiler.stop_trace()
data = ProfileData.from_file(str(sorted(Path(d).rglob("*.xplane.pb"))[-1]))
print(json.dumps([dict(e.stats) for p in data.planes if p.name == "/host:CPU"
                  for line in p.lines for e in line.events
                  if e.name == tracing.ENGINE_SCORE]))
"""


def test_sharded_score_span_carries_slots_and_shards():
    """On four (virtual CPU) devices the sharded engine's score span
    carries the grid it launched on every shard and the shard count: 19
    queries are one tile of 24, of 4 probes over the 3·6 buckets."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": str(ROOT / "src")}
    out = subprocess.run([sys.executable, "-c", SHARDED], capture_output=True,
                         text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    stats, = json.loads(out.stdout.strip().splitlines()[-1])
    assert stats == {"shards": 4, "slots": 1 * schedule_length(24, 4, 18)}
