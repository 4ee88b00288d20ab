"""The async serving tier (repro.serving): scheduling policy unit tests
(no asyncio — tickets with fake futures, time as plain floats) plus live
SearchServer tests driven through ``asyncio.run`` — the acceptance bar is
that micro-batched responses are id/score-identical to one-by-one
synchronous search on every runnable backend."""

import asyncio
import time

import jax
import numpy as np
import pytest

from repro.core import (
    ExecShape,
    FieldSpec,
    Retriever,
    SearchRequest,
    get_engine,
    normalize_fields,
)
from repro.serving import (
    Batcher,
    DeadlineExceeded,
    Overloaded,
    Scheduler,
    SearchServer,
    ServerStats,
    ShapeQueue,
    Ticket,
    default_max_batch,
)

BACKENDS = ("reference", "fused", "sharded")
SHAPE = ExecShape("reference", 6, 5, None)


# ------------------------------------------------------------ policy fixtures
class FakeFuture:
    """Duck-typed asyncio.Future for event-loop-free policy tests."""

    def __init__(self):
        self.value = None
        self.exception = None
        self._done = False

    def done(self):
        return self._done

    def set_result(self, v):
        assert not self._done
        self.value, self._done = v, True

    def set_exception(self, e):
        assert not self._done
        self.exception, self._done = e, True


def ticket(t=0.0, deadline=None, priority=0, seq=0, shape=SHAPE):
    return Ticket(
        request=SearchRequest(like=seq), shape=shape, future=FakeFuture(),
        t_enqueue=t, deadline=deadline, priority=priority, seq=seq,
    )


# ------------------------------------------------------------- policy: queues
def test_shape_queue_fifo_and_lookups():
    q = ShapeQueue(SHAPE)
    ts = [ticket(t=float(i), deadline=10.0 - i, priority=i % 2, seq=i)
          for i in range(5)]
    for t in ts:
        q.append(t)
    assert q.oldest_enqueue() == 0.0
    assert q.min_deadline() == 6.0                      # 10 - 4
    # shed victim: lowest priority (0), youngest among them (seq 4)
    assert q.lowest_priority() is ts[4]
    assert q.drain(2) == ts[:2] and len(q) == 3         # FIFO drain
    assert q.oldest_enqueue() == 2.0


def test_batcher_window_vs_size_flush_race():
    """Size can force a flush long before the window; the window forces
    one no matter how small the queue — and the race is decided per pass
    from (now, len) alone, deterministically."""
    b = Batcher(window_s=1.0, max_batch=4)
    q = b.queue(SHAPE)
    for i in range(3):
        q.append(ticket(t=0.0, seq=i))
    assert b.ready(now=0.5) == []                 # neither trigger yet
    assert b.due_at(q) == 1.0 and b.next_due() == 1.0
    assert b.ready(now=1.0) == [q]                # window elapsed
    q.append(ticket(t=0.9, seq=3))
    assert b.ready(now=0.95) == [q]               # size beat the window
    # drain cap: a burst stays ready and drains in max_batch slices
    for i in range(4, 10):
        q.append(ticket(t=0.9, seq=i))
    assert len(q.drain(b.max_batch)) == 4
    assert len(q) == 6 and b.ready(now=0.95) == [q]
    assert b.pending() == 6 and b.depths() == {SHAPE: 6}


def test_batcher_window_measured_from_oldest():
    """A steady trickle must not postpone the flush: the window anchors on
    the OLDEST ticket, so due_at never moves backwards in time."""
    b = Batcher(window_s=1.0, max_batch=100)
    q = b.queue(SHAPE)
    q.append(ticket(t=0.0))
    for t in (0.4, 0.8, 0.95):                    # trickle keeps arriving
        q.append(ticket(t=t))
        assert b.due_at(q) == 1.0                 # still the oldest's due
    assert b.ready(now=1.0) == [q]


# --------------------------------------------------------- policy: scheduling
def test_deadline_expiry_and_flush_ordering():
    sched = Scheduler(max_queue_depth=8)
    tight = ShapeQueue(ExecShape("reference", 6, 5, None))
    loose = ShapeQueue(ExecShape("reference", 9, 5, None))
    free = ShapeQueue(ExecShape("reference", 12, 5, None))
    t_dead = ticket(t=0.0, deadline=1.0, seq=0)
    tight.append(t_dead)
    tight.append(ticket(t=0.0, deadline=5.0, seq=1))
    loose.append(ticket(t=0.5, deadline=3.0, seq=2))
    free.append(ticket(t=0.1, seq=3))             # no deadline

    # expiry: only the passed deadline dies, typed + removed from its queue
    dead = sched.expire([tight, loose, free], now=2.0)
    assert dead == [t_dead] and len(tight) == 1
    assert isinstance(t_dead.future.exception, DeadlineExceeded)
    assert "budget" in str(t_dead.future.exception)

    # ordering: earliest surviving deadline first, deadline-free last
    assert sched.flush_order([free, tight, loose]) == [loose, tight, free]
    # among deadline-free queues: oldest waiter first
    free2 = ShapeQueue(ExecShape("reference", 3, 5, None))
    free2.append(ticket(t=0.05, seq=4))
    assert sched.flush_order([free, free2]) == [free2, free]


def test_priority_shedding_under_full_queue():
    sched = Scheduler(max_queue_depth=2, shed_low_priority=True)
    q = ShapeQueue(SHAPE)
    lo, hi = ticket(priority=0, seq=0), ticket(priority=1, seq=1)
    assert sched.admit(q, lo) is None and sched.admit(q, hi) is None

    # a higher-priority newcomer sheds the lowest-priority waiter
    vip = ticket(priority=2, seq=2)
    victim = sched.admit(q, vip)
    assert victim is lo and list(q) == [hi, vip]
    assert isinstance(lo.future.exception, Overloaded)
    assert "shed" in str(lo.future.exception)

    # equal (or lower) priority preempts nothing: typed rejection, and the
    # newcomer's own future is untouched (the caller re-raises, not fails)
    also_lo = ticket(priority=1, seq=3)
    with pytest.raises(Overloaded, match="preempts nothing"):
        sched.admit(q, also_lo)
    assert not also_lo.future.done() and list(q) == [hi, vip]

    # shedding off: a full queue rejects even a VIP outright
    strict = Scheduler(max_queue_depth=1, shed_low_priority=False)
    q2 = ShapeQueue(SHAPE)
    strict.admit(q2, ticket(priority=0, seq=0))
    with pytest.raises(Overloaded):
        strict.admit(q2, ticket(priority=9, seq=1))


def test_expired_waiter_releases_queue_slot():
    """A ticket whose deadline passes WHILE QUEUED must free its
    max_queue_depth slot at admission time: the newcomer takes the dead
    ticket's place instead of being shed/rejected (regression — expired
    waiters used to hold their slot until the serving loop's next sweep)."""
    expired_seen = []
    sched = Scheduler(max_queue_depth=2, shed_low_priority=True,
                      on_expired=expired_seen.append)
    q = ShapeQueue(SHAPE)
    dead = ticket(t=0.0, deadline=1.0, seq=0)
    live = ticket(t=0.0, deadline=50.0, seq=1)
    assert sched.admit(q, dead) is None and sched.admit(q, live) is None

    # queue full, but one waiter is already past its deadline at admit time
    newcomer = ticket(t=2.0, deadline=None, seq=2)
    assert sched.admit(q, newcomer) is None       # admitted, nothing shed
    assert list(q) == [live, newcomer]
    assert isinstance(dead.future.exception, DeadlineExceeded)
    assert expired_seen == [dead]                 # reported like sweep expiry

    # with no expired waiter the full queue still sheds/rejects as before
    extra = ticket(t=3.0, priority=0, seq=3)
    with pytest.raises(Overloaded):
        sched.admit(q, extra)


def test_stats_aggregation():
    s = ServerStats()
    for _ in range(3):
        s.record_submit()
    s.record_batch([0.001, 0.002], 0.010)
    s.record_batch([0.004], 0.020)
    s.record_expired()
    assert s.submitted == 3 and s.completed == 3 and s.batches == 2
    snap = s.snapshot({SHAPE: 4})
    assert snap["batch_size_hist"] == {1: 1, 2: 1}
    assert snap["mean_batch_size"] == 1.5
    assert snap["compute_ms"]["p50"] == pytest.approx(15.0)
    assert snap["latency_ms"]["p99"] >= snap["latency_ms"]["p50"] > 0
    assert snap["queue_depth"] == {str(SHAPE): 4}
    line = s.format_line()
    assert "served=3/3" in line and "expired=1" in line


# --------------------------------------------------------------- live servers
@pytest.fixture(scope="module")
def serving_corpus():
    spec = FieldSpec(names=("title", "authors", "abstract"),
                     dims=(32, 32, 64))
    x = jax.random.normal(jax.random.PRNGKey(17), (640, spec.total_dim))
    return normalize_fields(x, spec), spec


@pytest.fixture(scope="module")
def retriever(serving_corpus):
    docs, spec = serving_corpus
    return Retriever.build(
        docs, spec, 16, n_clusterings=3, method="fpf",
        key=jax.random.PRNGKey(0), backend="reference",
    )


def mlt_requests(n, seed=0, backend=None, **shape):
    rng = np.random.default_rng(seed)
    qids = rng.choice(640, n, replace=False)
    w = rng.dirichlet([1.0, 1.0, 1.0], size=n).astype(np.float32)
    return [
        SearchRequest(
            like=int(qids[i]),
            weights={"title": float(w[i, 0]), "authors": float(w[i, 1]),
                     "abstract": float(w[i, 2])},
            backend=backend, **shape,
        )
        for i in range(n)
    ]


def test_default_max_batch(retriever):
    assert default_max_batch(retriever) == 64     # reference doesn't tile
    fused = Retriever(retriever.index, backend="fused")
    mb = default_max_batch(fused)
    qt = get_engine(fused.index, "fused").tile_for(10)
    assert qt and mb >= 64 and mb % qt == 0       # full MXU tiles
    tiled = Retriever(retriever.index, backend="fused",
                      engine_opts={"query_tile": 24})
    assert default_max_batch(tiled) == 72         # the engine's own tile


@pytest.mark.parametrize("backend", BACKENDS)
def test_ragged_batch_parity_vs_one_by_one(case_retriever, retriever, backend):
    """11 concurrent submits against max_batch=8 -> one full batch plus a
    ragged tail of 3; every response must match one-by-one sync search."""
    served, name = case_retriever(retriever, backend)
    requests = mlt_requests(11, seed=1, backend=name, probes=6, k=5)

    async def go():
        async with SearchServer(
            served, window_s=0.01, max_batch=8
        ) as server:
            return await asyncio.gather(
                *(server.submit(r) for r in requests)
            ), server.stats.snapshot()

    responses, snap = asyncio.run(go())
    assert snap["completed"] == 11
    assert sorted(r.batch_size for r in responses) == [3] * 3 + [8] * 8

    # fresh: no caches
    solo, _ = case_retriever(Retriever(retriever.index, backend=name), backend)
    for resp, req in zip(responses, requests):
        ref = solo.search(req)
        assert np.array_equal(resp.doc_ids, ref.doc_ids), backend
        np.testing.assert_allclose(resp.scores, ref.scores, atol=1e-6)
        assert resp.backend == name
        # the server stamped an honest per-request latency split
        assert resp.queue_wait_s >= 0 and resp.compute_s > 0
        assert resp.latency_s == pytest.approx(
            resp.queue_wait_s + resp.compute_s
        )


def test_size_flush_beats_window(retriever):
    """max_batch submits flush immediately — nobody waits out a 30 s
    window (the live half of the window-vs-size race)."""
    requests = mlt_requests(4, seed=2, probes=6, k=5)

    async def go():
        async with SearchServer(
            retriever, window_s=30.0, max_batch=4
        ) as server:
            t0 = time.perf_counter()
            resps = await asyncio.gather(
                *(server.submit(r) for r in requests)
            )
            return resps, time.perf_counter() - t0

    responses, elapsed = asyncio.run(go())
    assert elapsed < 30.0
    assert [r.batch_size for r in responses] == [4] * 4


def test_deadline_expires_in_queue(retriever):
    """A queued request whose deadline passes before its window flushes
    fails typed; its shape-mates dispatch and complete normally. Deadlines
    bound queue time: the survivor's queue_wait is the window, not less."""
    live_req, dead_req = mlt_requests(2, seed=3, probes=6, k=5)

    async def go():
        async with SearchServer(
            retriever, window_s=0.25, max_batch=64
        ) as server:
            dead = asyncio.create_task(
                server.submit(dead_req, deadline_s=0.02)
            )
            live = asyncio.create_task(server.submit(live_req))
            with pytest.raises(DeadlineExceeded, match="budget"):
                await dead
            resp = await live
            return resp, server.stats.snapshot()

    resp, snap = asyncio.run(go())
    assert resp.batch_size == 1                   # the dead one never rode
    assert resp.queue_wait_s >= 0.2
    assert snap["expired"] == 1 and snap["completed"] == 1

    # fail-fast: an already-expired deadline never reaches a queue
    async def instant():
        async with SearchServer(retriever) as server:
            with pytest.raises(DeadlineExceeded, match="at submission"):
                await server.submit(live_req, deadline_s=0.0)

    asyncio.run(instant())


def test_live_shedding_priority_order(retriever):
    """With depth 1 and a long window: a high-priority newcomer sheds the
    queued low-priority waiter; an equal-priority newcomer is rejected."""
    reqs = mlt_requests(3, seed=4, probes=6, k=5)

    async def go():
        async with SearchServer(
            retriever, window_s=0.3, max_batch=64, max_queue_depth=1
        ) as server:
            low = asyncio.create_task(server.submit(reqs[0], priority=0))
            await asyncio.sleep(0)                # let `low` reach its queue
            high = asyncio.create_task(server.submit(reqs[1], priority=1))
            await asyncio.sleep(0)
            with pytest.raises(Overloaded, match="preempts nothing"):
                await server.submit(reqs[2], priority=1)
            with pytest.raises(Overloaded, match="shed"):
                await low
            resp = await high
            return resp, server.stats.snapshot()

    resp, snap = asyncio.run(go())
    assert resp.batch_size == 1
    assert snap["shed"] == 1 and snap["rejected"] == 1
    assert snap["completed"] == 1


def test_e2e_async_smoke(retriever):
    """Seeded end-to-end: heterogeneous shapes, two replicas, every submit
    answered, per-shape batching honoured, stats coherent."""
    requests = (
        mlt_requests(9, seed=5, probes=6, k=5)
        + mlt_requests(6, seed=6, probes=9, k=3)
    )

    async def go():
        async with SearchServer(
            retriever, window_s=0.02, max_batch=8, replicas=2
        ) as server:
            resps = await asyncio.gather(
                *(server.submit(r) for r in requests)
            )
            return resps, server.stats.snapshot()

    responses, snap = asyncio.run(go())
    assert snap["submitted"] == snap["completed"] == 15
    assert snap["expired"] == snap["rejected"] == snap["failed"] == 0
    assert snap["batches"] >= 3                   # 9 -> 8+1, 6 -> 6
    assert sum(
        n * c for n, c in snap["batch_size_hist"].items()
    ) == 15
    for resp, req in zip(responses, requests):
        assert resp.probes == req.probes and len(resp.ids) == req.k
        assert resp.latency_s == pytest.approx(
            resp.queue_wait_s + resp.compute_s
        )
    # shapes never mix: a k=3 response can only have ridden with k=3 peers
    k3 = [r for r in responses if len(r.ids) == 3]
    assert all(r.batch_size <= 6 for r in k3)


def test_stop_without_drain_fails_queued(retriever):
    """stop(drain=False) refuses queued work typed instead of hanging."""
    req, = mlt_requests(1, seed=7, probes=6, k=5)

    async def go():
        server = await SearchServer(
            retriever, window_s=5.0, max_batch=64
        ).start()
        fut = asyncio.create_task(server.submit(req))
        await asyncio.sleep(0)                    # reaches the queue
        await server.stop(drain=False)
        with pytest.raises(Overloaded, match="stopped"):
            await fut
        with pytest.raises(RuntimeError, match="not running"):
            await server.submit(req)

    asyncio.run(go())


def test_tiered_shapes_through_server(retriever):
    """Tiered requests ride the micro-batcher unchanged: exact submits key
    their own queue (pinned full-sweep shape), batch together, and answer
    id/score-identical to synchronous exact search; budgeted peers in the
    same burst are unaffected."""
    exact_reqs = mlt_requests(5, seed=8, k=5, exact=True)
    approx_reqs = mlt_requests(4, seed=9, probes=6, k=5)

    async def go():
        async with SearchServer(
            retriever, window_s=0.02, max_batch=8
        ) as server:
            resps = await asyncio.gather(
                *(server.submit(r) for r in exact_reqs + approx_reqs)
            )
            return resps, server.stats.snapshot()

    responses, snap = asyncio.run(go())
    assert snap["completed"] == 9
    exact_resps, approx_resps = responses[:5], responses[5:]

    t, kc = retriever._tk
    solo = Retriever(retriever.index, backend="reference")  # no caches
    for resp, req in zip(exact_resps, exact_reqs):
        assert resp.tier == "exact" and resp.batch_size == 5
        assert resp.probes == t * kc and resp.predicted_recall == 1.0
        ref = solo.search(req)
        assert np.array_equal(resp.doc_ids, ref.doc_ids)
        np.testing.assert_allclose(resp.scores, ref.scores, atol=1e-6)
    for resp in approx_resps:
        assert resp.tier == "approx" and resp.batch_size == 4
        assert resp.probes == 6
    # the two tiers never shared a queue
    assert snap["batch_size_hist"] == {4: 1, 5: 1}
