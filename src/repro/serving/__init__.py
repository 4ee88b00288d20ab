"""repro.serving — async micro-batching serving tier over the Retriever.

The engine's batched path (query-tiled fused kernel, one HBM read per
shared bucket per tile) is only fast when requests reach it *in batches* —
but real traffic is concurrent single requests. This package is the
mechanism between the two: an asyncio front-end that accumulates incoming
:class:`~repro.core.SearchRequest` objects in per-execution-shape queues
(:func:`~repro.core.exec_shape` — the same ``(backend, probes, k,
rescore)`` grouping ``Retriever.search`` applies to a synchronous batch),
flushes each queue when its micro-batch window elapses or it reaches a
query-tile multiple, and dispatches one engine call per flush on a replica
thread — with deadline scheduling, priority-aware load shedding under
bounded-queue backpressure, and an honest per-request latency split
(``queue_wait_s`` vs ``compute_s``) on every response.

Layout (policy/mechanism/loop kept separate, each independently testable):

    batcher.py    per-shape FIFOs; window-or-size flush readiness
    scheduler.py  typed failures + admission/expiry/ordering policy
    server.py     SearchServer event loop, ReplicaPool, executor dispatch
    stats.py      counters, batch-size histogram, p50/p99 wait/compute split
    health.py     circuit breaker, retry budget, EWMA health, degradation
    faults.py     deterministic fault injection (the chaos harness's seam)

The tier is fault-tolerant by default: per-shape dispatch timeouts derived
from observed p99 compute, retries on a different replica under capped
jittered backoff and a token-bucket retry budget, hedged dispatch for
batches stuck past the shape's p99, a per-replica circuit breaker, and a
degradation ladder (drop rescore, step probes down a calibrated rung)
that answers ``degraded=True`` instead of shedding — while ``exact=``/
``min_recall=`` requests fail typed (:class:`ReplicaUnavailable`) rather
than ever being silently downgraded. Tune it all through
:class:`ResilienceConfig`; chaos-test it with ``python -m
benchmarks.loadtest --chaos``.

Copy-paste usage::

    import asyncio
    from repro.core import Retriever, SearchRequest
    from repro.serving import SearchServer, DeadlineExceeded, Overloaded

    retriever = Retriever.build(docs, spec, k_clusters=64)

    async def main():
        async with SearchServer(
            retriever,
            window_s=0.002,       # micro-batch window: 2 ms
            max_queue_depth=256,  # backpressure bound per shape queue
            replicas=2,           # parallel dispatch slots
        ) as server:
            try:
                resp = await server.submit(
                    SearchRequest(like=7, k=10),
                    deadline_s=0.05,  # fail fast if still queued at 50 ms
                    priority=1,       # outranks priority-0 under shedding
                )
                print(resp.ids, resp.queue_wait_s, resp.compute_s)
            except DeadlineExceeded:
                ...               # expired in queue — engine never ran
            except Overloaded:
                ...               # rejected or shed: back off and retry
            print(server.stats.format_line())

    asyncio.run(main())

Load-test the tier with ``python -m benchmarks.loadtest`` (open/closed
loop, heterogeneous mixes, QPS + p50/p99 into ``BENCH_query.json``) or
drive it end to end with ``python -m repro.launch.serve --serve``.

Tracing. Every dispatch leaves named host spans in the JAX profiler's
trace, on the device planes' clock (:mod:`repro.tracing`; always on, a
microsecond or two each when no profiler runs):

    repro.serve.flush     event loop: ready queues drained into dispatches
    repro.serve.call      executor thread: one replica call (dispatch,
                          replica, n); holds the retriever's spans:
      repro.search.batch    Retriever.search (n, groups), and within it
                            .prepare, .engine, .wait, .fetch, .assemble;
                            repro.engine.navigate/.schedule/.score/.rescore
                            inside .engine
    repro.serve.respond   event loop: tickets stamped and resolved
                          (dispatch, the same number as its call)

The build adds ``repro.build.cluster`` (per clustering, ``t``) holding
``repro.build.fpf`` and ``repro.build.assign``, ``repro.build.buckets``
and ``repro.index.pack``; each compile leaves a ``repro.compile`` marker
(``seconds``) in the span that compiled. To capture them from a live
server, start it with ``python -m repro.launch.serve --serve
--profile-port 9999`` and record with ``python -m jax.collect_profile 9999
2000 --log_dir <dir>``.
"""

from .batcher import Batcher, ShapeQueue
from .faults import FAULT_PROFILES, FaultPolicy, FaultProfile, InjectedFault
from .health import (
    CircuitBreaker,
    ReplicaHealth,
    ResilienceConfig,
    RetryBudget,
    degrade_batch,
    degrade_request,
)
from .scheduler import (
    DeadlineExceeded,
    Overloaded,
    ReplicaUnavailable,
    Scheduler,
    ServingError,
    Ticket,
)
from .server import Replica, ReplicaPool, SearchServer, default_max_batch
from .stats import ServerStats

__all__ = [
    "SearchServer",
    "ReplicaPool",
    "Replica",
    "default_max_batch",
    "Batcher",
    "ShapeQueue",
    "Scheduler",
    "Ticket",
    "ServingError",
    "DeadlineExceeded",
    "Overloaded",
    "ReplicaUnavailable",
    "ServerStats",
    "ResilienceConfig",
    "CircuitBreaker",
    "RetryBudget",
    "ReplicaHealth",
    "degrade_request",
    "degrade_batch",
    "FaultPolicy",
    "FaultProfile",
    "FAULT_PROFILES",
    "InjectedFault",
]
