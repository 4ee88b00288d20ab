"""Serving-tier load test: open/closed-loop generators, QPS + p50/p99.

The benchmark of record for the async micro-batching front
(:mod:`repro.serving`). Three measurements over the SAME heterogeneous
request mix (per-request Dirichlet weights, mixed ``(k, probes)``
execution shapes — the paper's dynamic per-user setting):

``sequential``
    The pre-serving-tier baseline: one-by-one ``Retriever.search`` on a
    fresh facade. This is what concurrent traffic used to get — every
    request pays a full engine dispatch alone.
``closed``
    Closed-loop: ``concurrency`` workers, each submitting its next request
    only after its previous one completes (classic saturation load). The
    headline is achieved QPS vs the sequential baseline — micro-batching
    must actually reach the engine's batched path to win.
``open``
    Open-loop: requests arrive on a fixed-rate schedule *regardless* of
    completions (arrival-rate load, the honest way to measure latency
    under a target QPS — closed loops self-throttle and hide queueing
    collapse). Reports the latency split plus expiry/rejection counts
    when a ``--deadline-ms`` budget or queue bound bites.

Latencies are the per-request server-stamped split
(``queue_wait_s`` / ``compute_s`` — see ``SearchResponse``), so the p99
decomposes into "waited for the window/queue" vs "rode a batch through
the engine". Results land in the ``serving`` section of
``BENCH_query.json`` via ``benchmarks.run``. Off-TPU the fused backend is
interpret-mode (correctness smoke, not a speed claim); entries carry
``platform`` so CPU and TPU rows can never be compared by accident.

``--chaos`` swaps the throughput loops for the fault-injection acceptance
suite: the same closed-loop mix replayed against a fresh server per named
fault profile (:data:`repro.serving.FAULT_PROFILES`), hard-asserting that
every submit resolves, non-degraded answers are id-identical to the
synchronous path, ``exact``/``min_recall`` requests are never silently
degraded, the circuit breaker trips AND recovers under flapping, and the
wedged-replica profiles keep the p99 within 3x the fault-free run.
"""

from __future__ import annotations

import asyncio
import time

import jax
import numpy as np

from repro.core import Retriever, SearchRequest
from repro.launch.serve import build_retriever
from repro.serving import (
    DeadlineExceeded,
    FaultPolicy,
    Overloaded,
    ReplicaUnavailable,
    ResilienceConfig,
    SearchServer,
)

from .common import std_parser

# Heterogeneous execution-shape mix: most traffic at the default operating
# point, a minority shape (deeper k, tighter budget) riding alongside —
# enough to exercise per-shape queues without shattering every batch.
MIX_SHAPES = (
    {"k": 10, "probes": 12},
    {"k": 10, "probes": 12},
    {"k": 10, "probes": 12},
    {"k": 20, "probes": 8},
)

LOADTEST_SIZES = {
    "quick": {"n_docs": 4_000, "n_requests": 192},
    "ts1": {"n_docs": 20_000, "n_requests": 1_024},
    "ts2": {"n_docs": 50_000, "n_requests": 2_048},
}


def make_mix(n_docs: int, spec, n: int, seed: int = 0,
             backend: str | None = None) -> list[SearchRequest]:
    """n unique more-like-this requests cycling through MIX_SHAPES."""
    rng = np.random.default_rng(seed)
    qids = rng.choice(n_docs, size=min(n, n_docs), replace=False)
    w = rng.dirichlet([1.0] * spec.s, size=n).astype(np.float32)
    return [
        SearchRequest(
            like=int(qids[i % len(qids)]),
            weights=dict(zip(spec.names, map(float, w[i]))),
            backend=backend,
            **MIX_SHAPES[i % len(MIX_SHAPES)],
        )
        for i in range(n)
    ]


def _quantiles(xs) -> tuple[float, float]:
    """(p50, p99) in milliseconds."""
    if not len(xs):
        return 0.0, 0.0
    a = np.asarray(xs, np.float64) * 1e3
    return float(np.percentile(a, 50)), float(np.percentile(a, 99))


# ------------------------------------------------------------------ baselines
def sequential_baseline(retriever: Retriever,
                        requests: list[SearchRequest]) -> dict:
    """One-by-one synchronous search: the no-serving-tier reference."""
    lat = []
    t_start = time.perf_counter()
    for req in requests:
        t0 = time.perf_counter()
        retriever.search(req)
        lat.append(time.perf_counter() - t0)
    wall = time.perf_counter() - t_start
    p50, p99 = _quantiles(lat)
    return {
        "mode": "sequential",
        "n_requests": len(requests),
        "qps": round(len(requests) / wall, 2),
        "p50_ms": round(p50, 3),
        "p99_ms": round(p99, 3),
    }


# ------------------------------------------------------------ loop generators
async def closed_loop(server: SearchServer, requests: list[SearchRequest],
                      concurrency: int,
                      deadline_s: float | None = None) -> dict:
    """Fixed-concurrency workers, next request only after the last answer."""
    results: list = []
    errors = {"expired": 0, "rejected": 0}
    cursor = iter(requests)
    t_start = time.perf_counter()

    async def worker():
        for req in cursor:
            try:
                resp = await server.submit(req, deadline_s=deadline_s)
                results.append(resp)
            except DeadlineExceeded:
                errors["expired"] += 1
            except Overloaded:
                errors["rejected"] += 1

    await asyncio.gather(
        *(worker() for _ in range(min(concurrency, len(requests))))
    )
    wall = time.perf_counter() - t_start
    return _loop_report("closed", results, errors, wall,
                        concurrency=concurrency)


async def open_loop(server: SearchServer, requests: list[SearchRequest],
                    rate_qps: float,
                    deadline_s: float | None = None) -> dict:
    """Fixed arrival rate: submit on schedule, completions be damned."""
    results: list = []
    errors = {"expired": 0, "rejected": 0}
    loop = asyncio.get_running_loop()

    async def one(req):
        try:
            results.append(await server.submit(req, deadline_s=deadline_s))
        except DeadlineExceeded:
            errors["expired"] += 1
        except Overloaded:
            errors["rejected"] += 1

    t_start = time.perf_counter()
    t0 = loop.time()
    tasks = []
    for i, req in enumerate(requests):
        delay = (t0 + i / rate_qps) - loop.time()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.create_task(one(req)))
    await asyncio.gather(*tasks)
    wall = time.perf_counter() - t_start
    return _loop_report("open", results, errors, wall, rate_qps=rate_qps)


def _loop_report(mode: str, results, errors, wall, **extra) -> dict:
    lat = [r.latency_s for r in results]
    qwait = [r.queue_wait_s for r in results]
    comp = [r.compute_s for r in results]
    batch = [r.batch_size for r in results]
    p50, p99 = _quantiles(lat)
    qw50, qw99 = _quantiles(qwait)
    c50, c99 = _quantiles(comp)
    return {
        "mode": mode,
        "n_requests": len(results) + sum(errors.values()),
        "completed": len(results),
        "qps": round(len(results) / wall, 2) if wall > 0 else 0.0,
        "p50_ms": round(p50, 3),
        "p99_ms": round(p99, 3),
        "queue_wait_p50_ms": round(qw50, 3),
        "queue_wait_p99_ms": round(qw99, 3),
        "compute_p50_ms": round(c50, 3),
        "compute_p99_ms": round(c99, 3),
        "mean_batch": round(float(np.mean(batch)), 2) if batch else 0.0,
        "expired": errors["expired"],
        "rejected": errors["rejected"],
        **extra,
    }


# ------------------------------------------------------------ chaos harness
# The fault-injection acceptance run (``--chaos``): the SAME closed-loop mix
# per named fault profile, with hard assertions instead of vibes — every
# submit resolves (answer or typed failure, nothing blocks), every completed
# non-degraded response is id-identical to the synchronous path, degraded
# answers are stamped and their recall cost measured, the breaker trips AND
# recovers under flapping, and the hang profiles keep the closed-loop p99
# within 3x the fault-free run (with a one-cold-timeout absolute floor so a
# CI box's noisy fault-free p50 cannot flake the ratio).

CHAOS_PROFILES = ("transient", "slow", "flap", "storm", "hang_flap")


def _chaos_knobs(comp_p99_s: float, seed: int):
    """Derive the chaos timeout/hang knobs from observed healthy compute.

    Absolute knobs cannot work across platforms: one engine call is ~1 s
    on the CPU reference backend and ~1 ms fused-on-TPU, and compute
    under N concurrent replica dispatches on a CPU box runs several times
    slower than the same call alone — a timeout below that contended
    reality turns every healthy dispatch into a timeout storm that
    cascades through retries (measured, not hypothetical). So the
    fault-free profile runs first, effectively timeout-free, and its
    CONTENDED compute p99 sizes everything else: the timeout floor at
    that p99 (honest-but-slow is never a fault), the ceiling at 3x it,
    the injected hang at 2x the ceiling (a wedged call always overshoots
    the timeout), and the p99 acceptance floor at one ceiling + retry.
    """
    floor_s = max(0.05, comp_p99_s)
    ceil_s = max(0.75, 3.0 * comp_p99_s)
    cfg = ResilienceConfig(
        timeout_floor_s=floor_s, timeout_ceil_s=ceil_s,
        breaker_cooldown_s=0.5, backoff_base_s=0.005, seed=seed,
    )
    return cfg, max(2.0, 2.0 * ceil_s)


def _chaos_policy(profile: str, seed: int, hang_s: float) -> FaultPolicy:
    """Named profile with its hang duration rescaled to the platform."""
    import dataclasses

    from repro.serving import FAULT_PROFILES

    profiles = {
        idx: (dataclasses.replace(p, hang_s=hang_s) if p.hang_p else p)
        for idx, p in FAULT_PROFILES[profile].items()
    }
    return FaultPolicy(profiles, seed=seed, name=profile)


def _precompile_degraded(base: Retriever, requests) -> None:
    """Compile the traces the degradation ladder can reach.

    Degraded dispatches run at stepped-down probe budgets the healthy
    traffic never uses; without this, the FIRST degraded batch of a chaos
    run pays an XLA compile that dwarfs the attempt timeout and reads as
    yet another fault. One synchronous pass per reachable rung keeps the
    measured chaos runs about scheduling, not compilation.
    """
    from repro.serving import degrade_request

    t, kk = base.index.counts.shape
    warm, seen = [], set()
    for req in requests:
        shape = base.exec_shape(req)
        for rung in (1, 2):
            try:
                dreq, _ = degrade_request(
                    req, shape, rung=rung, ladder=base.index.ladder,
                    total_probes=t * kk, n_clusterings=t,
                    relax_floors=False,
                )
            except ValueError:
                continue  # guaranteed request: never degraded, no trace
            dshape = base.exec_shape(dreq)
            if dshape not in seen:
                seen.add(dshape)
                warm.append(dreq)
    if warm:
        base.search(warm)
        base._flush_request_caches()


async def chaos_closed_loop(server: SearchServer,
                            requests: list[SearchRequest],
                            concurrency: int) -> tuple[dict, dict, float]:
    """Closed loop that keeps per-request identity and typed failures.

    Returns ``(results, errors, wall)`` with ``results[i]`` the response for
    ``requests[i]`` (only completed ones present) and ``errors`` counting
    typed failures — under chaos a typed failure is an ACCEPTABLE outcome,
    silence is not.
    """
    results: dict[int, object] = {}
    errors = {"expired": 0, "rejected": 0, "unavailable": 0}
    cursor = iter(enumerate(requests))
    t_start = time.perf_counter()

    async def worker():
        for i, req in cursor:
            try:
                results[i] = await server.submit(req)
            except DeadlineExceeded:
                errors["expired"] += 1
            except ReplicaUnavailable:
                errors["unavailable"] += 1
            except Overloaded:
                errors["rejected"] += 1

    await asyncio.gather(
        *(worker() for _ in range(min(concurrency, len(requests))))
    )
    return results, errors, time.perf_counter() - t_start


async def _chaos_profile_run(retriever, requests, *, profile, cfg, policy,
                             concurrency, window_s, replicas,
                             max_queue_depth, max_batch=8) -> dict:
    """One profile through a fresh server: warmup, measure, snapshot.

    ``max_batch`` is capped low on purpose: fault handling is per
    DISPATCH, and a server that coalesces the whole closed loop into
    three giant batches gives the breaker/retry/hedge machinery almost
    nothing to act on.
    """
    async with SearchServer(
        retriever, window_s=window_s, replicas=replicas,
        max_batch=max_batch, max_queue_depth=max_queue_depth,
        resilience=cfg, fault_policy=policy,
    ) as server:
        # Warm each shape twice through the live (possibly faulty) pool:
        # compiles the traces and seeds the per-shape compute p99 the
        # timeout/hedge policy is derived from.
        shapes_seen = {}
        for req in requests:
            shapes_seen.setdefault(retriever.exec_shape(req), req)
        for req in shapes_seen.values():
            warm = [req] * min(server.max_batch, len(requests))
            for _ in range(2):
                await asyncio.gather(
                    *(server.submit(r) for r in warm),
                    return_exceptions=True,  # typed failures ok in warmup
                )
        for rep in server.pool.replicas:
            rep._flush_request_caches()
        results, errors, wall = await chaos_closed_loop(
            server, requests, concurrency
        )
        stats = server.stats.snapshot()
        health = server.pool.health_snapshot()
    lat = [r.latency_s for r in results.values()]
    p50, p99 = _quantiles(lat)
    return {
        "mode": "chaos",
        "profile": profile,
        "n_requests": len(requests),
        "completed": len(results),
        "qps": round(len(results) / wall, 2) if wall > 0 else 0.0,
        "p50_ms": round(p50, 3),
        "p99_ms": round(p99, 3),
        "wall_s": round(wall, 2),
        **errors,
        "retries": stats["retries"],
        "timeouts": stats["timeouts"],
        "hedges": stats["hedges"],
        "hedge_wins": stats["hedge_wins"],
        "degraded": stats["degraded"],
        "budget_exhausted": stats["budget_exhausted"],
        "breaker_trips": stats["breaker_trips"],
        "breaker_recoveries": stats["breaker_recoveries"],
        "_results": results,
        "_health": health,
    }


def _chaos_verify(entry: dict, requests, expected,
                  p99_free_ms: float | None) -> dict:
    """Apply the hard acceptance assertions; fold parity/recall into entry."""
    profile = entry["profile"]
    results = entry.pop("_results")
    health = entry.pop("_health")
    entry["breaker_states"] = {h["idx"]: h["state"] for h in health}
    entry["replica_dispatches"] = {
        h["idx"]: f"{h['successes']}ok/{h['failures']}fail[{h['state']}]"
        for h in health
    }
    n = len(requests)
    resolved = entry["completed"] + sum(
        entry[key] for key in ("expired", "rejected", "unavailable")
    )
    if resolved != n:
        raise SystemExit(
            f"[chaos:{profile}] {n - resolved} of {n} submits vanished — "
            f"every request must resolve to an answer or a typed failure"
        )
    parity_bad, guard_degraded = 0, 0
    deg_recall: list[float] = []
    labels: dict[str, int] = {}
    for i, resp in results.items():
        want = expected[i]
        if resp.degraded:
            if requests[i].min_recall is not None or requests[i].exact:
                guard_degraded += 1
            got = set(map(int, resp.doc_ids))
            truth = set(map(int, want.doc_ids))
            deg_recall.append(len(got & truth) / max(1, len(truth)))
            for lab in resp.degradation:
                key = lab.split(":", 1)[0]
                labels[key] = labels.get(key, 0) + 1
        elif (list(resp.doc_ids) != list(want.doc_ids)
              or not np.allclose(resp.scores, want.scores,
                                 rtol=1e-5, atol=1e-6)):
            parity_bad += 1
    if parity_bad:
        raise SystemExit(
            f"[chaos:{profile}] {parity_bad} non-degraded responses differ "
            f"from the synchronous path — retries/hedging may change "
            f"latency, never answers"
        )
    if guard_degraded:
        raise SystemExit(
            f"[chaos:{profile}] {guard_degraded} exact/min_recall responses "
            f"came back degraded=True — guaranteed requests must fail "
            f"typed, never silently downgrade"
        )
    if profile in ("flap", "hang_flap"):
        if not (entry["breaker_trips"] >= 1
                and entry["breaker_recoveries"] >= 1):
            raise SystemExit(
                f"[chaos:{profile}] breaker did not trip AND recover under "
                f"flapping (trips={entry['breaker_trips']}, "
                f"recoveries={entry['breaker_recoveries']}); half-open "
                f"probing is broken"
            )
    if profile in ("hang", "hang_flap") and p99_free_ms:
        # 3x the fault-free p99, floored at one cold attempt-timeout +
        # retry (the bound a single wedged dispatch can cost a request)
        bound_ms = max(3.0 * p99_free_ms,
                       1e3 * entry["timeout_ceil_s"] + 250.0)
        if entry["p99_ms"] > bound_ms:
            raise SystemExit(
                f"[chaos:{profile}] closed-loop p99 {entry['p99_ms']:.0f} ms "
                f"exceeds the bound {bound_ms:.0f} ms "
                f"(fault-free p99 {p99_free_ms:.0f} ms)"
            )
        entry["p99_vs_fault_free"] = round(
            entry["p99_ms"] / p99_free_ms, 2
        )
    entry["parity_violations"] = 0
    entry["degraded_recall_mean"] = (
        round(float(np.mean(deg_recall)), 3) if deg_recall else None
    )
    entry["degradation_kinds"] = labels
    return entry


def run_chaos(scale: str = "quick", seed: int = 0, *,
              backend: str = "reference", concurrency: int = 32,
              window_s: float = 0.002, replicas: int = 4,
              max_queue_depth: int = 256, profiles=None,
              n_docs: int | None = None,
              n_requests: int | None = None) -> list[dict]:
    """Chaos acceptance run: every named fault profile, hard-asserted.

    Builds one calibrated index, computes the synchronous ground-truth
    answer for every request in the mix, then replays the SAME closed-loop
    mix against a fresh fault-injected server per profile. A run that
    returns (exit 0) has proved: no lost submits, no silent wrong answers,
    no silent downgrades of guaranteed requests, breaker trip + recovery
    under flapping, and a bounded p99 with a wedged replica in the pool.
    """
    sz = LOADTEST_SIZES[scale]
    n_docs = n_docs or sz["n_docs"]
    n_requests = n_requests or sz["n_requests"]
    profiles = tuple(profiles or CHAOS_PROFILES)
    k = MIX_SHAPES[0]["k"]

    retriever, docs, spec = build_retriever(
        n_docs, backend=backend, seed=seed, calibrate=True,
    )
    requests = make_mix(n_docs, spec, n_requests, seed=seed)
    # Guard requests: a recall floor the ladder can honour — these must be
    # served at full fidelity or fail typed, NEVER silently degraded.
    rng = np.random.default_rng(seed + 1)
    for i in range(0, len(requests), 16):
        qid = int(rng.integers(n_docs))
        requests[i] = SearchRequest(like=qid, k=k, probes=12,
                                    min_recall=0.85)
    served = retriever.backend
    platform = jax.default_backend()
    print(f"\n# Chaos loadtest — fault-injected serving acceptance "
          f"(n={n_docs}, {n_requests} requests, {replicas} replicas, "
          f"backend={served}, platform={platform})")

    # Synchronous ground truth on a fresh facade (one batched call; the
    # min_recall guards calibrate the planner ladder here, once).
    base = Retriever(retriever.index, backend=served,
                     default_probes=retriever.default_probes,
                     calibrate=True)
    expected = base.search(requests)
    _precompile_degraded(base, requests)
    retriever._flush_request_caches()

    async def _all():
        # Fault-free pass first, with an effectively-unbounded timeout (a
        # cold XLA compile must read as slow, not faulty): it is the
        # parity/latency reference, and its compute p99 sizes the chaos
        # timeout knobs for the fault runs.
        free = await _chaos_profile_run(
            retriever, requests, profile="none",
            cfg=ResilienceConfig(seed=seed, timeout_floor_s=60.0,
                                 timeout_ceil_s=60.0, hedge=False),
            policy=None,
            concurrency=concurrency, window_s=window_s, replicas=replicas,
            max_queue_depth=max_queue_depth,
        )
        comp = [r.compute_s for r in free["_results"].values()]
        comp_p99 = float(np.percentile(comp, 99)) if comp else 1.0
        cfg, hang_s = _chaos_knobs(comp_p99, seed)
        print(f"chaos knobs from fault-free compute p99 "
              f"{comp_p99 * 1e3:.0f} ms: timeout ceiling "
              f"{cfg.timeout_ceil_s:.2f} s, injected hang {hang_s:.1f} s")
        entries = [free]
        for profile in profiles:
            entries.append(await _chaos_profile_run(
                retriever, requests, profile=profile, cfg=cfg,
                policy=_chaos_policy(profile, seed, hang_s),
                concurrency=concurrency, window_s=window_s,
                replicas=replicas, max_queue_depth=max_queue_depth,
            ))
        for entry in entries:
            entry["timeout_ceil_s"] = (
                None if entry["profile"] == "none" else cfg.timeout_ceil_s
            )
        return entries

    entries = asyncio.run(_all())
    p99_free_ms = entries[0]["p99_ms"]
    failures = []
    for entry in entries:
        try:
            _chaos_verify(entry, requests, expected,
                          None if entry["profile"] == "none"
                          else p99_free_ms)
        except SystemExit as e:
            failures.append(str(e))
        extra = ""
        if entry.get("degraded"):
            extra = (f", degraded={entry['degraded']} "
                     f"(recall {entry.get('degraded_recall_mean')})")
        print(f"chaos[{entry['profile']:>9}]: {entry['qps']:7.1f} QPS, "
              f"p50/p99 {entry['p50_ms']:6.1f}/{entry['p99_ms']:7.1f} ms, "
              f"retries={entry['retries']} timeouts={entry['timeouts']} "
              f"hedges={entry['hedges']}/{entry['hedge_wins']} "
              f"trips={entry['breaker_trips']}/"
              f"{entry['breaker_recoveries']} "
              f"unavailable={entry['unavailable']}{extra}")
        if "replica_dispatches" in entry:
            print(f"      replicas: {entry['replica_dispatches']}")
    if failures:
        raise SystemExit("\n".join(failures))
    print("chaos: all profiles passed parity, honesty, breaker and p99 "
          "assertions")
    labels = {"backend": served, "platform": platform}
    for e in entries:
        for key, val in labels.items():
            e.setdefault(key, val)
    return entries


# ----------------------------------------------------------------- the runner
async def _run_async(retriever, requests, *, concurrency, rate_qps,
                     window_s, replicas, max_queue_depth, deadline_s,
                     modes) -> list[dict]:
    out = []
    async with SearchServer(
        retriever, window_s=window_s, replicas=replicas,
        max_queue_depth=max_queue_depth,
    ) as server:
        # Warm the dominant batched traces (full max_batch per shape) so
        # the measured loops price serving, not XLA compilation. The
        # sequential baseline gets the same courtesy from its own warmup.
        shapes_seen = {}
        for req in requests:
            shapes_seen.setdefault(retriever.exec_shape(req), req)
        for req in shapes_seen.values():
            warm = [req] * min(server.max_batch, len(requests))
            await asyncio.gather(*(server.submit(r) for r in warm))
        def flush_caches():
            # the warmup (and each measured mode) answers requests FROM the
            # mix: flush the facade caches so the next mode's answers come
            # from the engine, not memoisation
            for replica in server.pool.replicas:
                replica._flush_request_caches()

        flush_caches()
        if "closed" in modes:
            entry = await closed_loop(server, requests, concurrency,
                                      deadline_s)
            entry.update(window_ms=window_s * 1e3,
                         max_batch=server.max_batch, replicas=replicas)
            out.append(entry)
        if "open" in modes:
            flush_caches()
            closed_qps = next(
                (e["qps"] for e in out if e["mode"] == "closed"), None
            )
            rate = rate_qps or (
                round(0.8 * closed_qps, 1) if closed_qps else 100.0
            )
            entry = await open_loop(server, requests, rate, deadline_s)
            entry.update(window_ms=window_s * 1e3,
                         max_batch=server.max_batch, replicas=replicas)
            out.append(entry)
        out_stats = server.stats.snapshot()
    out.append({"mode": "server_stats", **out_stats})
    return out


def run(scale: str = "quick", seed: int = 0, *, backend: str = "auto",
        pack_dtype: str | None = None, concurrency: int = 64,
        rate_qps: float | None = None, window_s: float = 0.002,
        replicas: int = 1, max_queue_depth: int = 256,
        deadline_s: float | None = None, n_docs: int | None = None,
        n_requests: int | None = None,
        modes=("closed", "open")) -> list[dict]:
    """Build, load-test, return labelled entries for BENCH_query.json.

    ``pack_dtype`` sets the bucket-major storage precision the fused and
    sharded backends serve from (bf16/int8 shrink the packed bytes); every
    entry is labelled with it (plus ``n_shards`` for the sharded backend)
    so quantised serving rows never masquerade as fp32 ones.
    """
    sz = LOADTEST_SIZES[scale]
    n_docs = n_docs or sz["n_docs"]
    n_requests = n_requests or sz["n_requests"]

    retriever, docs, spec = build_retriever(
        n_docs, backend=backend, seed=seed, pack_dtype=pack_dtype,
    )
    if retriever.backend == "fused":
        retriever.index.ensure_bucket_major()
    requests = make_mix(n_docs, spec, n_requests, seed=seed)
    served = retriever.backend
    platform = jax.default_backend()
    print(f"\n# Loadtest — async serving tier vs sequential baseline "
          f"(n={n_docs}, {n_requests} requests, backend={served}, "
          f"pack_dtype={pack_dtype or 'float32'}, "
          f"platform={platform}; fused/sharded interpret off-TPU)")

    # Sequential baseline on a FRESH facade: the served retriever's
    # request/response caches must not answer for the engine.
    base = Retriever(retriever.index, backend=served,
                     default_probes=retriever.default_probes)
    warm_shapes = {}
    for req in requests:
        warm_shapes.setdefault(base.exec_shape(req), req)
    for req in warm_shapes.values():   # compile the single-request traces
        base.search(req)
    base._flush_request_caches()
    seq = sequential_baseline(base, requests)
    print(f"sequential: {seq['qps']:.1f} QPS, "
          f"p50/p99 {seq['p50_ms']:.1f}/{seq['p99_ms']:.1f} ms")

    entries = asyncio.run(_run_async(
        retriever, requests, concurrency=concurrency, rate_qps=rate_qps,
        window_s=window_s, replicas=replicas,
        max_queue_depth=max_queue_depth, deadline_s=deadline_s,
        modes=modes,
    ))
    for e in entries:
        if e["mode"] == "closed":
            e["speedup_vs_sequential"] = round(e["qps"] / seq["qps"], 2)
            print(f"closed-loop (c={concurrency}): {e['qps']:.1f} QPS "
                  f"({e['speedup_vs_sequential']:.2f}x sequential), "
                  f"p50/p99 {e['p50_ms']:.1f}/{e['p99_ms']:.1f} ms "
                  f"(wait {e['queue_wait_p50_ms']:.1f}/"
                  f"{e['queue_wait_p99_ms']:.1f}, compute "
                  f"{e['compute_p50_ms']:.1f}/{e['compute_p99_ms']:.1f}), "
                  f"mean batch {e['mean_batch']:.1f}")
        elif e["mode"] == "open":
            print(f"open-loop ({e['rate_qps']:.1f} QPS offered): "
                  f"{e['qps']:.1f} achieved, p50/p99 {e['p50_ms']:.1f}/"
                  f"{e['p99_ms']:.1f} ms, expired={e['expired']} "
                  f"rejected={e['rejected']}")
    labels = {"backend": served, "platform": platform,
              "pack_dtype": pack_dtype or "float32"}
    if served == "sharded":
        labels["n_shards"] = jax.device_count()
    entries.insert(0, seq)
    for e in entries:
        for key, val in labels.items():
            e.setdefault(key, val)
    return entries


def main():
    ap = std_parser(__doc__)
    ap.add_argument("--backend", default="auto")
    ap.add_argument("--chaos", action="store_true",
                    help="run the fault-injection acceptance suite instead "
                         "of the throughput loops: every named fault "
                         "profile through a fresh fault-injected server, "
                         "hard-asserting parity, degradation honesty, "
                         "breaker trip+recovery, and the hang-profile p99 "
                         "bound (exit 1 on any violation)")
    ap.add_argument("--profiles", default=",".join(CHAOS_PROFILES),
                    help="--chaos: comma-separated fault profile names")
    ap.add_argument("--pack-dtype", default=None,
                    choices=[None, "float32", "bfloat16", "int8"],
                    help="bucket-major storage precision the fused/sharded "
                         "backend serves from (bf16 halves, int8 quarters "
                         "the packed bytes)")
    ap.add_argument("--docs", type=int, default=None,
                    help="override the scale's corpus size")
    ap.add_argument("--requests", type=int, default=None,
                    help="override the scale's request count")
    ap.add_argument("--concurrency", type=int, default=64,
                    help="closed-loop worker count")
    ap.add_argument("--rate", type=float, default=None,
                    help="open-loop arrival rate in QPS (default: 0.8x the "
                         "measured closed-loop QPS)")
    ap.add_argument("--window-ms", type=float, default=2.0,
                    help="micro-batch window")
    ap.add_argument("--replicas", type=int, default=1,
                    help="parallel dispatch slots (ReplicaPool size)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline (exercises expiry under "
                         "open-loop overload)")
    ap.add_argument("--mode", default="both",
                    choices=("closed", "open", "both"))
    args = ap.parse_args()
    if args.chaos:
        backend = "reference" if args.backend == "auto" else args.backend
        run_chaos(args.scale, args.seed, backend=backend,
                  concurrency=min(args.concurrency, 32),
                  window_s=args.window_ms / 1e3,
                  replicas=max(args.replicas, 4),
                  profiles=tuple(p for p in args.profiles.split(",") if p),
                  n_docs=args.docs, n_requests=args.requests)
        return
    modes = ("closed", "open") if args.mode == "both" else (args.mode,)
    run(args.scale, args.seed, backend=args.backend,
        pack_dtype=(
            None if args.pack_dtype in (None, "float32")
            else args.pack_dtype
        ),
        concurrency=args.concurrency, rate_qps=args.rate,
        window_s=args.window_ms / 1e3, replicas=args.replicas,
        deadline_s=(
            None if args.deadline_ms is None else args.deadline_ms / 1e3
        ),
        n_docs=args.docs, n_requests=args.requests, modes=modes)


if __name__ == "__main__":
    main()
