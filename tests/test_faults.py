"""Fault-tolerant serving (repro.serving.health / .faults): pure policy
unit tests with frozen float clocks (breaker, retry budget, degradation
ladder), determinism of the chaos injector, live SearchServer tests under
injected faults (retry-on-transient, wedged-replica timeout, breaker trip
AND recovery, typed failure for guaranteed requests), and a hypothesis
property that degraded responses still satisfy the core API invariants."""

import asyncio

import jax
import numpy as np
import pytest

from repro.core import (
    ExecShape,
    FieldSpec,
    Retriever,
    SearchRequest,
    normalize_fields,
)
from repro.core.calibrate import ProbeLadder
from repro.serving import (
    FAULT_PROFILES,
    CircuitBreaker,
    FaultPolicy,
    FaultProfile,
    InjectedFault,
    ReplicaUnavailable,
    ResilienceConfig,
    RetryBudget,
    SearchServer,
    degrade_batch,
    degrade_request,
)
from repro.serving.health import ReplicaHealth

SHAPE = ExecShape("reference", 6, 5, None)


# ----------------------------------------------------------- circuit breaker
def test_breaker_trips_at_threshold_and_cools_down():
    b = CircuitBreaker(failures=3, cooldown_s=1.0)
    assert b.state == "closed" and b.allow(now=0.0)
    assert not b.record_failure(now=0.1)
    assert not b.record_failure(now=0.2)
    assert b.record_failure(now=0.3)              # third consecutive: TRIP
    assert b.state == "open" and b.trips == 1
    assert not b.allow(now=0.5)                   # cooling down
    assert not b.would_allow(now=0.5)
    # cooldown elapsed: exactly ONE half-open probe is admitted
    assert b.would_allow(now=1.31)
    assert b.allow(now=1.31) and b.state == "half_open"
    assert not b.allow(now=1.32)                  # second probe refused
    assert b.record_success(now=1.4)              # probe ok: RECOVERY
    assert b.state == "closed" and b.recoveries == 1
    assert b.allow(now=1.5)


def test_breaker_failed_probe_reopens():
    b = CircuitBreaker(failures=1, cooldown_s=1.0)
    assert b.record_failure(now=0.0) and b.state == "open"
    assert b.allow(now=1.0) and b.state == "half_open"
    assert b.record_failure(now=1.1)              # probe failed: re-trip
    assert b.state == "open" and b.trips == 2 and b.recoveries == 0
    assert not b.allow(now=1.5)                   # fresh cooldown from 1.1
    assert b.allow(now=2.1)


def test_breaker_would_allow_is_pure():
    """Selection peeks with would_allow; only allow commits the probe slot."""
    b = CircuitBreaker(failures=1, cooldown_s=0.5)
    b.record_failure(now=0.0)
    assert b.would_allow(now=0.6) and b.state == "open"   # no transition
    assert b.would_allow(now=0.6)                         # still idempotent
    assert b.allow(now=0.6) and b.state == "half_open"
    assert not b.would_allow(now=0.6)                     # probe in flight
    # a mid-flight success while closed resets the consecutive counter
    b.record_success(now=0.7)
    b.record_failure(now=0.8)
    b.record_success(now=0.9)
    assert b.consecutive == 0


def test_retry_budget_drains_and_refills():
    budget = RetryBudget(ratio=0.5, cap=2.0)
    assert budget.try_spend() and budget.try_spend()      # starts full
    assert not budget.try_spend()                         # drained: the brake
    budget.on_success()
    assert budget.tokens == pytest.approx(0.5)
    assert not budget.try_spend()                         # half a token != one
    budget.on_success()
    assert budget.try_spend() and not budget.try_spend()
    for _ in range(10):
        budget.on_success()
    assert budget.tokens == pytest.approx(2.0)            # capped


def test_resilience_config_timeout_and_backoff():
    cfg = ResilienceConfig(
        timeout_mult=4.0, timeout_floor_s=0.1, timeout_ceil_s=2.0,
        backoff_base_s=0.01, backoff_cap_s=0.04,
    )
    assert cfg.attempt_timeout(None) == 2.0               # no obs: ceiling
    assert cfg.attempt_timeout(0.2) == pytest.approx(0.8)
    assert cfg.attempt_timeout(0.001) == pytest.approx(0.1)   # floor
    assert cfg.attempt_timeout(10.0) == pytest.approx(2.0)    # ceiling
    # capped exponential with a +/-50% jitter window around the base
    assert cfg.backoff(1, jitter=0.5) == pytest.approx(0.01)
    assert cfg.backoff(2, jitter=0.0) == pytest.approx(0.01)  # 0.02 * 0.5
    assert cfg.backoff(5, jitter=0.999) == pytest.approx(0.04 * 1.499)
    with pytest.raises(ValueError, match="timeout_floor_s"):
        ResilienceConfig(timeout_floor_s=1.0, timeout_ceil_s=0.5)
    with pytest.raises(ValueError, match="ewma_alpha"):
        ResilienceConfig(ewma_alpha=0.0)


def test_replica_health_ewma_and_lag():
    h = ReplicaHealth(0, ResilienceConfig(ewma_alpha=0.5))
    assert h.ewma_latency_s is None and h.lag(now=5.0) == 0.0
    h.record_success(now=1.0, latency_s=0.1)
    h.record_success(now=2.0, latency_s=0.3)
    assert h.ewma_latency_s == pytest.approx(0.2)
    h.busy_since = 10.0
    assert h.lag(now=12.5) == pytest.approx(2.5)
    assert h.record_failure(now=3.0, timed_out=True) is False
    snap = h.snapshot(now=12.5)
    assert snap["dispatches"] == 3 and snap["timeouts"] == 1
    assert snap["state"] == "closed" and snap["ewma_ms"] == pytest.approx(200.0)


# -------------------------------------------------------- degradation ladder
def _ladder(probes=(3, 6, 12)):
    return ProbeLadder.from_dict({
        "probes": list(probes),
        "recall": [0.6 + 0.1 * i for i in range(len(probes))],
        "n_clusterings": 3,
        "k_clusters": 16,
    })


def test_degrade_rungs_are_cumulative_and_audited():
    req = SearchRequest(like=0, probes=12, rescore=20)
    shape = ExecShape("reference", 12, 10, 20)
    r1, lab1 = degrade_request(req, shape, rung=1)
    assert r1.rescore is None and r1.probes == 12
    assert lab1 == ("rescore:20->none",)
    r2, lab2 = degrade_request(
        req, shape, rung=2, ladder=_ladder(), total_probes=12,
        n_clusterings=3,
    )
    assert r2.rescore is None and r2.probes == 6      # one calibrated rung
    assert lab2 == ("rescore:20->none", "probes:12->6")
    # no ladder: halve, floored at one probe per clustering
    r3, lab3 = degrade_request(
        SearchRequest(like=0, probes=4), ExecShape("reference", 4, 10, None),
        rung=2, n_clusterings=3,
    )
    assert r3.probes == 3 and lab3 == ("probes:4->3",)
    # nothing left to take away: the request rides as-is, zero labels
    r4, lab4 = degrade_request(
        SearchRequest(like=0, probes=3), ExecShape("reference", 3, 10, None),
        rung=2, ladder=_ladder(), n_clusterings=3,
    )
    assert r4 is not None and lab4 == ()


def test_degrade_refuses_guarantees():
    shape = ExecShape("reference", 6, 10, None)
    with pytest.raises(ValueError, match="exact"):
        degrade_request(SearchRequest(like=0, exact=True),
                        ExecShape("reference", 0, 10, None, tier="exact"),
                        rung=1)
    with pytest.raises(ValueError, match="min_recall"):
        degrade_request(SearchRequest(like=0, probes=6, min_recall=0.9),
                        shape, rung=1)
    # relax_floors: the floor is RELAXED, never silently — stamped label
    r, lab = degrade_request(
        SearchRequest(like=0, probes=6, min_recall=0.9), shape,
        rung=1, relax_floors=True,
    )
    assert r.min_recall is None
    assert lab == ("floor:0.9->best-effort",)


def test_degrade_batch_serves_rest_fails_guaranteed():
    shape = ExecShape("reference", 6, 10, None)
    reqs = [
        SearchRequest(like=0, probes=6, rescore=10),
        SearchRequest(like=1, probes=6, min_recall=0.9),
        SearchRequest(like=2, probes=6),
    ]
    shape = ExecShape("reference", 6, 10, 10)
    out, labels, refused = degrade_batch(reqs, shape, rung=1)
    assert refused == [1]
    assert out[1] is reqs[1] and labels[1] == ()      # untouched, typed later
    assert out[0].rescore is None and labels[0]
    assert len(out) == len(labels) == 3               # positions preserved


# ------------------------------------------------------------ fault injector
def test_fault_profile_validation_and_describe():
    with pytest.raises(ValueError, match="error_p"):
        FaultProfile(error_p=1.5)
    with pytest.raises(ValueError, match="flap_run"):
        FaultProfile(flap_run=-1)
    assert FaultProfile().benign and FaultProfile().describe() == "healthy"
    d = FaultProfile(hang_p=0.5, flap_run=4).describe()
    assert "flap(run=4)" in d and "hang" in d
    with pytest.raises(ValueError, match="unknown fault profile"):
        FaultPolicy.named("nope")
    policy = FaultPolicy.named("hang_flap", seed=7)
    assert policy.profile(1).hang_p == 1.0 and policy.profile(2).flap_run == 4
    assert policy.profile(0).benign                   # primary stays healthy
    assert "r1=" in policy.describe()


def _fault_trace(policy: FaultPolicy, idx: int, n: int) -> list[str]:
    """Outcome sequence of n wrapped calls ('ok' or the fault message)."""
    calls = []
    wrapped = policy.wrap(idx, lambda: calls.append("ok") or "ok")
    out = []
    for _ in range(n):
        try:
            wrapped()
            out.append("ok")
        except InjectedFault as e:
            out.append(str(e))
    return out


def test_fault_injection_is_deterministic():
    a = _fault_trace(FaultPolicy({1: FaultProfile(error_p=0.5)}, seed=3), 1, 40)
    b = _fault_trace(FaultPolicy({1: FaultProfile(error_p=0.5)}, seed=3), 1, 40)
    c = _fault_trace(FaultPolicy({1: FaultProfile(error_p=0.5)}, seed=4), 1, 40)
    assert a == b                       # same seed: same fault sequence
    assert a != c                       # distinct stream per seed
    assert any(o == "ok" for o in a) and any("error" in o for o in a)
    # flapping is by call index, no RNG: runs of flap_run good then bad
    t = _fault_trace(FaultPolicy({1: FaultProfile(flap_run=2)}, seed=0), 1, 8)
    assert ["ok" if o == "ok" else "bad" for o in t] == [
        "ok", "ok", "bad", "bad", "ok", "ok", "bad", "bad",
    ]
    # a benign profile is not wrapped at all
    policy = FaultPolicy({1: FaultProfile()})
    fn = lambda: "x"  # noqa: E731
    assert policy.wrap(1, fn) is fn


# --------------------------------------------------------------- live chaos
@pytest.fixture(scope="module")
def retriever():
    spec = FieldSpec(names=("title", "authors", "abstract"),
                     dims=(32, 32, 64))
    x = jax.random.normal(jax.random.PRNGKey(23), (512, spec.total_dim))
    docs = normalize_fields(x, spec)
    r = Retriever.build(
        docs, spec, 16, n_clusterings=3, method="fpf",
        key=jax.random.PRNGKey(0), backend="reference",
    )
    # warm the trace so live fault tests measure dispatch policy, not XLA
    r.search([SearchRequest(like=i, probes=6, k=5) for i in range(4)])
    return r


def _requests(n, seed=0, **shape):
    rng = np.random.default_rng(seed)
    qids = rng.choice(512, n, replace=False)
    return [SearchRequest(like=int(q), probes=6, k=5, **shape)
            for q in qids]


def _serve(retriever, requests, *, policy, cfg, replicas=2, max_batch=2,
           return_exceptions=False):
    async def go():
        async with SearchServer(
            retriever, window_s=0.002, max_batch=max_batch,
            replicas=replicas, resilience=cfg, fault_policy=policy,
        ) as server:
            resps = await asyncio.gather(
                *(server.submit(r) for r in requests),
                return_exceptions=return_exceptions,
            )
            return resps, server.stats.snapshot(), server.pool.health_snapshot()

    return asyncio.run(go())


def test_pool_pick_skips_trials_when_budget_dry(retriever):
    """A half-open trial's failure costs the batch a retry, so a dry
    retry budget (probe_ok=False) must steer the pick to a closed-breaker
    replica — unless NO closed replica exists, when someone must probe
    anyway or the pool deadlocks."""
    from repro.serving.server import ReplicaPool

    pool = ReplicaPool(
        retriever, 2,
        config=ResilienceConfig(breaker_cooldown_s=0.0, breaker_failures=3),
    )
    bad = pool.entries[0].health.breaker
    for _ in range(3):
        bad.record_failure(0.0)
    assert bad.state == "open"
    # cooled down (cooldown 0): the trial normally wins the pick outright
    assert pool._pick(1.0, frozenset()) is pool.entries[0]
    # dry budget: the healthy closed replica is picked instead
    assert pool._pick(1.0, frozenset(), probe_ok=False) is pool.entries[1]
    # every breaker open: probe even with a dry budget (progress beats
    # stranding — an un-probed pool would never close any circuit)
    other = pool.entries[1].health.breaker
    for _ in range(3):
        other.record_failure(1.0)
    assert pool._pick(2.0, frozenset(), probe_ok=False) is not None


def test_transient_errors_retried_to_parity(retriever):
    """Replica 1 fails EVERY dispatch; retries land on replica 0 and every
    response still matches the synchronous path id-for-id."""
    requests = _requests(10, seed=5)
    resps, snap, health = _serve(
        retriever, requests,
        policy=FaultPolicy({1: FaultProfile(error_p=1.0)}, seed=0),
        # generous timeout floor: injected errors raise instantly, and a
        # tight adaptive timeout could false-trip under CI contention
        cfg=ResilienceConfig(seed=0, hedge=False, breaker_cooldown_s=30.0,
                             timeout_floor_s=5.0),
    )
    solo = Retriever(retriever.index, backend="reference")
    for resp, req in zip(resps, requests):
        ref = solo.search(req)
        assert np.array_equal(resp.doc_ids, ref.doc_ids)
        np.testing.assert_allclose(resp.scores, ref.scores, atol=1e-6)
        assert not resp.degraded
    assert snap["completed"] == 10 and snap["failed"] == 0
    assert snap["retries"] >= 1                     # r1's failures re-dispatch
    h1 = health[1]
    assert h1["failures"] >= 1 and h1["successes"] == 0
    # three consecutive failures tripped r1's breaker; long cooldown keeps
    # it open so the tail of the run never touched the bad replica again
    assert snap["breaker_trips"] >= 1 and h1["state"] == "open"


def test_wedged_replica_times_out_and_retries(retriever):
    """A hung dispatch must NOT block its batch: the attempt times out,
    the batch retries elsewhere, and the response beats the hang."""
    requests = _requests(6, seed=6)
    resps, snap, health = _serve(
        retriever, requests,
        policy=FaultPolicy({1: FaultProfile(hang_p=1.0, hang_s=8.0)}, seed=0),
        cfg=ResilienceConfig(
            seed=0, hedge=False, timeout_floor_s=0.75, timeout_ceil_s=0.75,
            breaker_cooldown_s=30.0,
        ),
    )
    assert snap["completed"] == 6 and snap["failed"] == 0
    assert snap["timeouts"] >= 1 and snap["retries"] >= 1
    assert health[1]["timeouts"] >= 1
    solo = Retriever(retriever.index, backend="reference")
    for resp, req in zip(resps, requests):
        assert np.array_equal(resp.doc_ids, solo.search(req).doc_ids)


def test_breaker_trips_and_recovers_under_flap(retriever):
    """Flapping replica: the breaker must OPEN during a bad run and CLOSE
    again via a half-open probe during a good one."""
    requests = _requests(36, seed=7)
    resps, snap, health = _serve(
        retriever, requests,
        policy=FaultPolicy({1: FaultProfile(flap_run=4)}, seed=0),
        # generous retry budget: this test targets the breaker lifecycle,
        # not the retry-storm brake (unit-tested separately)
        cfg=ResilienceConfig(seed=0, hedge=False, breaker_cooldown_s=0.05,
                             backoff_base_s=0.001, timeout_floor_s=5.0,
                             retry_budget_cap=64.0),
        max_batch=1,
    )
    assert snap["completed"] == 36 and snap["failed"] == 0
    assert snap["breaker_trips"] >= 1
    assert snap["breaker_recoveries"] >= 1
    assert health[1]["trips"] >= 1 and health[1]["recoveries"] >= 1


def test_guaranteed_requests_fail_typed_never_degraded(retriever):
    """With every replica erroring, min_recall/exact requests must surface
    the typed ReplicaUnavailable — never a silently-degraded answer."""
    requests = [
        SearchRequest(like=1, probes=6, k=5, min_recall=0.9),
        SearchRequest(like=2, probes=6, k=5),
        SearchRequest(like=3, k=5, exact=True),
    ]
    resps, snap, health = _serve(
        retriever, requests,
        policy=FaultPolicy(
            {0: FaultProfile(error_p=1.0), 1: FaultProfile(error_p=1.0)},
            seed=0,
        ),
        cfg=ResilienceConfig(seed=0, hedge=False, max_retries=1,
                             breaker_cooldown_s=0.01, backoff_base_s=0.001),
        return_exceptions=True,
    )
    for r in resps:
        # every slot is a typed failure (no replica ever answered) — and in
        # particular NOT a degraded response smuggled past the guarantee
        assert isinstance(r, ReplicaUnavailable)
    assert snap["failed"] == 3 and snap["degraded"] == 0


# --------------------------------------- property: degraded answers stay honest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    given = None


def _check_degraded_invariants(retriever, rung, probes, rescore, seed):
    """Whatever rung a request is walked down, the response the server
    would stamp ``degraded=True`` is still a well-formed answer: ids are
    live corpus docs, field_scores sum to the score, and n_scored is
    honest (a degraded answer is a cheaper answer, never a corrupt one)."""
    rng = np.random.default_rng(seed)
    req = SearchRequest(like=int(rng.integers(512)), probes=probes,
                        rescore=rescore, k=8)
    shape = ExecShape("reference", probes, 8, rescore)
    t, kk = retriever.index.counts.shape
    degraded, labels = degrade_request(
        req, shape, rung=rung, ladder=retriever.index.ladder,
        total_probes=t * kk, n_clusterings=t,
    )
    resp = retriever.search(degraded)
    assert len(resp.doc_ids) == len(set(int(i) for i in resp.doc_ids))
    removed = retriever.index.removed
    for hit in resp.hits:
        assert 0 <= hit.doc_id < retriever.index.docs.shape[0]
        if removed is not None and removed.shape[0]:
            assert not bool(removed[hit.doc_id])      # no tombstoned ids
        assert hit.score == pytest.approx(
            sum(hit.field_scores.values()), abs=1e-4
        )
    assert 0 < resp.n_scored <= retriever.index.docs.shape[0]
    # degradation must only ever CHEAPEN the plan, and always audibly
    if labels:
        assert degraded.probes <= req.probes
        assert (degraded.rescore or 0) <= (req.rescore or 0)
    else:
        # empty labels only when there was nothing to take: rung >= 1
        # always strips an existing rescore tail, rung >= 2 always steps
        # probes unless already at the bottom rung (floor: one/clustering)
        assert rung == 0 or rescore is None
        if rung >= 2:
            assert probes <= 3


if given is not None:

    @settings(deadline=None, max_examples=25)
    @given(
        rung=st.integers(0, 2),
        probes=st.integers(3, 12),
        rescore=st.one_of(st.none(), st.integers(8, 20)),
        seed=st.integers(0, 2**16),
    )
    def test_degraded_responses_keep_api_invariants(
        retriever, rung, probes, rescore, seed
    ):
        _check_degraded_invariants(retriever, rung, probes, rescore, seed)

else:

    @pytest.mark.parametrize("case", range(25))
    def test_degraded_responses_keep_api_invariants(retriever, case):
        # hypothesis unavailable: a seeded sweep over the same space
        rng = np.random.default_rng(case)
        _check_degraded_invariants(
            retriever,
            rung=int(rng.integers(0, 3)),
            probes=int(rng.integers(3, 13)),
            rescore=(None if rng.random() < 0.5
                     else int(rng.integers(8, 21))),
            seed=int(rng.integers(2**16)),
        )
