"""Typed retrieval API: request validation, probe planning, batching,
per-field score decomposition, and — the acceptance bar — exact parity
between ``Retriever.search`` responses and the raw ``engine.search`` tuples
on the same index for every runnable backend."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ClusterPruneIndex,
    FieldSpec,
    Hit,
    Retriever,
    SearchRequest,
    SearchResponse,
    aggregate_similarity,
    get_engine,
    normalize_fields,
    plan_probes,
    validate_weights,
    weighted_query,
)

BACKENDS = ("reference", "fused", "sharded")


@pytest.fixture(scope="module")
def api_corpus():
    """Gaussian corpus (no ties => unique top-k => exact parity)."""
    spec = FieldSpec(names=("title", "authors", "abstract"),
                     dims=(32, 32, 64))
    x = jax.random.normal(jax.random.PRNGKey(11), (640, spec.total_dim))
    return normalize_fields(x, spec), spec


@pytest.fixture(scope="module")
def retriever(api_corpus):
    docs, spec = api_corpus
    return Retriever.build(
        docs, spec, 16, n_clusterings=3, method="fpf",
        key=jax.random.PRNGKey(0), backend="reference",
    )


# ------------------------------------------------------------------ requests
def test_request_validation():
    q = jnp.ones((8,))
    with pytest.raises(ValueError, match="exactly one of"):
        SearchRequest()
    with pytest.raises(ValueError, match="exactly one of"):
        SearchRequest(query=q, like=3)
    with pytest.raises(ValueError, match="k must be"):
        SearchRequest(like=3, k=0)
    with pytest.raises(ValueError, match="probes must be"):
        SearchRequest(like=3, probes=0)
    with pytest.raises(ValueError, match="not both"):
        SearchRequest(like=3, probes=4, recall_target=0.9)
    with pytest.raises(ValueError, match="recall_target"):
        SearchRequest(like=3, recall_target=1.5)
    with pytest.raises(ValueError, match="doc id"):
        SearchRequest(like=-2)


def test_weight_resolution_by_field_name(retriever):
    spec = retriever.spec
    req = SearchRequest(like=0, weights={"title": 0.6, "abstract": 0.4})
    w = req.resolve_weights(spec)
    np.testing.assert_allclose(w, [0.6, 0.0, 0.4])   # unnamed field -> 0
    with pytest.raises(ValueError, match="unknown field"):
        SearchRequest(like=0, weights={"tittle": 1.0}).resolve_weights(spec)
    with pytest.raises(ValueError, match="one entry per field"):
        SearchRequest(like=0, weights=(0.5, 0.5)).resolve_weights(spec)
    # a request carries ONE weight vector — batched rows (which the
    # batch-tolerant validate_weights would accept) are rejected here
    with pytest.raises(ValueError, match="one entry per field"):
        SearchRequest(like=0, weights=np.ones((2, 3))).resolve_weights(spec)
    # None -> equal weights
    np.testing.assert_allclose(
        SearchRequest(like=0).resolve_weights(spec), [1 / 3] * 3
    )


def test_query_routing_errors(retriever):
    with pytest.raises(ValueError, match="out of range"):
        retriever.search(SearchRequest(like=10**6))
    with pytest.raises(ValueError, match="corpus concat dim"):
        retriever.search(SearchRequest(query=jnp.ones((7,))))


def test_non_finite_queries_rejected_on_every_path(retriever):
    """A NaN/Inf query embedding raises at the API boundary on BOTH query
    forms — concatenated vector and per-field sequence — instead of
    silently poisoning every similarity downstream."""
    D = retriever.spec.total_dim
    bad = np.ones(D, np.float32)
    bad[3] = np.nan
    with pytest.raises(ValueError, match="non-finite"):
        retriever.search(SearchRequest(query=bad))
    bad[3] = np.inf
    with pytest.raises(ValueError, match="non-finite"):
        retriever.search(SearchRequest(query=bad))
    # per-field form: one poisoned field block is enough to reject
    fields = [np.ones(d, np.float32) for d in retriever.spec.dims]
    fields[1][0] = np.nan
    with pytest.raises(ValueError, match="non-finite"):
        retriever.search(SearchRequest(query=fields))
    # a finite vector of the same shapes still routes fine
    ok = [np.ones(d, np.float32) for d in retriever.spec.dims]
    assert retriever.search(SearchRequest(query=ok, probes=4, k=3)).hits


def test_numpy_batch_query_not_split_as_fields(retriever):
    """Regression: weighted_query must treat a bare np.ndarray batch
    (nq, D) as concatenated queries, not iterate it as a per-field list
    (which concatenated the batch rows into one giant flat vector). The
    all-MLT batched path feeds exactly that — index.docs is numpy."""
    from repro.core import weighted_query

    spec = retriever.spec
    q_np = np.asarray(retriever.index.docs[:4])
    w = np.full((4, spec.s), 1.0 / spec.s, np.float32)
    out = weighted_query(q_np, w, spec)
    assert out.shape == q_np.shape                # batch shape preserved
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(weighted_query(jnp.asarray(q_np), w, spec)),
        atol=1e-6,
    )
    # end to end: a >=2 all-MLT batch matches one-by-one search
    reqs = [SearchRequest(like=i, probes=6, k=5) for i in range(3)]
    batch = retriever.search(reqs)
    for req, resp in zip(reqs, batch):
        solo = retriever.search(req)
        assert np.array_equal(resp.doc_ids, solo.doc_ids)
        np.testing.assert_allclose(resp.scores, solo.scores, atol=1e-6)


@pytest.mark.parametrize("bad", [(-0.5, 1.0, 0.5), (0.0, 0.0, 0.0)])
def test_weights_validated_at_api_boundary(retriever, bad):
    """Negative / all-zero weights raise instead of producing NaN rankings."""
    with pytest.raises(ValueError, match="weights"):
        retriever.search(SearchRequest(like=1, weights=bad))


def test_validate_weights_batch_rows():
    spec = FieldSpec(names=("a", "b"), dims=(4, 4))
    ok = validate_weights(np.asarray([[0.5, 0.5], [1.0, 0.0]]), spec)
    assert ok.dtype == np.float32
    with pytest.raises(ValueError):
        validate_weights(np.asarray([[0.5, 0.5], [0.0, 0.0]]), spec)
    with pytest.raises(ValueError):
        validate_weights(np.asarray([np.nan, 1.0]), spec)


# ------------------------------------------------------------------- planner
def test_plan_probes_monotone_and_bounded():
    t, kc = 3, 110
    budgets = [plan_probes(r, t, kc) for r in
               (0.1, 0.5, 0.8, 0.9, 0.95, 0.99, 1.0)]
    assert budgets == sorted(budgets)
    assert all(t <= b <= t * kc for b in budgets)
    assert plan_probes(1.0, t, kc) == t * kc       # exact search
    with pytest.raises(ValueError):
        plan_probes(0.0, t, kc)


def test_recall_target_maps_to_probes(retriever):
    """Uncalibrated index: recall_target falls back to the static ladder
    (with a warning — the per-index calibrated path lives in
    tests/test_calibrate.py) and reports the nominal target as predicted."""
    t, kc = retriever.index.counts.shape
    with pytest.warns(UserWarning, match="static"):
        resp = retriever.search(SearchRequest(like=5, recall_target=0.9, k=4))
    assert resp.probes == plan_probes(0.9, t, kc)
    assert resp.predicted_recall == pytest.approx(0.9)
    # the plan is cached per target and (T, K) hoisted at construction
    assert retriever._tk == (int(t), int(kc))
    assert retriever._plan_cache[0.9][0] == resp.probes


# ----------------------------------------------------- parity (acceptance)
@pytest.mark.parametrize("backend", BACKENDS)
def test_retriever_parity_with_raw_engine(
    case_retriever, retriever, api_corpus, backend
):
    """Retriever hits == raw engine.search tuples (ids, scores, n_scored)."""
    retriever, backend = case_retriever(retriever, backend)
    docs, spec = api_corpus
    rng = np.random.default_rng(3)
    qids = rng.choice(docs.shape[0], 12, replace=False)
    wmat = rng.dirichlet([1.0] * spec.s, 12).astype(np.float32)
    reqs = [
        SearchRequest(like=int(q), weights=dict(zip(spec.names, map(float, w))),
                      probes=6, k=10, backend=backend)
        for q, w in zip(qids, wmat)
    ]
    responses = retriever.search(reqs)

    qw = weighted_query(docs[qids], jnp.asarray(wmat), spec)
    opts = retriever.engine_opts if backend == retriever.backend else {}
    s, i, n = get_engine(retriever.index, backend, **opts).search(
        qw, probes=6, k=10, exclude=jnp.asarray(qids, jnp.int32)
    )
    assert np.array_equal(
        np.stack([r.doc_ids for r in responses]), np.asarray(i)
    ), backend
    np.testing.assert_allclose(
        np.stack([r.scores for r in responses]), np.asarray(s), atol=1e-6
    )
    assert np.array_equal(
        np.asarray([r.n_scored for r in responses]), np.asarray(n)
    ), backend
    assert all(r.backend == backend for r in responses)


# -------------------------------------------------------------- decomposition
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("form", ("keyword", "mlt"))
def test_field_decomposition(
    case_retriever, retriever, api_corpus, backend, form
):
    """Per-field contributions sum to the aggregate score and rank hits
    identically to the definitional aggregate_similarity — for keyword and
    more-like-this requests on every runnable backend."""
    retriever, backend = case_retriever(retriever, backend)
    docs, spec = api_corpus
    w = {"title": 0.5, "authors": 0.2, "abstract": 0.3}
    if form == "mlt":
        req = SearchRequest(like=37, weights=w, probes=6, k=8,
                            backend=backend)
        qvec, excl = docs[37], 37
    else:
        qvec = docs[101]
        req = SearchRequest(query=qvec, weights=w, probes=6, k=8,
                            exclude=101, backend=backend)
        excl = 101
    resp = retriever.search(req)
    assert len(resp.hits) > 0 and excl not in resp.ids

    wv = jnp.asarray([w[n] for n in spec.names])
    for h in resp.hits:
        # (1) exact split: contributions sum to the aggregate engine score
        assert set(h.field_scores) == set(spec.names)
        np.testing.assert_allclose(
            sum(h.field_scores.values()), h.score, atol=1e-5
        )
    # (2) ranking-consistent with the paper's definitional WS form
    hit_docs = docs[jnp.asarray(resp.ids)]
    ws = aggregate_similarity(qvec, wv, hit_docs, spec)
    order = np.argsort(-np.asarray(ws), kind="stable")
    assert np.array_equal(order, np.arange(len(resp.hits))), (
        f"{backend}/{form}: hit order disagrees with aggregate_similarity"
    )


def test_hit_field_scores_reflect_weights(retriever, api_corpus):
    """A zero-weighted field contributes (numerically) nothing."""
    resp = retriever.search(
        SearchRequest(like=12, weights={"title": 1.0}, probes=6, k=5)
    )
    for h in resp.hits:
        assert abs(h.field_scores["authors"]) < 1e-6
        assert abs(h.field_scores["abstract"]) < 1e-6


# ------------------------------------------------------------------ batching
def test_heterogeneous_batch_routing(retriever, api_corpus):
    """Mixed forms/shapes come back in request order with correct grouping."""
    docs, spec = api_corpus
    reqs = [
        SearchRequest(like=3, probes=6, k=5),
        SearchRequest(query=docs[9], weights=(0.2, 0.2, 0.6), probes=6, k=5,
                      exclude=9),
        SearchRequest(like=4, probes=9, k=3),
        SearchRequest(like=8, probes=6, k=5, backend="fused"),
    ]
    out = retriever.search(reqs)
    assert [type(r) for r in out] == [SearchResponse] * 4
    # group shapes: reqs 0+1 share (reference, 6, 5); 2 and 3 are alone
    assert out[0].batch_size == 2 and out[1].batch_size == 2
    assert out[2].batch_size == 1 and out[2].probes == 9
    assert out[3].backend == "fused" and out[3].batch_size == 1
    # batched result == the same request served alone
    solo = retriever.search(reqs[0])
    assert np.array_equal(out[0].doc_ids, solo.doc_ids)
    np.testing.assert_allclose(out[0].scores, solo.scores, atol=1e-6)
    assert isinstance(solo, SearchResponse)
    assert retriever.search([]) == []


def test_rescore_request_validation():
    with pytest.raises(ValueError, match="rescore depth must be >= k"):
        SearchRequest(like=3, k=10, rescore=5)
    # rescore == k is legal (a pure exact-rescore of the returned set)
    assert SearchRequest(like=3, k=10, rescore=10).rescore == 10


@pytest.mark.parametrize("backend", BACKENDS)
def test_rescore_through_retriever(
    case_retriever, retriever, api_corpus, backend
):
    """SearchRequest(rescore=...) reaches the engine on every backend: on
    the fp32 pack it's an id/score identity that deepens n_scored, and the
    response scores equal exact fp32 dot products of the returned ids."""
    retriever, backend = case_retriever(retriever, backend)
    docs, spec = api_corpus
    plain = retriever.search(
        SearchRequest(like=7, probes=6, k=5, backend=backend))
    resc = retriever.search(
        SearchRequest(like=7, probes=6, k=5, rescore=15, backend=backend))
    assert np.array_equal(resc.doc_ids, plain.doc_ids), backend
    np.testing.assert_allclose(resc.scores, plain.scores, atol=1e-5)
    assert resc.n_scored > plain.n_scored
    qw = weighted_query(docs[7][None], jnp.full((1, 3), 1 / 3), spec)
    exact = np.asarray(docs[jnp.asarray(resc.doc_ids)] @ qw[0])
    np.testing.assert_allclose(resc.scores, exact, atol=1e-5)


def test_rescore_batching_and_cache_key(fresh_retriever):
    """rescore participates in batch grouping and the response-cache key:
    same request with/without rescore are distinct groups AND distinct
    cached responses."""
    retriever, docs, spec = fresh_retriever
    reqs = [
        SearchRequest(like=3, probes=6, k=5),
        SearchRequest(like=4, probes=6, k=5),
        SearchRequest(like=5, probes=6, k=5, rescore=12),
    ]
    out = retriever.search(reqs)
    assert out[0].batch_size == 2 and out[1].batch_size == 2
    assert out[2].batch_size == 1
    plain = retriever.search(SearchRequest(like=3, probes=6, k=5))
    resc = retriever.search(SearchRequest(like=3, probes=6, k=5, rescore=12))
    assert plain is not resc
    assert retriever.search(
        SearchRequest(like=3, probes=6, k=5, rescore=12)) is resc


def test_mlt_self_exclusion_default(retriever):
    resp = retriever.search(SearchRequest(like=21, probes=8, k=10))
    assert 21 not in resp.ids
    # explicit exclude=-1 disables the self-mask: the doc is its own 1-NN
    resp2 = retriever.search(SearchRequest(like=21, probes=8, k=10,
                                           exclude=-1))
    assert resp2.hits[0].doc_id == 21


def test_response_surface(retriever):
    resp = retriever.search(SearchRequest(like=2, probes=6, k=5))
    assert len(resp) == len(resp.hits) and list(resp) == list(resp.hits)
    assert resp.doc_ids.shape == (5,) and resp.scores.shape == (5,)
    assert resp.latency_s > 0 and resp.n_scored > 0
    assert resp.predicted_recall is None   # explicit probes, no ladder
    assert isinstance(resp.hits[0], Hit)
    # scores come back best-first
    live = resp.scores[resp.doc_ids >= 0]
    assert np.all(np.diff(live) <= 1e-6)


# ----------------------------------------------------- exec_shape + latency
def test_exec_shape_resolution(retriever):
    """The public grouping contract: retriever defaults fill unspecified
    fields, explicit request fields win, recall_target needs a planner."""
    from repro.core import ExecShape, exec_shape

    shape = retriever.exec_shape(SearchRequest(like=1))
    assert shape == ExecShape(
        "reference", retriever.default_probes, 10, None
    )
    assert retriever.exec_shape(
        SearchRequest(like=1, probes=7, k=4, backend="fused", rescore=8)
    ) == ExecShape("fused", 7, 4, 8)
    # module-level form: recall_target without a planner must raise, not
    # guess a budget the serving engine would then not use
    with pytest.raises(ValueError, match="plan_target"):
        exec_shape(SearchRequest(like=1, recall_target=0.9),
                   default_backend="reference", default_probes=6)
    assert exec_shape(
        SearchRequest(like=1, recall_target=0.9),
        default_backend="reference", default_probes=6,
        plan_target=lambda t: 11,
    ) == ExecShape("reference", 11, 10, None)
    # the shape IS the batch-grouping key _search_batch uses: requests
    # sharing one shape ride one engine call
    reqs = [SearchRequest(like=3, probes=6, k=5),
            SearchRequest(like=4, probes=6, k=5),
            SearchRequest(like=5, probes=9, k=5)]
    shapes = [retriever.exec_shape(r) for r in reqs]
    assert shapes[0] == shapes[1] != shapes[2]
    out = retriever.search(reqs)
    assert out[0].batch_size == 2 and out[2].batch_size == 1


def test_latency_split_sync_path(retriever):
    """Synchronous responses carry the per-request latency split: no queue
    on this path (queue_wait_s == 0), compute is the group's shared engine
    wall, and latency_s is exactly their sum."""
    resps = retriever.search([
        SearchRequest(like=31, probes=7, k=5),
        SearchRequest(like=32, probes=7, k=5),
    ])
    for r in resps:
        assert r.queue_wait_s == 0.0 and r.compute_s > 0
        assert r.latency_s == pytest.approx(r.compute_s)
    # riders of one group share the engine call they all waited on
    assert resps[0].compute_s == resps[1].compute_s
    assert resps[0].batch_size == 2


# ------------------------------------------------- deprecated shim (qchunk)
def test_index_search_qchunk_silent_drop_fixed(retriever, api_corpus):
    """qchunk with a non-reference backend raises instead of being ignored."""
    docs, _ = api_corpus
    idx = retriever.index
    qw = docs[5:7]
    with pytest.raises(ValueError, match="qchunk"):
        idx.search(qw, probes=6, k=5, qchunk=4, backend="fused")
    # reference still honours it, and the default passes everywhere
    s, i, n = idx.search(qw, probes=6, k=5, qchunk=4, backend="reference")
    s2, i2, n2 = idx.search(qw, probes=6, k=5, backend="fused")
    assert np.array_equal(np.asarray(i), np.asarray(i2))


# ---------------------------------------------- request caching + mutation
@pytest.fixture()
def fresh_retriever(api_corpus):
    """Function-scoped: caching/mutation tests get their own index."""
    docs, spec = api_corpus
    r = Retriever.build(
        docs[:600], spec, 16, n_clusterings=3, method="fpf",
        key=jax.random.PRNGKey(0), backend="reference",
    )
    return r, docs, spec


def test_repeat_request_served_from_cache(fresh_retriever):
    """Byte-identical MLT repeats return the SAME response object; raw
    vector queries are not memoised."""
    retriever, docs, spec = fresh_retriever
    req = SearchRequest(like=12, weights={"title": 0.5, "abstract": 0.5},
                        probes=9, k=5)
    first = retriever.search(req)
    again = retriever.search(
        SearchRequest(like=12, weights={"title": 0.5, "abstract": 0.5},
                      probes=9, k=5))
    assert again is first
    # a different weight draw is a different answer, not a cache hit
    other = retriever.search(
        SearchRequest(like=12, weights={"title": 0.9, "abstract": 0.1},
                      probes=9, k=5))
    assert other is not first
    # vector-query requests bypass the response cache
    vec = SearchRequest(query=docs[12], probes=9, k=5, exclude=12)
    assert retriever.search(vec) is not retriever.search(vec)


def test_planning_runs_no_per_row_device_work(fresh_retriever, monkeypatch):
    """An all-MLT batch is planned by ONE resolve program whatever its size:
    a batch of 8 and a batch of 64 start the same device programs inside
    the planning spans, and none of them indexes a device array by row."""
    import contextlib

    from repro import tracing
    from repro.core import api

    retriever, docs, spec = fresh_retriever
    counts = {"resolve": 0, "getitem": 0}
    planning = [False]

    real_span = api.span

    @contextlib.contextmanager
    def tagged_span(name, **meta):
        prior = planning[0]
        planning[0] = name == tracing.SEARCH_PREPARE
        try:
            with real_span(name, **meta) as sp:
                yield sp
        finally:
            planning[0] = prior

    real_resolve = api._mlt_weighted_query

    def counted_resolve(*args, **kwargs):
        counts["resolve"] += planning[0]
        return real_resolve(*args, **kwargs)

    array_type = type(jnp.zeros(()))
    real_getitem = array_type.__getitem__

    def counted_getitem(self, idx):
        counts["getitem"] += planning[0]
        return real_getitem(self, idx)

    monkeypatch.setattr(api, "span", tagged_span)
    monkeypatch.setattr(api, "_mlt_weighted_query", counted_resolve)
    monkeypatch.setattr(array_type, "__getitem__", counted_getitem)

    rng = np.random.default_rng(3)
    seen = {}
    for n in (8, 64):
        counts.update(resolve=0, getitem=0)
        reqs = [
            SearchRequest(like=int(like), weights=tuple(w), probes=6, k=4)
            for like, w in zip(rng.choice(500, n, replace=False),
                               rng.dirichlet(np.ones(3), n))
        ]
        out = retriever.search(reqs)
        assert [r.batch_size for r in out] == [n] * n
        seen[n] = dict(counts)
    assert seen[8] == seen[64] == {"resolve": 1, "getitem": 0}


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("with_vector", (True, False))
def test_batched_resolve_matches_solo(
    case_retriever, fresh_retriever, backend, with_vector
):
    """Every miss of a batch resolves in one step: a repeated (like,
    weights) pair under two budgets, another weight draw and (optionally)
    a raw vector, planned together across two engine groups, get the doc
    ids and scores each request gets alone; the pair still caches two
    responses."""
    retriever, docs, spec = fresh_retriever
    retriever, backend = case_retriever(retriever, backend)
    w = (0.2, 0.3, 0.5)
    reqs = [
        SearchRequest(like=5, weights=w, probes=6, k=4, backend=backend),
        SearchRequest(like=5, weights=w, probes=12, k=4, backend=backend),
        SearchRequest(like=5, weights=(0.6, 0.3, 0.1), probes=6, k=4,
                      backend=backend),
    ]
    if with_vector:
        reqs.append(SearchRequest(query=docs[9], weights=w, probes=6, k=4,
                                  exclude=9, backend=backend))
    batched = retriever.search(reqs)
    assert [r.batch_size for r in batched] == [len(reqs) - 1, 1] + [
        len(reqs) - 1] * (len(reqs) - 2)
    pair = [key for key in retriever._response_cache
            if key[:2] == (5, retriever._weights_key(w))]
    assert len(pair) == 2
    assert len(retriever._response_cache) == 3

    retriever._flush_request_caches()
    for req, got in zip(reqs, batched):
        solo = retriever.search(req)
        assert solo is not got
        assert np.array_equal(got.doc_ids, solo.doc_ids)
        assert np.array_equal(got.scores, solo.scores)
        # the split is one einsum over the group, so its float32 sums
        # round by the group's size (a few ulp), as in the routing test
        for hg, hs in zip(got.hits, solo.hits):
            assert hg.field_scores.keys() == hs.field_scores.keys()
            np.testing.assert_allclose(
                list(hg.field_scores.values()),
                list(hs.field_scores.values()), rtol=0, atol=1e-6)


def test_cache_invalidated_by_mutation(fresh_retriever):
    """retriever.add/remove flush the caches, and the next answer reflects
    the mutated corpus (an exact copy must take over as hit #1)."""
    retriever, docs, spec = fresh_retriever
    req = SearchRequest(like=33, probes=12, k=5)
    before = retriever.search(req)
    assert retriever.search(req) is before
    [new_id] = retriever.add(docs[33][None, :])
    after = retriever.search(req)
    assert after is not before
    assert after.hits[0].doc_id == int(new_id)
    assert retriever.remove([new_id]) == 1
    final = retriever.search(req)
    assert int(new_id) not in final.ids
    assert np.array_equal(final.doc_ids, before.doc_ids)


def test_cache_invalidated_by_direct_index_mutation(fresh_retriever):
    """Mutations applied to the index directly (not through the facade)
    must also flush — the version counter is the coherency token."""
    retriever, docs, spec = fresh_retriever
    req = SearchRequest(like=8, probes=12, k=5)
    before = retriever.search(req)
    retriever.index.add_documents(docs[8][None, :])
    after = retriever.search(req)
    assert after is not before
    assert after.hits[0].doc_id == 600      # the copy, appended at n=600


def test_stale_ladder_warns_without_calibrate(fresh_retriever):
    import warnings as _w

    from repro.core import calibrate_index

    retriever, docs, spec = fresh_retriever
    calibrate_index(retriever.index, n_queries=8, n_weight_draws=2,
                    probe_grid=(3, 12))
    retriever.search(SearchRequest(like=1, recall_target=0.8, k=5))
    retriever.add(docs[:100])               # 100/600 churn: stale
    assert retriever.index.ladder_stale
    with pytest.warns(UserWarning, match="stale"):
        retriever.search(SearchRequest(like=1, recall_target=0.8, k=5))
    # warned once, not per request
    with _w.catch_warnings():
        _w.simplefilter("error")
        retriever.search(SearchRequest(like=2, recall_target=0.8, k=5))


def test_stale_ladder_refit_with_calibrate(api_corpus):
    docs, spec = api_corpus
    retriever = Retriever.build(
        docs[:600], spec, 16, n_clusterings=3, method="fpf",
        key=jax.random.PRNGKey(0), backend="reference", calibrate=True,
        calibrate_opts={"n_queries": 8, "n_weight_draws": 2,
                        "probe_grid": (3, 12)},
    )
    retriever.search(SearchRequest(like=1, recall_target=0.8, k=5))
    first_ladder = retriever.index.ladder
    assert first_ladder is not None
    retriever.add(docs[:100])
    assert retriever.index.ladder_stale
    retriever.search(SearchRequest(like=1, recall_target=0.8, k=5))
    assert retriever.index.ladder is not first_ladder   # refit
    assert retriever.index.n_mutations == 0
    assert not retriever.index.ladder_stale


# ------------------------------------------------------------ tiered requests
def test_tier_request_validation():
    with pytest.raises(ValueError, match="contradictory"):
        SearchRequest(like=3, exact=True, probes=6)
    with pytest.raises(ValueError, match="contradictory"):
        SearchRequest(like=3, exact=True, recall_target=0.9)
    with pytest.raises(ValueError, match="not both"):
        SearchRequest(like=3, exact=True, min_recall=0.9)
    with pytest.raises(ValueError, match="min_recall"):
        SearchRequest(like=3, min_recall=1.5)
    with pytest.raises(ValueError, match="min_recall"):
        SearchRequest(like=3, min_recall=0.0)
    # legal combinations: exact alone, min_recall with a starting budget
    assert SearchRequest(like=3, exact=True).exact
    assert SearchRequest(like=3, probes=4, min_recall=0.9).min_recall == 0.9
    assert SearchRequest(like=3, recall_target=0.8, min_recall=0.9).k == 10


@pytest.mark.parametrize("backend", BACKENDS)
def test_exact_tier_through_retriever(
    case_retriever, retriever, api_corpus, backend
):
    """SearchRequest(exact=True): brute-force-identical answers on every
    backend, tier/probes/predicted_recall stamped honestly."""
    from repro.core import brute_force_topk

    retriever, backend = case_retriever(retriever, backend)
    docs, spec = api_corpus
    rng = np.random.default_rng(5)
    qids = rng.choice(docs.shape[0], 8, replace=False)
    wmat = rng.dirichlet([1.0] * spec.s, 8).astype(np.float32)
    reqs = [
        SearchRequest(like=int(q),
                      weights=dict(zip(spec.names, map(float, w))),
                      exact=True, k=10, backend=backend)
        for q, w in zip(qids, wmat)
    ]
    responses = retriever.search(reqs)
    qw = weighted_query(docs[qids], jnp.asarray(wmat), spec)
    gt_s, gt_i = brute_force_topk(
        docs, qw, 10, exclude=jnp.asarray(qids, jnp.int32)
    )
    assert np.array_equal(
        np.stack([r.doc_ids for r in responses]), np.asarray(gt_i)
    ), backend
    np.testing.assert_allclose(
        np.stack([r.scores for r in responses]), np.asarray(gt_s), atol=1e-5
    )
    t, kc = retriever._tk
    for r in responses:
        assert r.tier == "exact" and r.escalations == 0
        assert r.probes == t * kc
        assert r.predicted_recall == 1.0
        assert r.batch_size == len(reqs)


def test_exact_tier_shape_and_batching(retriever):
    """exact requests resolve to the pinned full-sweep shape, group with
    each other, and stay separate from budgeted requests."""
    from repro.core import ExecShape

    t, kc = retriever._tk
    sh = retriever.exec_shape(SearchRequest(like=1, exact=True))
    assert sh == ExecShape("reference", t * kc, 10, None, "exact", None)
    out = retriever.search([
        SearchRequest(like=3, exact=True, k=5),
        SearchRequest(like=4, exact=True, k=5),
        SearchRequest(like=5, probes=6, k=5),
    ])
    assert out[0].batch_size == 2 and out[1].batch_size == 2
    assert out[2].batch_size == 1 and out[2].tier == "approx"


@pytest.mark.parametrize("backend", BACKENDS)
def test_oversized_probes_clamped(
    case_retriever, retriever, api_corpus, backend
):
    """Regression: probes= past T*K used to die in the engine with an
    opaque XLA error; it now clamps to the probe-everything budget at
    shape resolution on every backend."""
    retriever, backend = case_retriever(retriever, backend)
    t, kc = retriever._tk
    sh = retriever.exec_shape(SearchRequest(like=1, probes=10_000))
    assert sh.probes == t * kc and sh.tier == "approx"
    resp = retriever.search(
        SearchRequest(like=9, probes=10_000, k=5, backend=backend))
    full = retriever.search(
        SearchRequest(like=9, probes=t * kc, k=5, backend=backend))
    assert resp.probes == t * kc
    assert np.array_equal(resp.doc_ids, full.doc_ids), backend


def test_auto_backend_resolves_in_shape(api_corpus):
    """Regression: backend="auto" used to leak the literal string into
    ExecShape — batching separately from default requests, dropping
    engine_opts, and caching a duplicate engine under the "auto" key."""
    docs, spec = api_corpus
    retriever = Retriever.build(
        docs[:600], spec, 16, n_clusterings=3, method="fpf",
        key=jax.random.PRNGKey(0), backend="reference",
        engine_opts={"qchunk": 4},
    )
    sh_auto = retriever.exec_shape(SearchRequest(like=1, backend="auto"))
    sh_none = retriever.exec_shape(SearchRequest(like=1))
    assert sh_auto == sh_none and sh_auto.backend == "reference"
    # one engine call for the pair, not two
    out = retriever.search([
        SearchRequest(like=3, probes=6, k=5, backend="auto"),
        SearchRequest(like=4, probes=6, k=5),
    ])
    assert out[0].batch_size == 2 and out[1].batch_size == 2
    assert out[0].backend == "reference"
    # engine_opts reached the engine (no duplicate under "auto", no
    # opts-less default engine built for the auto request)
    cached = list(getattr(retriever.index, "_engines", {}))
    assert ("reference", (("qchunk", 4),)) in cached
    assert not any(name == "auto" for name, _ in cached)
    assert ("reference", ()) not in cached


def test_min_recall_without_ladder_serves_exact(api_corpus):
    """No calibrated ladder => no prediction can state the floor; the
    request is served by the exact tier (guarantee over guesswork)."""
    docs, spec = api_corpus
    retriever = Retriever.build(
        docs[:600], spec, 16, n_clusterings=3, method="fpf",
        key=jax.random.PRNGKey(0), backend="reference",
    )
    assert retriever.index.ladder is None
    sh = retriever.exec_shape(SearchRequest(like=1, min_recall=0.9))
    t, kc = retriever._tk
    assert sh.tier == "exact" and sh.probes == t * kc
    resp = retriever.search(SearchRequest(like=5, min_recall=0.9, k=5))
    assert resp.tier == "exact" and resp.predicted_recall == 1.0


@pytest.fixture()
def calibrated_retriever(api_corpus):
    """Function-scoped retriever with a fitted (tiny) probe ladder."""
    from repro.core import calibrate_index

    docs, spec = api_corpus
    r = Retriever.build(
        docs[:600], spec, 16, n_clusterings=3, method="fpf",
        key=jax.random.PRNGKey(0), backend="reference",
    )
    calibrate_index(r.index, n_queries=16, n_weight_draws=2,
                    probe_grid=(3, 6, 12, 24), seed=2)
    return r, docs, spec


def test_min_recall_escalates_and_meets_floor(calibrated_retriever):
    """A floor the planned budget's prediction cannot meet escalates, is
    achieved on the calibration corpus, and charges cumulative n_scored."""
    from repro.core import brute_force_topk, recall_fraction

    retriever, docs, spec = calibrated_retriever
    ladder = retriever.index.ladder
    floor = min(1.0, float(ladder.recall[-1]))      # reachable by rungs
    assert float(ladder.predicted_recall(3)) < floor
    rng = np.random.default_rng(7)
    qids = rng.choice(600, 16, replace=False)
    reqs = [SearchRequest(like=int(q), probes=3, min_recall=floor, k=10)
            for q in qids]
    responses = retriever.search(reqs)
    for r in responses:
        assert r.tier in ("escalated", "exact")
        assert r.escalations >= 1
        assert r.predicted_recall >= floor
    # honest cumulative accounting: strictly more than one pass at the
    # final budget
    single = retriever.search(
        SearchRequest(like=int(qids[0]), probes=responses[0].probes, k=10))
    assert responses[0].n_scored > single.n_scored
    # the floor is met on achieved recall (mean over the query draw)
    qw = weighted_query(
        docs[jnp.asarray(qids)],
        jnp.full((len(qids), spec.s), 1.0 / spec.s), spec,
    )
    _, gt_i = brute_force_topk(
        docs[:600], qw, 10, exclude=jnp.asarray(qids, jnp.int32))
    ids = jnp.asarray(np.stack([r.doc_ids for r in responses]))
    achieved = float(jnp.mean(recall_fraction(ids, gt_i)))
    assert achieved >= floor - 0.05, (achieved, floor)


def test_min_recall_met_floor_batches_as_approx(calibrated_retriever):
    """A floor the planned budget already satisfies stays tier "approx"
    and shares the engine call with unconstrained requests."""
    retriever, docs, spec = calibrated_retriever
    ladder = retriever.index.ladder
    top = int(ladder.probes[-1])
    floor = float(ladder.predicted_recall(top)) - 0.05
    assert 0.0 < floor <= 1.0
    sh_floor = retriever.exec_shape(
        SearchRequest(like=1, probes=top, min_recall=floor))
    sh_plain = retriever.exec_shape(SearchRequest(like=2, probes=top))
    assert sh_floor == sh_plain and sh_floor.tier == "approx"
    out = retriever.search([
        SearchRequest(like=3, probes=top, min_recall=floor, k=5),
        SearchRequest(like=4, probes=top, k=5),
    ])
    assert out[0].batch_size == 2 and out[0].tier == "approx"
    assert out[0].escalations == 0


def test_tier_fields_in_response_cache_key(calibrated_retriever):
    """exact / min_recall are part of request identity: the same like= must
    not alias across tiers in the response cache."""
    retriever, docs, spec = calibrated_retriever
    plain = retriever.search(SearchRequest(like=11, probes=3, k=5))
    exact = retriever.search(SearchRequest(like=11, exact=True, k=5))
    floored = retriever.search(
        SearchRequest(like=11, probes=3, min_recall=0.99, k=5))
    assert plain is not exact and plain is not floored
    assert exact.tier == "exact" and plain.tier == "approx"
    # repeats hit their own entries
    assert retriever.search(SearchRequest(like=11, exact=True, k=5)) is exact


# ------------------------------------------------------- tombstoned like=
def test_tombstoned_like_raises(fresh_retriever):
    """Regression: more-like-this on a removed doc silently served results
    seeded from the tombstone; now every path raises a clear error."""
    retriever, docs, spec = fresh_retriever
    retriever.remove([42])
    # single request (batched MLT fast path)
    with pytest.raises(ValueError, match="removed"):
        retriever.search(SearchRequest(like=42, probes=6, k=5))
    # mixed batch (resolve_query path: a vector query disables the
    # all-MLT gather, so the per-request resolution must check too)
    with pytest.raises(ValueError, match="removed"):
        retriever.search([
            SearchRequest(like=42, probes=6, k=5),
            SearchRequest(query=docs[9], probes=6, k=5, exclude=9),
        ])
    # untouched docs still serve, and never return the tombstone
    resp = retriever.search(SearchRequest(like=41, probes=12, k=10))
    assert 42 not in resp.ids


def test_cached_like_answer_does_not_outlive_removal(fresh_retriever):
    """Response-cache interaction: a cached like= answer must not be
    served after the seed doc is removed — through the facade or via a
    direct index mutation."""
    retriever, docs, spec = fresh_retriever
    req = SearchRequest(like=12, probes=6, k=5)
    first = retriever.search(req)
    assert retriever.search(req) is first          # cached
    retriever.remove([12])
    with pytest.raises(ValueError, match="removed"):
        retriever.search(req)
    # direct index mutation (version bump is the coherency token)
    req2 = SearchRequest(like=13, probes=6, k=5)
    second = retriever.search(req2)
    assert retriever.search(req2) is second
    retriever.index.remove_documents([13])
    with pytest.raises(ValueError, match="removed"):
        retriever.search(req2)


# ------------------------------------------------------ property (hypothesis)
try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:                                   # container has no dev deps
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:

    @st.composite
    def _request_batches(draw):
        """A batch of 2-5 legal SearchRequests spanning the tier lattice."""
        n = draw(st.integers(min_value=2, max_value=5))
        reqs = []
        for i in range(n):
            exact = draw(st.booleans())
            kwargs = {"like": i, "k": 5}
            kwargs["backend"] = draw(st.sampled_from(
                (None, "auto", "reference", "fused")))
            if exact:
                kwargs["exact"] = True
            else:
                kwargs["probes"] = draw(st.sampled_from((None, 4, 6, 100_000)))
                if draw(st.booleans()):
                    kwargs["min_recall"] = draw(st.sampled_from((0.5, 0.9)))
                kwargs["rescore"] = draw(st.sampled_from((None, 10)))
            reqs.append(SearchRequest(**kwargs))
        return reqs

    @settings(max_examples=15, deadline=None)
    @given(_request_batches())
    def test_shape_grouping_property(retriever, reqs):
        """`Retriever.exec_shape` is the batching contract: for any legal
        request mix, each response's batch_size equals the number of
        requests in the batch that resolve to the same shape — the
        serving tier's queue keys and `_search_batch`'s groups agree."""
        retriever._flush_request_caches()
        shapes = [retriever.exec_shape(r) for r in reqs]
        responses = retriever.search(reqs)
        for shape, resp in zip(shapes, responses):
            assert resp.batch_size == shapes.count(shape)
            assert resp.backend == shape.backend != "auto"
            if shape.tier == "exact":
                assert resp.tier == "exact"
                assert resp.predicted_recall == 1.0
