"""Build-pipeline seam + incremental index maintenance.

Three concerns, mirroring what tests/test_engine.py does for the query side:

1. **Clusterer registry** — the seam itself: registration, lookup, platform
   auto-pick, and the contract that a custom clusterer drops into
   ``ClusterPruneIndex.build(method=...)``.
2. **fpf_fused parity** — an index built through the Pallas ``fpf_iter``
   kernel path is IDENTICAL (exact bucket/leader equality, per-round center
   parity) to the pure-JAX ``fpf`` reference at a fixed seed; interpret mode
   makes this meaningful on CPU.
3. **Incremental maintenance** — ``add_documents`` / ``remove_documents``
   mutate a built index without a rebuild: adds land in the probed buckets
   of every engine backend, removes can never be returned, bucket padding
   grows on overflow, quality after a 10% ingest stays within the
   tests/test_quality.py floors, and the whole mutation state (tombstones,
   stale-ladder drift counter) survives save/load.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CLUSTERERS,
    ClusteringResult,
    ClusterPruneIndex,
    LADDER_DRIFT_THRESHOLD,
    assign_refine,
    assign_to_centers,
    assign_to_centers_multi,
    available_clusterers,
    brute_force_topk,
    brute_force_bottomk,
    competitive_recall,
    fpf_centers,
    get_clusterer,
    get_engine,
    normalized_aggregate_goodness,
    pick_clusterer,
    register_clusterer,
    weighted_query,
)

BACKENDS = ("reference", "fused", "sharded")


# ------------------------------------------------------------------ registry
def test_registry_contents():
    names = available_clusterers()
    for expected in ("fpf", "fpf_fused", "kmeans", "random"):
        assert expected in names
    assert get_clusterer("fpf").name == "fpf"
    with pytest.raises(ValueError, match="unknown clusterer"):
        get_clusterer("does-not-exist")


def test_auto_pick_matches_platform():
    picked = pick_clusterer()
    expected = "fpf_fused" if jax.default_backend() == "tpu" else "fpf"
    assert picked == expected
    assert get_clusterer("auto").name == picked


def test_custom_clusterer_builds_an_index(random_corpus):
    """The recipe in ROADMAP.md: register -> build(method=name) -> search."""
    docs, spec = random_corpus

    @register_clusterer("_test_stride")
    class StrideClusterer:
        """Deterministic toy: every k-th doc is a representative."""

        def __init__(self, **_):
            pass

        def cluster(self, x, k, key):
            reps = x[:: max(1, x.shape[0] // k)][:k]
            return assign_refine(x, k, reps)

    try:
        idx = ClusterPruneIndex.build(docs, spec, 8, n_clusterings=2,
                                      method="_test_stride")
        assert idx.method == "_test_stride"
        qw = weighted_query(docs[:4], jnp.ones((4, 3)) / 3, spec)
        _, ids, _ = idx.search(qw, probes=16, k=5)   # full probe = exact
        _, gt_i = brute_force_topk(docs, qw, 5)
        assert np.array_equal(np.sort(np.asarray(ids)),
                              np.sort(np.asarray(gt_i)))
    finally:
        CLUSTERERS.pop("_test_stride", None)


def test_clusterer_result_counts_cover(random_corpus):
    docs, _ = random_corpus
    for name in ("fpf", "fpf_fused", "kmeans", "random"):
        res = get_clusterer(name).cluster(docs, 12, jax.random.PRNGKey(3))
        assert isinstance(res, ClusteringResult)
        assert int(jnp.sum(res.counts)) == docs.shape[0]


# ------------------------------------------------------------ fused parity
def test_fused_rounds_match_reference_per_round():
    """Every Gonzalez round through the Pallas kernel returns the same
    (maxsim, next-center) as the pure-jnp oracle — parity per ROUND, not
    just for the final center set."""
    from repro.kernels import fpf_iter, fpf_iter_ref

    x = jax.random.normal(jax.random.PRNGKey(9), (300, 48))
    x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
    ms_k = jnp.full((300,), -jnp.inf)
    ms_r = ms_k
    cur = 17
    for _ in range(8):
        ms_k, idx_k, val_k = fpf_iter(x, x[cur], ms_k, block_m=128)
        ms_r, idx_r, val_r = fpf_iter_ref(x, x[cur], ms_r)
        np.testing.assert_allclose(np.asarray(ms_k), np.asarray(ms_r),
                                   atol=1e-6)
        assert int(idx_k) == int(idx_r)
        np.testing.assert_allclose(float(val_k), float(val_r), atol=1e-6)
        cur = int(idx_k)


def test_fused_clusterer_matches_reference(random_corpus):
    docs, _ = random_corpus
    key = jax.random.PRNGKey(5)
    ref = get_clusterer("fpf").cluster(docs, 10, key)
    fused = get_clusterer("fpf_fused").cluster(docs, 10, key)
    assert np.array_equal(np.asarray(ref.assign), np.asarray(fused.assign))
    np.testing.assert_allclose(np.asarray(ref.reps), np.asarray(fused.reps),
                               atol=0)


def test_build_path_parity_fpf_fused(random_corpus):
    """Acceptance bar: index.build(method="fpf_fused") == method="fpf"
    exactly, at a fixed seed (interpret mode on CPU)."""
    docs, spec = random_corpus
    key = jax.random.PRNGKey(7)
    a = ClusterPruneIndex.build(docs, spec, 12, n_clusterings=3,
                                method="fpf", key=key)
    b = ClusterPruneIndex.build(docs, spec, 12, n_clusterings=3,
                                method="fpf_fused", key=key)
    assert b.method == "fpf_fused"
    assert np.array_equal(np.asarray(a.buckets), np.asarray(b.buckets))
    assert np.array_equal(np.asarray(a.counts), np.asarray(b.counts))
    np.testing.assert_allclose(np.asarray(a.leaders), np.asarray(b.leaders),
                               atol=0)


def test_fpf_centers_exported_and_deterministic():
    x = jax.random.normal(jax.random.PRNGKey(1), (200, 16))
    x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
    c1 = fpf_centers(x, 6, jax.random.PRNGKey(2))
    c2 = fpf_centers(x, 6, jax.random.PRNGKey(2))
    assert np.array_equal(np.asarray(c1), np.asarray(c2))
    assert len(set(np.asarray(c1).tolist())) == 6     # distinct centers


# ------------------------------------------------------ incremental: adds
@pytest.fixture()
def built_index(random_corpus):
    """Index over the first 1000 docs; the remaining 200 are add fodder."""
    docs, spec = random_corpus
    idx = ClusterPruneIndex.build(docs[:1000], spec, 16, n_clusterings=3,
                                  method="fpf", key=jax.random.PRNGKey(0))
    return idx, docs, spec


def test_assign_multi_matches_per_clustering_loop(built_index):
    """The fused (T·K) assignment matmul must reproduce a per-T loop of the
    shared assignment primitive exactly — batched ingest may not change
    where a document lands."""
    idx, docs, spec = built_index
    x = docs[1000:1100]
    multi_a, multi_s = assign_to_centers_multi(x, idx.leaders, chunk=32)
    for ti in range(idx.leaders.shape[0]):
        a, s = assign_to_centers(x, idx.leaders[ti], chunk=32)
        assert np.array_equal(np.asarray(multi_a[ti]), np.asarray(a)), ti
        np.testing.assert_allclose(
            np.asarray(multi_s[ti]), np.asarray(s), atol=1e-6
        )


def test_batched_ingest_matches_one_by_one(built_index):
    """One 100-doc add == 100 single-doc adds: same buckets, same counts
    (the single host-side scatter fills free slots deterministically)."""
    import copy

    idx, docs, spec = built_index
    idx2 = copy.deepcopy(idx)
    idx.add_documents(docs[1000:1100])
    for i in range(1000, 1100):
        idx2.add_documents(docs[i:i + 1])
    assert np.array_equal(np.asarray(idx.counts), np.asarray(idx2.counts))
    assert np.array_equal(np.asarray(idx.assign), np.asarray(idx2.assign))
    # bucket membership agrees as sets per bucket (insertion order within
    # a bucket's free slots is an implementation detail)
    b1, b2 = np.asarray(idx.buckets), np.asarray(idx2.buckets)
    assert b1.shape == b2.shape
    assert np.array_equal(np.sort(b1, axis=-1), np.sort(b2, axis=-1))


def test_add_documents_ids_and_state(built_index):
    idx, docs, spec = built_index
    v0 = idx.version
    ids = idx.add_documents(docs[1000:1100])
    assert np.array_equal(ids, np.arange(1000, 1100))
    assert idx.n_docs == 1100 and idx.n_live == 1100
    assert idx.version == v0 + 1
    assert idx.n_mutations == 100
    assert idx.assign.shape == (3, 1100)
    assert "_local_bucket_major" not in vars(idx)     # lazily re-packed
    # counts stay consistent with bucket contents
    bk = np.asarray(idx.buckets)
    assert int((bk < 1100).sum()) == 3 * 1100
    assert int(np.asarray(idx.counts).sum()) == 3 * 1100


def test_added_docs_retrievable_on_every_backend(case_engine, built_index):
    """A copy of doc q is q's true nearest neighbour: after adding copies,
    every backend must return the copy as hit #1 for like=q."""
    idx, docs, spec = built_index
    src = np.asarray([3, 141, 592, 888])
    new_ids = idx.add_documents(docs[src])
    qw = weighted_query(docs[src], jnp.full((4, 3), 1 / 3), spec)
    for backend in BACKENDS:
        s, ids, _ = case_engine(idx, backend).search(
            qw, probes=12, k=5, exclude=jnp.asarray(src, jnp.int32)
        )
        top = np.asarray(ids)[:, 0]
        assert np.array_equal(top, new_ids), (backend, top, new_ids)


def test_full_probe_after_add_is_exact(built_index):
    idx, docs, spec = built_index
    idx.add_documents(docs[1000:])
    qw = weighted_query(docs[37:41], jnp.ones((4, 3)) / 3, spec)
    _, ids, _ = idx.search(qw, probes=3 * 16, k=7)
    _, gt_i = brute_force_topk(idx.docs, qw, 7)
    assert np.array_equal(np.sort(np.asarray(ids)), np.sort(np.asarray(gt_i)))


def test_bucket_padding_grows_on_overflow(built_index):
    """Adding many near-identical docs overflows one bucket: B must grow to
    the next sublane multiple of 8 and every copy stays retrievable."""
    idx, docs, spec = built_index
    b_before = idx.buckets.shape[-1]
    clones = jnp.tile(docs[7][None, :], (b_before + 5, 1))
    new_ids = idx.add_documents(clones)
    b_after = idx.buckets.shape[-1]
    assert b_after > b_before and b_after % 8 == 0
    qw = weighted_query(docs[7][None], jnp.ones((1, 3)) / 3, spec)
    _, ids, _ = idx.search(qw, probes=3 * 16, k=len(new_ids),
                           exclude=jnp.asarray([7], jnp.int32))
    got = set(np.asarray(ids).reshape(-1).tolist())
    assert set(new_ids.tolist()) <= got


def test_add_rejects_bad_dim(built_index):
    idx, docs, spec = built_index
    with pytest.raises(ValueError, match="concat dim"):
        idx.add_documents(jnp.ones((2, 5)))
    assert idx.add_documents(jnp.zeros((0, spec.total_dim))).size == 0


# --------------------------------------------------- incremental: removes
def test_removed_docs_never_returned(case_engine, built_index):
    idx, docs, spec = built_index
    qw = weighted_query(docs[10:14], jnp.ones((4, 3)) / 3, spec)
    _, ids0, _ = idx.search(qw, probes=12, k=5)
    victims = np.unique(np.asarray(ids0).reshape(-1))
    victims = victims[victims >= 0][:6]
    n_removed = idx.remove_documents(victims)
    assert n_removed == len(victims)
    assert idx.n_live == 1000 - n_removed
    for backend in BACKENDS:
        _, ids, _ = case_engine(idx, backend).search(qw, probes=48, k=10)
        live = np.asarray(ids).reshape(-1)
        assert not set(victims.tolist()) & set(live[live >= 0].tolist())
    # double-remove is a no-op, out-of-range raises
    assert idx.remove_documents(victims) == 0
    with pytest.raises(ValueError, match="doc ids must be in"):
        idx.remove_documents([10_000])


def test_remove_then_add_reuses_slots(built_index):
    """Tombstoned slots become free capacity: remove then add the same
    number of docs and the bucket padding does not grow."""
    idx, docs, spec = built_index
    b_before = idx.buckets.shape[-1]
    idx.remove_documents(np.arange(100))
    counts_after_rm = int(np.asarray(idx.counts).sum())
    assert counts_after_rm == 3 * 900
    idx.add_documents(docs[1000:1100])
    assert idx.buckets.shape[-1] == b_before
    assert int(np.asarray(idx.counts).sum()) == 3 * 1000
    # the removed ids stay dead even after the add reused their slots
    qw = weighted_query(docs[50:54], jnp.ones((4, 3)) / 3, spec)
    _, ids, _ = idx.search(qw, probes=48, k=10)
    live = np.asarray(ids).reshape(-1)
    assert not (set(range(100)) & set(live[live >= 0].tolist()))


# ------------------------------------------------- ladder drift + roundtrip
def test_ladder_stale_tracks_drift(built_index):
    from repro.core import calibrate_index

    idx, docs, spec = built_index
    assert not idx.ladder_stale                       # no ladder yet
    calibrate_index(idx, n_queries=8, n_weight_draws=2, probe_grid=(3, 12))
    assert not idx.ladder_stale and idx.n_mutations == 0
    idx.add_documents(docs[1000:1040])                # 4% churn: fine
    assert not idx.ladder_stale
    idx.add_documents(docs[1040:1150])                # ~14% total: stale
    assert idx.n_mutations > LADDER_DRIFT_THRESHOLD * idx.n_live
    assert idx.ladder_stale
    # refitting resets the drift counter
    calibrate_index(idx, n_queries=8, n_weight_draws=2, probe_grid=(3, 12))
    assert not idx.ladder_stale and idx.n_mutations == 0


def test_mutated_index_save_load_roundtrip(tmp_path, built_index):
    from repro.core import calibrate_index

    idx, docs, spec = built_index
    calibrate_index(idx, n_queries=8, n_weight_draws=2, probe_grid=(3, 12))
    idx.add_documents(docs[1000:1150])
    idx.remove_documents([4, 9, 1003])
    assert idx.ladder_stale
    path = tmp_path / "mutated.npz"
    idx.save(path)
    loaded = ClusterPruneIndex.load(path)

    assert loaded.n_docs == idx.n_docs
    assert loaded.n_live == idx.n_live
    assert np.array_equal(np.asarray(loaded.buckets), np.asarray(idx.buckets))
    assert np.array_equal(loaded.removed, idx.removed)
    assert loaded.n_mutations == idx.n_mutations
    assert loaded.ladder is not None
    assert loaded.ladder_stale                        # staleness survives
    # search parity original vs loaded (removed stay removed)
    qw = weighted_query(docs[20:24], jnp.ones((4, 3)) / 3, spec)
    _, i0, _ = idx.search(qw, probes=12, k=8)
    _, i1, _ = loaded.search(qw, probes=12, k=8)
    assert np.array_equal(np.asarray(i0), np.asarray(i1))
    for gone in (4, 9, 1003):
        assert gone not in np.asarray(i1).reshape(-1).tolist()


def test_calibrate_masks_removed_docs(built_index):
    """Ground truth of a calibration on a mutated index must not count
    unreachable (tombstoned) docs as misses."""
    from repro.core import calibrate_index

    idx, docs, spec = built_index
    idx.remove_documents(np.arange(0, 1000, 3))       # remove a third
    ladder = calibrate_index(idx, n_queries=8, n_weight_draws=2,
                             probe_grid=(3, 48))
    # at full probes everything reachable is found -> fitted recall == 1
    assert ladder.recall[-1] >= 0.999


# --------------------------------------------- incremental: quality floors
@pytest.mark.slow
def test_incremental_add_quality_floors(case_engine, ):
    """Acceptance bar: after ingesting >=10% new docs WITHOUT a rebuild,
    every engine backend stays within the tests/test_quality.py CR/NAG
    floors (same corpus recipe and metrics; fixed seeds — the pipeline is
    deterministic, so a drop beyond the floors is a real semantic change),
    and the ingested docs do show up in answers."""
    from repro.data import CorpusConfig, make_corpus

    docs_np, spec, _ = make_corpus(CorpusConfig(
        n_docs=1500, field_dims=(64, 64, 128),
        vocab_sizes=(800, 1200, 3000), n_topics=200, topic_mix_alpha=1.0,
        noise_terms=(4, 2, 24), seed=3,
    ))
    docs = jnp.asarray(docs_np)
    n_base = 1350                                      # ingest 150 = 10%
    index = ClusterPruneIndex.build(
        docs[:n_base], spec, 40, n_clusterings=3, method="fpf",
        key=jax.random.PRNGKey(2),
    )
    new_ids = index.add_documents(docs[n_base:])
    assert index.n_docs == 1500

    rng = np.random.default_rng(11)
    qids = jnp.asarray(rng.choice(1500, 32, replace=False), jnp.int32)
    weight_sets = ((1 / 3, 1 / 3, 1 / 3), (0.6, 0.2, 0.2), (0.15, 0.15, 0.7))
    floors = ((6, 5.5, 0.90), (12, 7.0, 0.93), (24, 8.3, 0.955))

    cells = []
    for w in weight_sets:
        qw = weighted_query(
            docs[qids], jnp.tile(jnp.asarray(w, jnp.float32)[None], (32, 1)),
            spec,
        )
        gt_s, gt_i = brute_force_topk(docs, qw, 10, exclude=qids)
        far_s, _ = brute_force_bottomk(docs, qw, 10, exclude=qids)
        cells.append((qw, gt_s, gt_i, far_s))

    added_seen = 0
    for backend in BACKENDS:
        engine = case_engine(index, backend)
        for probes, cr_floor, nag_floor in floors:
            for wi, (qw, gt_s, gt_i, far_s) in enumerate(cells):
                s, ids, _ = engine.search(qw, probes=probes, k=10,
                                          exclude=qids)
                cr = float(jnp.mean(competitive_recall(ids, gt_i)))
                nag = float(jnp.mean(
                    normalized_aggregate_goodness(s, gt_s, far_s)))
                assert cr >= cr_floor, (
                    f"{backend}, probes={probes}, weight set {wi}: CR "
                    f"{cr:.3f} below the {cr_floor} floor after a 10% "
                    f"incremental ingest")
                assert nag >= nag_floor, (
                    f"{backend}, probes={probes}, weight set {wi}: NAG "
                    f"{nag:.4f} below the {nag_floor} floor after a 10% "
                    f"incremental ingest")
                added_seen += int(np.sum(np.asarray(ids) >= n_base))
    assert added_seen > 0, "no ingested doc ever surfaced in a top-k"
    assert new_ids[0] == n_base


@pytest.mark.slow
def test_incremental_add_close_to_rebuild():
    """Parity-vs-rebuild: the incrementally-updated index tracks a from-
    scratch rebuild of the same mutated corpus within a small CR delta."""
    from repro.data import CorpusConfig, make_corpus

    docs_np, spec, _ = make_corpus(CorpusConfig(
        n_docs=1500, field_dims=(64, 64, 128),
        vocab_sizes=(800, 1200, 3000), n_topics=200, topic_mix_alpha=1.0,
        noise_terms=(4, 2, 24), seed=3,
    ))
    docs = jnp.asarray(docs_np)
    key = jax.random.PRNGKey(2)
    incr = ClusterPruneIndex.build(docs[:1350], spec, 40, n_clusterings=3,
                                   method="fpf", key=key)
    incr.add_documents(docs[1350:])
    full = ClusterPruneIndex.build(docs, spec, 40, n_clusterings=3,
                                   method="fpf", key=key)

    rng = np.random.default_rng(11)
    qids = jnp.asarray(rng.choice(1500, 32, replace=False), jnp.int32)
    qw = weighted_query(docs[qids], jnp.full((32, 3), 1 / 3), spec)
    _, gt_i = brute_force_topk(docs, qw, 10, exclude=qids)
    for probes in (6, 12, 24):
        _, ids_i, _ = incr.search(qw, probes=probes, k=10, exclude=qids)
        _, ids_f, _ = full.search(qw, probes=probes, k=10, exclude=qids)
        cr_i = float(jnp.mean(competitive_recall(ids_i, gt_i)))
        cr_f = float(jnp.mean(competitive_recall(ids_f, gt_i)))
        assert cr_i >= cr_f - 0.75, (probes, cr_i, cr_f)
