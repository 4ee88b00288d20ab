"""Behaviour tests of the cluster-prune index + baselines + metrics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CellDecIndex,
    ClusterPruneIndex,
    CorruptIndexError,
    brute_force_bottomk,
    brute_force_topk,
    competitive_recall,
    fpf_cluster,
    kmeans_cluster,
    normalized_aggregate_goodness,
    random_leader_cluster,
    region_of,
    weighted_query,
)


def test_brute_force_is_exact(random_corpus):
    docs, spec = random_corpus
    q = docs[3:7]
    s, i = brute_force_topk(docs, q, 5)
    ref = jnp.argsort(-(q @ docs.T), axis=-1)[:, :5]
    assert np.array_equal(np.asarray(i), np.asarray(ref))
    fs, fi = brute_force_bottomk(docs, q, 5)
    ref_far = jnp.argsort(q @ docs.T, axis=-1)[:, :5]
    assert set(map(int, fi[0])) == set(map(int, ref_far[0]))


@pytest.mark.parametrize("method", ["fpf", "fpf_fused", "kmeans", "random"])
def test_clusterers_cover(random_corpus, method):
    docs, spec = random_corpus
    from repro.core import get_clusterer

    res = get_clusterer(method).cluster(docs, 16, jax.random.PRNGKey(0))
    assert res.reps.shape == (16, docs.shape[1])
    assert int(jnp.sum(res.counts)) == docs.shape[0]
    assert float(res.max_radius) <= 2.0 + 1e-5


def test_fpf_centers_are_spread(random_corpus):
    """FPF picks far-apart centers: max pairwise similarity bounded.

    Representatives are compared on the unit sphere (FPF medoids are corpus
    vectors of norm sqrt(s); random-leader reps are unit centroids)."""
    docs, _ = random_corpus

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    res = fpf_cluster(docs, 8, jax.random.PRNGKey(1))
    sims = unit(res.reps) @ unit(res.reps).T - jnp.eye(8)
    rand = random_leader_cluster(docs, 8, jax.random.PRNGKey(1))
    rand_sims = unit(rand.reps) @ unit(rand.reps).T - jnp.eye(8)
    # spread property holds on average vs random leaders
    assert float(jnp.max(sims)) <= float(jnp.max(rand_sims)) + 0.05


def test_full_probe_equals_bruteforce(random_corpus):
    """Probing every cluster must return the exact answer."""
    docs, spec = random_corpus
    idx = ClusterPruneIndex.build(docs, spec, 12, n_clusterings=1)
    q = weighted_query(docs[5:9], jnp.ones((4, 3)) / 3, spec)
    s, i, _ = idx.search(q, probes=12, k=7)
    gt_s, gt_i = brute_force_topk(docs, q, 7)
    assert np.array_equal(np.sort(np.asarray(i)), np.sort(np.asarray(gt_i)))
    np.testing.assert_allclose(
        np.sort(np.asarray(s)), np.sort(np.asarray(gt_s)), atol=1e-5
    )


def test_recall_improves_with_probes(small_corpus):
    docs, spec, _ = small_corpus
    idx = ClusterPruneIndex.build(docs, spec, 40, n_clusterings=3)
    q = weighted_query(docs[10:40], jnp.ones((30, 3)) / 3, spec)
    gt_s, gt_i = brute_force_topk(docs, q, 10)
    last = -1.0
    for probes in (3, 9, 30):
        _, ids, _ = idx.search(q, probes=probes, k=10)
        rec = float(jnp.mean(competitive_recall(ids, gt_i)))
        assert rec >= last - 0.3     # monotone up to small noise
        last = rec
    assert last >= 8.0               # near-exhaustive at high probes


def test_no_duplicate_results(small_corpus):
    docs, spec, _ = small_corpus
    idx = ClusterPruneIndex.build(docs, spec, 30, n_clusterings=3)
    q = weighted_query(docs[:16], jnp.ones((16, 3)) / 3, spec)
    _, ids, _ = idx.search(q, probes=9, k=10)
    for row in np.asarray(ids):
        live = row[row >= 0]
        assert len(set(live.tolist())) == len(live)


def test_exclude_self(small_corpus):
    docs, spec, _ = small_corpus
    idx = ClusterPruneIndex.build(docs, spec, 30)
    qids = jnp.arange(8, dtype=jnp.int32)
    q = weighted_query(docs[:8], jnp.ones((8, 3)) / 3, spec)
    _, ids, _ = idx.search(q, probes=10, k=5, exclude=qids)
    assert not np.any(np.asarray(ids) == np.arange(8)[:, None])


def test_metrics_ranges(random_corpus):
    docs, spec = random_corpus
    q = weighted_query(docs[:5], jnp.ones((5, 3)) / 3, spec)
    gt_s, gt_i = brute_force_topk(docs, q, 6)
    far_s, _ = brute_force_bottomk(docs, q, 6)
    # perfect answer: recall k, NAG 1
    cr = competitive_recall(gt_i, gt_i)
    nag = normalized_aggregate_goodness(gt_s, gt_s, far_s)
    assert np.allclose(np.asarray(cr), 6)
    np.testing.assert_allclose(np.asarray(nag), 1.0, atol=1e-5)
    # worst answer: NAG 0
    nag0 = normalized_aggregate_goodness(far_s, gt_s, far_s)
    np.testing.assert_allclose(np.asarray(nag0), 0.0, atol=1e-5)


def test_celldec_regions():
    assert int(region_of(jnp.asarray([0.6, 0.2, 0.2]), 3)) == 0
    assert int(region_of(jnp.asarray([0.2, 0.6, 0.2]), 3)) == 1
    assert int(region_of(jnp.asarray([0.2, 0.2, 0.6]), 3)) == 2
    assert int(region_of(jnp.asarray([0.34, 0.33, 0.33]), 3)) == 3


def test_celldec_search(small_corpus):
    docs, spec, _ = small_corpus
    cd = CellDecIndex.build(docs, spec, 30, method="kmeans", iters=3)
    w = jnp.asarray([[0.6, 0.2, 0.2], [0.33, 0.34, 0.33]])
    s, i, n = cd.search_weighted(docs[4:6], w, probes=8, k=10)
    qw = weighted_query(docs[4:6], w, spec)
    gt_s, gt_i = brute_force_topk(docs, qw, 10)
    rec = float(jnp.mean(competitive_recall(i, gt_i)))
    assert rec >= 3.0                # approximate but sane


# ---------------------------------------------- pack_dtype + int8 scales
def test_validate_pack_dtype_single_gate(random_corpus):
    """One gate, one error message: build / ensure_local_bucket_major /
    load all reject unsupported dtypes through validate_pack_dtype."""
    import dataclasses

    from repro.core import (
        SUPPORTED_PACK_DTYPES, get_engine, validate_pack_dtype,
    )

    docs, spec = random_corpus
    assert validate_pack_dtype("float32") == "float32"
    assert validate_pack_dtype(jnp.bfloat16) == "bfloat16"
    assert validate_pack_dtype("int8") == "int8"
    assert set(SUPPORTED_PACK_DTYPES) == {"float32", "bfloat16", "int8"}
    for bad in ("float16", "int4", "not-a-dtype"):
        with pytest.raises(ValueError, match="unsupported pack_dtype"):
            validate_pack_dtype(bad)
    with pytest.raises(ValueError, match="unsupported pack_dtype"):
        ClusterPruneIndex.build(docs, spec, 8, pack_dtype="float16")
    # a twin mutated to a bad dtype is caught at the lazy re-pack, before
    # the fused engine ever sees malformed bucket storage
    idx = ClusterPruneIndex.build(docs, spec, 8)
    bad = dataclasses.replace(idx, pack_dtype="float64")
    with pytest.raises(ValueError, match="unsupported pack_dtype"):
        bad.ensure_local_bucket_major(1)


def test_int8_build_quarters_bytes_and_searches(random_corpus):
    """build(pack_dtype='int8') stores the bucket-major pack at a quarter
    of fp32 bytes, carries per-bucket scales, and serves searches."""
    docs, spec = random_corpus
    f32 = ClusterPruneIndex.build(docs, spec, 12, n_clusterings=3,
                                  key=jax.random.PRNGKey(0))
    i8 = ClusterPruneIndex.build(docs, spec, 12, n_clusterings=3,
                                 key=jax.random.PRNGKey(0),
                                 pack_dtype="int8")
    d32 = f32.ensure_local_bucket_major(1)[0]
    d8, _, sc8, _ = i8.ensure_local_bucket_major(1)
    assert d8.dtype == jnp.int8
    assert d8.nbytes * 4 == d32.nbytes
    assert sc8 is not None
    assert sc8.shape == d8.shape[:2]
    assert bool(jnp.all(sc8 > 0))
    q = weighted_query(docs[3:9], jnp.ones((6, 3)) / 3, spec)
    _, gt_i = brute_force_topk(docs, q, 5)
    _, ids, _ = i8.search(q, probes=8, k=5, backend="fused")
    rec = float(jnp.mean(competitive_recall(ids, gt_i)))
    assert rec >= 3.0


def test_int8_scales_survive_save_load(tmp_path, random_corpus):
    """A quantised index round-trips through save/load: the archive holds
    no pack and no scales, the (deterministic) lazy re-pack reproduces the
    int8 tensor and its per-bucket scales bit-exactly, and a loaded int8
    index answers identically to the original. An archive that still
    carries ``bucket_scales`` loads."""
    docs, spec = random_corpus
    idx = ClusterPruneIndex.build(docs, spec, 12, n_clusterings=3,
                                  key=jax.random.PRNGKey(0),
                                  pack_dtype="int8")
    path = tmp_path / "int8.npz"
    idx.save(path)
    members = dict(np.load(path))
    assert "bucket_scales" not in members
    loaded = ClusterPruneIndex.load(path)
    assert loaded.pack_dtype == "int8"
    d0, _, sc0, _ = idx.ensure_local_bucket_major(1)
    d1, _, sc1, _ = loaded.ensure_local_bucket_major(1)
    assert d1.dtype == jnp.int8
    assert np.array_equal(np.asarray(d1), np.asarray(d0))
    np.testing.assert_array_equal(np.asarray(sc1), np.asarray(sc0))
    legacy = tmp_path / "legacy.npz"
    np.savez(legacy, **members, bucket_scales=np.asarray(sc0)[0])
    assert ClusterPruneIndex.load(legacy).pack_dtype == "int8"
    q = weighted_query(docs[11:15], jnp.ones((4, 3)) / 3, spec)
    s0, i0, n0 = idx.search(q, probes=8, k=6, backend="fused")
    s1, i1, n1 = loaded.search(q, probes=8, k=6, backend="fused")
    assert np.array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1), atol=1e-6)
    assert np.array_equal(np.asarray(n0), np.asarray(n1))
    # fp32/bf16 packs carry no scales, before and after a round-trip
    f32 = ClusterPruneIndex.build(docs, spec, 12)
    p2 = tmp_path / "f32.npz"
    f32.save(p2)
    assert ClusterPruneIndex.load(p2).ensure_local_bucket_major(1)[2] is None


def test_paper_ordering_on_structured_corpus(small_corpus):
    """The paper's headline: Our (FPF multi) >= CellDec >= PODS07 recall
    at equal probe budgets, on a topical corpus with unequal weights."""
    docs, spec, _ = small_corpus
    n = docs.shape[0]
    k_clusters = 40
    rng = np.random.default_rng(0)
    qids = jnp.asarray(rng.choice(n, 40, replace=False), jnp.int32)
    w = jnp.asarray(
        np.tile([[0.6, 0.2, 0.2]], (40, 1)), jnp.float32
    )
    q = docs[qids]
    qw = weighted_query(q, w, spec)
    gt_s, gt_i = brute_force_topk(docs, qw, 10, exclude=qids)

    ours = ClusterPruneIndex.build(docs, spec, k_clusters, n_clusterings=3,
                                   method="fpf")
    pods = ClusterPruneIndex.build(docs, spec, k_clusters, n_clusterings=1,
                                   method="random")
    probes = 9
    _, ids_o, _ = ours.search(qw, probes=probes, k=10, exclude=qids)
    _, ids_p, _ = pods.search(qw, probes=probes, k=10, exclude=qids)
    rec_o = float(jnp.mean(competitive_recall(ids_o, gt_i)))
    rec_p = float(jnp.mean(competitive_recall(ids_p, gt_i)))
    assert rec_o >= rec_p - 0.2, (rec_o, rec_p)


def test_ensure_local_bucket_major_cache_and_invalidate(random_corpus):
    """The shard-local bucket-major pack: LOCAL ids, sentinel-free -1
    padding, per-shard-count caching, dropped on mutation, and int8
    per-(shard, bucket) scales quartering the packed bytes."""
    import dataclasses

    docs, spec = random_corpus
    idx = ClusterPruneIndex.build(docs, spec, 12, n_clusterings=3,
                                  method="fpf")
    n = idx.n_docs
    data, ids, scales, n_local = idx.ensure_local_bucket_major(4)
    s, tk, b_l, d = data.shape
    assert s == 4 and n_local == -(-n // 4)
    assert scales is None and data.dtype == jnp.float32
    # LOCAL ids: in [-1, n_local); every live doc appears in every clustering
    a = np.asarray(ids)
    assert a.min() >= -1 and a.max() < n_local
    t = idx.buckets.shape[0]
    assert (a >= 0).sum() == t * n
    # packed rows are the doc vectors they claim to be
    dd = np.asarray(docs)
    for sh in range(4):
        rows = np.argwhere(a[sh] >= 0)[:5]
        for bi, ci in rows:
            gid = sh * n_local + a[sh, bi, ci]
            np.testing.assert_allclose(
                np.asarray(data[sh, bi, ci]), dd[gid], atol=1e-6
            )
    # cached per shard count; invalidated (and re-derived) on mutation
    assert idx.ensure_local_bucket_major(4)[0] is data
    assert idx.ensure_local_bucket_major(2)[3] == -(-n // 2)
    idx.add_documents(jax.random.normal(jax.random.PRNGKey(3),
                                        (1, spec.total_dim)))
    data2, ids2, _, _ = idx.ensure_local_bucket_major(4)
    assert data2 is not data
    assert (np.asarray(ids2) >= 0).sum() == t * (n + 1)

    # int8: quarter the packed bytes, scales per (shard, bucket)
    i8 = dataclasses.replace(idx, pack_dtype="int8")
    d8, ids8, sc8, _ = i8.ensure_local_bucket_major(4)
    assert d8.dtype == jnp.int8 and sc8.shape == d8.shape[:2]
    assert d8.shape == data2.shape and data2.nbytes == 4 * d8.nbytes


# ------------------------------------------------------- persistence safety
def _built_index(random_corpus):
    docs, spec = random_corpus
    return ClusterPruneIndex.build(docs, spec, 12, n_clusterings=3,
                                   key=jax.random.PRNGKey(1))


def test_save_is_atomic_no_temp_debris(tmp_path, random_corpus):
    """save publishes via os.replace: the final file appears complete, no
    .tmp files survive, and a re-save over an existing file never leaves a
    mixed state (the previous archive stays intact until the rename)."""
    idx = _built_index(random_corpus)
    path = tmp_path / "idx"                       # suffix-less: .npz appended
    idx.save(path)
    final = tmp_path / "idx.npz"
    assert final.exists()
    assert [p.name for p in tmp_path.iterdir()] == ["idx.npz"]
    before = final.read_bytes()
    idx.save(path)                                # overwrite in place
    assert [p.name for p in tmp_path.iterdir()] == ["idx.npz"]
    loaded = ClusterPruneIndex.load(final)
    np.testing.assert_array_equal(np.asarray(loaded.docs),
                                  np.asarray(idx.docs))
    assert len(before) > 0


def test_load_truncated_archive_raises_typed(tmp_path, random_corpus):
    """A half-written/garbage file raises CorruptIndexError naming the
    file — not an opaque zipfile/numpy traceback."""
    idx = _built_index(random_corpus)
    path = tmp_path / "idx.npz"
    idx.save(path)
    blob = path.read_bytes()

    # truncation mid-archive: decompression of some member fails
    cut = tmp_path / "cut.npz"
    cut.write_bytes(blob[: len(blob) // 3])
    with pytest.raises(CorruptIndexError, match="cut.npz"):
        ClusterPruneIndex.load(cut)

    # not an archive at all
    junk = tmp_path / "junk.npz"
    junk.write_bytes(b"this is not an npz archive")
    with pytest.raises(CorruptIndexError, match="not a readable"):
        ClusterPruneIndex.load(junk)

    # a missing file is a missing file, not corruption
    with pytest.raises(FileNotFoundError):
        ClusterPruneIndex.load(tmp_path / "absent.npz")


def test_load_missing_and_mismatched_members_raise_typed(
    tmp_path, random_corpus
):
    idx = _built_index(random_corpus)
    good = tmp_path / "good.npz"
    idx.save(good)
    with np.load(good, allow_pickle=False) as z:
        members = {k: z[k] for k in z.files}

    # a member dropped entirely: the error names it
    partial = dict(members)
    del partial["docs"]
    p1 = tmp_path / "missing.npz"
    np.savez_compressed(p1, **partial)
    with pytest.raises(CorruptIndexError, match="'docs'"):
        ClusterPruneIndex.load(p1)

    # internally inconsistent members (partial overwrite): dims vs docs
    bad = dict(members)
    bad["dims"] = np.asarray([1, 1, 1], np.int64)
    p2 = tmp_path / "mismatch.npz"
    np.savez_compressed(p2, **bad)
    with pytest.raises(CorruptIndexError, match="internally inconsistent"):
        ClusterPruneIndex.load(p2)

    # invalid calibration JSON in the ladder slot
    bad2 = dict(members)
    bad2["ladder"] = np.str_('{"probes": "what"}')
    p3 = tmp_path / "badladder.npz"
    np.savez_compressed(p3, **bad2)
    with pytest.raises(CorruptIndexError, match="ladder"):
        ClusterPruneIndex.load(p3)
