"""Backend parity: reference / fused (interpret) / sharded must be ONE
algorithm executed three ways — identical top-k ids, scores (to float
tolerance), and n_scored cost accounting on the same built index."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ClusterPruneIndex,
    FieldSpec,
    available_backends,
    get_engine,
    normalize_fields,
    pick_backend,
    split_probes,
    weighted_query,
)

BACKENDS = ("reference", "fused", "sharded")


@pytest.fixture(scope="module")
def engine_corpus():
    """Gaussian corpus (no duplicate vectors => no score ties => the top-k
    is unique and parity can demand exact id equality)."""
    spec = FieldSpec(names=("a", "b", "c"), dims=(32, 32, 64))
    x = jax.random.normal(jax.random.PRNGKey(7), (640, spec.total_dim))
    return normalize_fields(x, spec), spec


@pytest.fixture(scope="module")
def built_index(engine_corpus):
    docs, spec = engine_corpus
    return ClusterPruneIndex.build(
        docs, spec, 16, n_clusterings=3, method="fpf",
        key=jax.random.PRNGKey(0), pack_major=True,
    )


def _assert_parity(ref, other, name):
    s_ref, i_ref, n_ref = (np.asarray(a) for a in ref)
    s, i, n = (np.asarray(a) for a in other)
    assert np.array_equal(i, i_ref), f"{name}: top-k ids diverge"
    np.testing.assert_allclose(s, s_ref, atol=1e-5, err_msg=name)
    assert np.array_equal(n, n_ref), f"{name}: n_scored diverges"


@pytest.mark.parametrize("backend", BACKENDS[1:])
def test_backend_parity_plain(built_index, engine_corpus, backend):
    docs, spec = engine_corpus
    qw = docs[20:36]
    ref = get_engine(built_index, "reference").search(qw, probes=6, k=10)
    out = get_engine(built_index, backend).search(qw, probes=6, k=10)
    _assert_parity(ref, out, backend)


@pytest.mark.parametrize("backend", BACKENDS[1:])
def test_backend_parity_exclude(built_index, engine_corpus, backend):
    """Self-exclusion must mask the same doc in every backend."""
    docs, spec = engine_corpus
    qids = jnp.arange(8, dtype=jnp.int32)
    qw = docs[:8]
    ref = get_engine(built_index, "reference").search(
        qw, probes=6, k=10, exclude=qids
    )
    out = get_engine(built_index, backend).search(
        qw, probes=6, k=10, exclude=qids
    )
    _assert_parity(ref, out, backend)
    assert not np.any(np.asarray(out[1]) == np.arange(8)[:, None])


@pytest.mark.parametrize("backend", BACKENDS[1:])
def test_backend_parity_weighted(built_index, engine_corpus, backend):
    """The dynamically-weighted path (the paper's setting)."""
    docs, spec = engine_corpus
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.dirichlet([1.0] * spec.s, 12), jnp.float32)
    q = docs[100:112]
    ref = get_engine(built_index, "reference").search_weighted(
        q, w, probes=9, k=7
    )
    out = get_engine(built_index, backend).search_weighted(
        q, w, probes=9, k=7
    )
    _assert_parity(ref, out, backend)


def test_index_search_delegates_to_backends(built_index, engine_corpus):
    """ClusterPruneIndex.search(backend=...) is the same seam."""
    docs, spec = engine_corpus
    qw = docs[5:9]
    ref = built_index.search(qw, probes=6, k=5)
    for backend in BACKENDS[1:]:
        out = built_index.search(qw, probes=6, k=5, backend=backend)
        _assert_parity(ref, out, backend)


def test_single_query_shape(built_index, engine_corpus):
    docs, spec = engine_corpus
    w1 = jnp.ones((spec.s,)) / spec.s
    for backend in BACKENDS:
        eng = get_engine(built_index, backend)
        s, i, n = eng.search(docs[3], probes=6, k=5)
        assert s.shape == (5,) and i.shape == (5,) and n.shape == ()
        # 1-D weighted queries keep the squeezed shape too (matches the
        # ClusterPruneIndex.search_weighted contract)
        s, i, n = eng.search_weighted(docs[3], w1, probes=6, k=5)
        assert s.shape == (5,) and i.shape == (5,) and n.shape == ()


def test_nav_query_routes_probing(built_index, engine_corpus):
    """All backends navigate with nav_query but score with qw (CellDec
    semantics) — so they must still agree with each other."""
    docs, _ = engine_corpus
    qw = docs[40:48]
    nav = docs[48:56]
    ref = get_engine(built_index, "reference").search(
        qw, probes=6, k=10, nav_query=nav
    )
    for backend in BACKENDS[1:]:
        out = get_engine(built_index, backend).search(
            qw, probes=6, k=10, nav_query=nav
        )
        _assert_parity(ref, out, backend)


def test_n_scored_counts_probed_buckets(built_index):
    """n_scored == members of probed buckets (dups included) + T*K leaders."""
    idx = built_index
    qw = idx.docs[7:8]
    t, k_clusters = idx.counts.shape
    probes_t = split_probes(6, t)
    lsims = jnp.einsum("tkd,qd->qtk", idx.leaders, qw)
    expected = t * k_clusters
    for ti, p in enumerate(probes_t):
        _, top_c = jax.lax.top_k(lsims[:, ti, :], p)
        expected += int(jnp.sum(idx.counts[ti][top_c[0]]))
    for backend in BACKENDS:
        _, _, n = get_engine(built_index, backend).search(qw, probes=6, k=5)
        assert int(n[0]) == expected, backend


def test_registry_and_autopick():
    assert set(BACKENDS) <= set(available_backends())
    assert pick_backend() in available_backends()
    with pytest.raises(ValueError, match="unknown backend"):
        get_engine(object(), "no-such-backend")


# ------------------------------------------------------- size-aware pick
# Index metadata only: the pick reads shapes, never the arrays.
V5E_LIMIT = 16_900_000_000   # about what one v5e chip reports as bytes_limit


def _meta(n, k_clusters, b, pack_dtype=None, t=3, d=4096):
    return types.SimpleNamespace(
        docs=jax.ShapeDtypeStruct((n, d), jnp.float32),
        buckets=jax.ShapeDtypeStruct((t, k_clusters, b), jnp.int32),
        pack_dtype=pack_dtype,
    )


TS1 = _meta(50_000, 500, 312)       # 7.67 GB fp32 pack
TS2 = _meta(100_000, 1000, 568)     # 27.9 GB fp32 pack
TS2_INT8 = _meta(100_000, 1000, 568, "int8")


@pytest.mark.parametrize("platform, devices, limit, index, expected", [
    ("tpu", 1, V5E_LIMIT, TS1, "fused"),
    ("tpu", 1, V5E_LIMIT, TS2, "fused"),       # nothing to shard over
    ("tpu", 4, V5E_LIMIT, TS1, "fused"),       # fits one chip
    ("tpu", 4, V5E_LIMIT, TS2, "sharded"),
    ("tpu", 4, V5E_LIMIT, TS2_INT8, "fused"),  # a quarter of the bytes
    ("tpu", 4, V5E_LIMIT, None, "fused"),
    ("tpu", 4, 0, TS2, "fused"),               # no limit reported
    ("cpu", 1, 0, TS2, "reference"),
    ("cpu", 4, 0, TS1, "sharded"),
    ("cpu", 4, 0, None, "sharded"),
])
def test_pick_by_platform_and_size(monkeypatch, platform, devices, limit,
                                   index, expected):
    from repro.core import engine

    monkeypatch.setattr(engine, "_host", lambda: (platform, devices, limit))
    assert pick_backend(index) == expected


def test_exec_shape_picks_through_the_index(monkeypatch):
    from repro.core import SearchRequest, exec_shape
    from repro.core import engine

    monkeypatch.setattr(engine, "_host", lambda: ("tpu", 4, V5E_LIMIT))
    req = SearchRequest(query=np.ones(4096, np.float32))
    shape = lambda index: exec_shape(req, default_backend="auto",
                                     default_probes=12, index=index).backend
    assert (shape(TS1), shape(TS2)) == ("fused", "sharded")


def test_pick_leaves_its_marker(monkeypatch):
    from repro import tracing
    from repro.core import engine

    marks = []

    class Recorder:
        def __init__(self, name, **args):
            marks.append((name, args))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(engine, "_host", lambda: ("tpu", 4, V5E_LIMIT))
    monkeypatch.setattr(engine, "span", Recorder)
    assert pick_backend(TS2) == "sharded"
    assert engine.pack_bytes(TS2) == 3 * 1000 * 568 * 4096 * 4
    assert marks == [(tracing.ENGINE_PICK, {
        "backend": "sharded", "pack_bytes": 3 * 1000 * 568 * 4096 * 4,
        "bytes_limit": V5E_LIMIT, "devices": 4})]


# --------------------------------------------------------- v2 query tiling
# The fused backend serves through the query-tiled bucket_score v2 kernel:
# queries are grouped into QT-row tiles, each tile gets a deduplicated probe
# schedule, and ragged batch tails are padded to the tile and sliced off.
# These tests pin the tiling edges with a KNOWN tile size.
QT = 8


@pytest.mark.parametrize("nq", [1, QT - 1, QT, QT + 1, 3 * QT + 5])
def test_tiled_parity_ragged_batches(built_index, engine_corpus, nq):
    """Fused-vs-reference parity at every ragged-tail shape around the
    query tile, with a per-query exclude (self-exclusion pattern)."""
    docs, _ = engine_corpus
    qw = docs[100:100 + nq]
    ex = jnp.arange(100, 100 + nq, dtype=jnp.int32)
    ref = get_engine(built_index, "reference").search(
        qw, probes=6, k=10, exclude=ex
    )
    out = get_engine(built_index, "fused", query_tile=QT).search(
        qw, probes=6, k=10, exclude=ex
    )
    _assert_parity(ref, out, f"fused-tiled nq={nq}")


def test_tiled_shared_bucket_dedup(built_index, engine_corpus):
    """A tile of IDENTICAL queries probes identical buckets: the schedule
    collapses to one copy of each bucket, and the in-tile cross-clustering
    dedup must still return each doc id once per query — same answer as
    the per-query reference."""
    docs, _ = engine_corpus
    qw = jnp.tile(docs[42:43], (QT, 1))                  # one shared tile
    # per-query exclude differs across the tile, so the shared schedule
    # must not leak one query's exclusion into its neighbours
    ex = jnp.asarray([42, -1] * (QT // 2), jnp.int32)
    ref = get_engine(built_index, "reference").search(
        qw, probes=9, k=10, exclude=ex
    )
    out = get_engine(built_index, "fused", query_tile=QT).search(
        qw, probes=9, k=10, exclude=ex
    )
    _assert_parity(ref, out, "fused-shared-tile")
    # dedup inside the tile: no duplicate ids within any query's top-k
    ids = np.asarray(out[1])
    for row in ids:
        live = row[row >= 0]
        assert len(set(live.tolist())) == len(live)


def test_tiled_schedule_is_deduplicated(built_index, engine_corpus):
    """The engine-side scheduler reads each shared bucket once per tile:
    identical queries => schedule length == one query's probe count, not
    QT times it."""
    from repro.kernels.bucket_score.ops import build_probe_schedule

    docs, _ = engine_corpus
    eng = get_engine(built_index, "fused", query_tile=QT)
    nav = jnp.tile(docs[42:43], (QT, 1))
    flat = eng._flat_probes(nav, eng._probes_t(9))        # (QT, 9)
    sched, member = build_probe_schedule(np.asarray(flat), QT)
    live = member[0].any(axis=1)
    assert sched.shape[0] == 1
    assert live.sum() == 9                                # dedup'd union
    assert member[0][live].all()                          # every query member


def test_engine_cache_keyed_by_opts(built_index):
    """Variant engines (sweep qchunks, tile overrides) are cached per opts
    — no per-call reconstruction — while distinct opts stay distinct."""
    e1 = get_engine(built_index, "reference", qchunk=4)
    e2 = get_engine(built_index, "reference", qchunk=4)
    e3 = get_engine(built_index, "reference", qchunk=2)
    assert e1 is e2 and e1 is not e3
    f1 = get_engine(built_index, "fused", query_tile=QT)
    f2 = get_engine(built_index, "fused", query_tile=QT)
    assert f1 is f2 and f1 is not get_engine(built_index, "fused")


# ------------------------------------------------------------- bf16 pack
@pytest.fixture(scope="module")
def bf16_index(built_index):
    """The SAME clustering with half-precision bucket-major storage (the
    repack is a pure layout/precision transform — clustering, leaders and
    buckets are shared, so probing is identical)."""
    import dataclasses

    return dataclasses.replace(
        built_index, bucket_data=None, pack_dtype="bfloat16"
    )


def test_bf16_pack_halves_bucket_major_bytes(built_index, bf16_index):
    d32, i32, sc32 = built_index.ensure_bucket_major()
    d16, i16, sc16 = bf16_index.ensure_bucket_major()
    assert d16.dtype == jnp.bfloat16
    assert d16.nbytes * 2 == d32.nbytes
    assert np.array_equal(np.asarray(i16), np.asarray(i32))
    assert sc32 is None and sc16 is None      # scales are an int8-only thing


@pytest.mark.parametrize("nq", [1, QT - 1, 2 * QT + 3])
def test_bf16_pack_parity(built_index, bf16_index, engine_corpus, nq):
    """bf16 storage: EXACT id parity against the reference engine scoring
    the same bf16-quantised values (storage precision is the only degree
    of freedom), score parity to bf16 tolerance and identical n_scored
    against the full-precision reference (navigation keeps fp32 leaders)."""
    import dataclasses

    docs, _ = engine_corpus
    qw = docs[200:200 + nq]
    ex = jnp.arange(200, 200 + nq, dtype=jnp.int32)
    out = get_engine(bf16_index, "fused", query_tile=QT).search(
        qw, probes=6, k=10, exclude=ex
    )
    # fp32 reference: scores drift only by storage quantisation
    ref = get_engine(built_index, "reference").search(
        qw, probes=6, k=10, exclude=ex
    )
    np.testing.assert_allclose(
        np.asarray(out[0]), np.asarray(ref[0]), atol=2e-2
    )
    assert np.array_equal(np.asarray(out[2]), np.asarray(ref[2]))
    # quantised twin: reference engine over bf16-rounded docs and queries
    # reproduces the kernel's candidate scores -> ids must match EXACTLY
    quant = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    twin = dataclasses.replace(built_index, docs=quant(built_index.docs))
    tref = get_engine(twin, "reference").search(
        quant(qw), probes=6, k=10, exclude=ex, nav_query=qw
    )
    assert np.array_equal(np.asarray(out[1]), np.asarray(tref[1])), (
        "bf16 fused ids diverge from the bf16-quantised reference"
    )


# ------------------------------------------------------------- int8 pack
@pytest.fixture(scope="module")
def int8_index(built_index):
    """The SAME clustering with int8 quantised bucket-major storage —
    probing (fp32 leaders) and bucket membership are untouched; only the
    stored vector precision drops."""
    import dataclasses

    return dataclasses.replace(
        built_index, bucket_data=None, bucket_scales=None, pack_dtype="int8"
    )


def test_int8_pack_quarters_bucket_major_bytes(built_index, int8_index):
    d32, i32, sc32 = built_index.ensure_bucket_major()
    d8, i8, sc8 = int8_index.ensure_bucket_major()
    assert d8.dtype == jnp.int8
    assert d8.nbytes * 4 == d32.nbytes
    assert np.array_equal(np.asarray(i8), np.asarray(i32))
    assert sc32 is None
    assert sc8 is not None and sc8.shape == (i8.shape[0],)
    assert np.all(np.asarray(sc8) > 0)


@pytest.mark.parametrize("nq", [1, QT - 1, 2 * QT + 3])
def test_int8_pack_parity(built_index, int8_index, engine_corpus, nq):
    """int8 storage: n_scored identical to the fp32 reference (navigation
    is untouched), scores within the quantisation tolerance, and top-k ids
    overlapping near-perfectly at every ragged batch shape."""
    docs, _ = engine_corpus
    qw = docs[200:200 + nq]
    ex = jnp.arange(200, 200 + nq, dtype=jnp.int32)
    out = get_engine(int8_index, "fused", query_tile=QT).search(
        qw, probes=6, k=10, exclude=ex
    )
    ref = get_engine(built_index, "reference").search(
        qw, probes=6, k=10, exclude=ex
    )
    assert np.array_equal(np.asarray(out[2]), np.asarray(ref[2]))
    np.testing.assert_allclose(
        np.asarray(out[0]), np.asarray(ref[0]), atol=3e-2
    )
    i_out, i_ref = np.atleast_2d(np.asarray(out[1])), np.atleast_2d(
        np.asarray(ref[1]))
    overlap = np.mean([
        len(set(a.tolist()) & set(b.tolist())) / 10
        for a, b in zip(i_out, i_ref)
    ])
    assert overlap >= 0.9, overlap


# ---------------------------------------------------------- rescore tail
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("nq", [1, QT - 1, QT + 1])
def test_rescore_fp32_identity(built_index, engine_corpus, backend, nq):
    """On an fp32 pack the exact-rescore tail re-scores already-exact
    candidates: ids and scores are IDENTICAL to the plain search on every
    backend and ragged shape, and only n_scored grows (the re-scored
    candidates are honestly charged)."""
    docs, _ = engine_corpus
    qw = docs[100:100 + nq]
    ex = jnp.arange(100, 100 + nq, dtype=jnp.int32)
    eng = get_engine(built_index, backend)
    s0, i0, n0 = eng.search(qw, probes=6, k=10, exclude=ex)
    s1, i1, n1 = eng.search(qw, probes=6, k=10, exclude=ex, rescore=25)
    assert np.array_equal(np.asarray(i0), np.asarray(i1)), backend
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1), atol=1e-5)
    assert np.all(np.asarray(n1) > np.asarray(n0))
    # accounting: exactly the valid depth-25 candidates were re-scored
    _, i_deep, n_deep = eng.search(qw, probes=6, k=25, exclude=ex)
    extra = np.sum(np.atleast_2d(np.asarray(i_deep)) >= 0, axis=-1)
    assert np.array_equal(
        np.asarray(n1).reshape(-1), np.asarray(n_deep).reshape(-1) + extra
    )


def test_rescore_validates_depth(built_index, engine_corpus):
    docs, _ = engine_corpus
    with pytest.raises(ValueError, match="rescore depth"):
        get_engine(built_index, "reference").search(
            docs[:4], probes=6, k=10, rescore=5
        )


@pytest.mark.parametrize("nq", [1, QT - 1, 2 * QT + 3])
def test_rescore_exact_scores_on_quantised_packs(
    built_index, bf16_index, int8_index, engine_corpus, nq
):
    """The rescore tail's contract on quantised storage: every returned
    score is the EXACT fp32 dot of the returned doc — storage noise can
    change which candidates surface, never the reported order/scores of
    the ones that do."""
    docs, _ = engine_corpus
    qw = docs[300:300 + nq]
    ex = jnp.arange(300, 300 + nq, dtype=jnp.int32)
    for idx, label in ((bf16_index, "bf16"), (int8_index, "int8")):
        s, ids, _ = get_engine(idx, "fused", query_tile=QT).search(
            qw, probes=6, k=10, exclude=ex, rescore=20
        )
        s = np.atleast_2d(np.asarray(s))
        ids = np.atleast_2d(np.asarray(ids))
        qn = np.asarray(qw)
        dn = np.asarray(built_index.docs)
        for r in range(s.shape[0]):
            live = ids[r] >= 0
            exact = dn[ids[r][live]] @ qn[r]
            np.testing.assert_allclose(
                s[r][live], exact, atol=1e-5, err_msg=f"{label} row {r}"
            )
            # descending order on the exact scores
            assert np.all(np.diff(s[r][live]) <= 1e-6), label


def test_int8_rescore_recovers_fp32_topk(built_index, int8_index,
                                         engine_corpus):
    """With a generous rescore depth the int8 fused path returns the SAME
    top-k as the fp32 reference on this corpus — the quantised search only
    proposes candidates; the fp32 tail ranks them."""
    docs, _ = engine_corpus
    qw = docs[20:36]
    ref = get_engine(built_index, "reference").search(qw, probes=6, k=10)
    out = get_engine(int8_index, "fused", query_tile=QT).search(
        qw, probes=6, k=10, rescore=30
    )
    assert np.array_equal(np.asarray(out[1]), np.asarray(ref[1]))
    np.testing.assert_allclose(
        np.asarray(out[0]), np.asarray(ref[0]), atol=1e-5
    )


# ------------------------------------------------- device-side scheduling
@pytest.mark.parametrize("nq", [1, QT - 1, QT, QT + 1, 3 * QT + 5])
def test_device_schedule_matches_host_schedule_end_to_end(
    built_index, engine_corpus, nq
):
    """The fused engine's jitted device schedule and the host-numpy oracle
    schedule drive the tiled kernel to IDENTICAL results on every ragged
    shape (exclude + cross-clustering dedup + ragged tails)."""
    from repro.kernels.bucket_score import bucket_score_tiled
    from repro.kernels.bucket_score.ops import (
        build_probe_schedule, build_probe_schedule_device, schedule_length,
    )

    docs, _ = engine_corpus
    qw = docs[100:100 + nq]
    ex = jnp.arange(100, 100 + nq, dtype=jnp.int32)
    eng = get_engine(built_index, "fused", query_tile=QT)
    data, ids, scales = built_index.ensure_bucket_major()
    flat = eng._flat_probes(qw, eng._probes_t(6))
    hs, hm = build_probe_schedule(np.asarray(flat), QT)
    s_len = schedule_length(QT, int(flat.shape[1]), int(ids.shape[0]))
    ds, dm = build_probe_schedule_device(flat, query_tile=QT, s_len=s_len)
    host = bucket_score_tiled(qw, data, ids, jnp.asarray(hs),
                              jnp.asarray(hm), k=10, exclude=ex,
                              scales=scales)
    dev = bucket_score_tiled(qw, data, ids, ds, dm, k=10, exclude=ex,
                             scales=scales)
    assert np.array_equal(np.asarray(host[1]), np.asarray(dev[1])), nq
    np.testing.assert_allclose(
        np.asarray(host[0]), np.asarray(dev[0]), atol=1e-6
    )


def test_fused_search_builds_schedule_under_jit(built_index, engine_corpus,
                                                monkeypatch):
    """No host numpy in the fused hot path: FusedEngine.search must never
    call the host scheduler (the device builder is jitted end to end)."""
    import importlib

    ops = importlib.import_module("repro.kernels.bucket_score.ops")

    def _boom(*a, **k):
        raise AssertionError(
            "FusedEngine.search called the host build_probe_schedule"
        )

    monkeypatch.setattr(ops, "build_probe_schedule", _boom)
    docs, _ = engine_corpus
    out = get_engine(built_index, "fused", query_tile=QT).search(
        docs[10:22], probes=6, k=5
    )
    ref = get_engine(built_index, "reference").search(
        docs[10:22], probes=6, k=5
    )
    _assert_parity(ref, out, "fused-device-schedule")


def test_lazy_bucket_major(engine_corpus):
    """A build that defers packing still serves fused via lazy conversion."""
    docs, spec = engine_corpus
    idx = ClusterPruneIndex.build(
        docs, spec, 16, n_clusterings=2, pack_major=False,
    )
    assert idx.bucket_data is None
    qw = docs[10:14]
    ref = get_engine(idx, "reference").search(qw, probes=4, k=5)
    out = get_engine(idx, "fused").search(qw, probes=4, k=5)
    assert idx.bucket_data is not None            # cached after first use
    _assert_parity(ref, out, "fused-lazy")


# ----------------------------------------------------------- tiered exact
def _gt(index, qw, k, exclude):
    from repro.core import brute_force_topk

    return brute_force_topk(index.docs, jnp.atleast_2d(qw), k,
                            exclude=jnp.atleast_1d(exclude))


@pytest.mark.parametrize("backend", BACKENDS)
def test_exact_tier_matches_brute_force(built_index, engine_corpus, backend):
    """search_exact sweeps all T*K buckets: ids identical to brute force,
    scores to float tolerance, on every backend."""
    docs, _ = engine_corpus
    qw = docs[20:36]
    ex = jnp.arange(20, 36, dtype=jnp.int32)
    s, i, n = get_engine(built_index, backend).search_exact(
        qw, k=10, exclude=ex
    )
    gt_s, gt_i = _gt(built_index, qw, 10, ex)
    assert np.array_equal(np.asarray(i), np.asarray(gt_i)), backend
    np.testing.assert_allclose(
        np.asarray(s), np.asarray(gt_s), atol=1e-5, err_msg=backend
    )
    # honest accounting: every member of every bucket of every clustering
    # was scored, plus the T*K leader comparisons
    t, kc = built_index.counts.shape
    expected = int(jnp.sum(built_index.counts)) + int(t * kc)
    assert np.all(np.asarray(n) == expected), backend


def test_exact_tier_single_query_shape(built_index, engine_corpus):
    docs, _ = engine_corpus
    s, i, n = get_engine(built_index, "reference").search_exact(docs[3], k=5)
    assert s.shape == (5,) and i.shape == (5,) and n.shape == ()


@pytest.mark.parametrize("pack", ["bf16", "int8"])
def test_exact_tier_quantised_packs(built_index, bf16_index, int8_index,
                                    engine_corpus, pack):
    """The exact tier on a quantised fused pack routes through the forced
    fp32 rescore: returned ids AND scores match fp32 brute force exactly —
    the quantised sweep only proposes, the fp32 tail ranks."""
    idx = bf16_index if pack == "bf16" else int8_index
    docs, _ = engine_corpus
    qw = docs[200:200 + QT + 3]
    ex = jnp.arange(200, 200 + QT + 3, dtype=jnp.int32)
    s, i, _ = get_engine(idx, "fused", query_tile=QT).search_exact(
        qw, k=10, exclude=ex
    )
    gt_s, gt_i = _gt(built_index, qw, 10, ex)
    assert np.array_equal(np.asarray(i), np.asarray(gt_i)), pack
    np.testing.assert_allclose(
        np.asarray(s), np.asarray(gt_s), atol=1e-5, err_msg=pack
    )


@pytest.mark.parametrize("backend", BACKENDS)
def test_oversized_probes_clamp(built_index, engine_corpus, backend):
    """Regression: an explicit probes= budget past T*K used to push
    jax.lax.top_k(lsims, p) past K and die with an opaque XLA error; it
    now clamps to the documented probe-everything = exact semantics."""
    docs, _ = engine_corpus
    qw = docs[50:58]
    eng = get_engine(built_index, backend)
    t, kc = built_index.counts.shape
    total = int(t * kc)
    s_all, i_all, n_all = eng.search(qw, probes=total, k=10)
    s, i, n = eng.search(qw, probes=10_000, k=10)
    assert np.array_equal(np.asarray(i), np.asarray(i_all)), backend
    np.testing.assert_allclose(
        np.asarray(s), np.asarray(s_all), atol=1e-6, err_msg=backend
    )
    assert np.array_equal(np.asarray(n), np.asarray(n_all)), backend


# ------------------------------------------------------ escalation driver
@pytest.fixture()
def laddered_index(built_index):
    """A copy of the built index carrying a hand-made two-rung ladder (the
    driver consumes rungs + fitted recall; a synthetic fit keeps the test
    deterministic and cheap)."""
    import dataclasses

    from repro.core.calibrate import ProbeLadder

    idx = dataclasses.replace(built_index)
    t, kc = (int(x) for x in built_index.counts.shape)
    idx.ladder = ProbeLadder(
        probes=(6, 24), recall=(0.6, 0.9),
        n_clusterings=t, k_clusters=kc,
    )
    return idx


def test_escalation_meets_floor_at_next_rung(laddered_index, engine_corpus):
    docs, _ = engine_corpus
    eng = get_engine(laddered_index, "reference")
    qw = docs[20:28]
    s, i, n, info = eng.search_escalating(qw, probes=6, k=10, min_recall=0.8)
    assert info["tier"] == "escalated"
    assert info["escalations"] == 1
    assert info["probes"] == 24
    assert info["predicted_recall"] == pytest.approx(0.9)
    # honest cumulative accounting: both passes' candidates are charged
    _, _, n6 = eng.search(qw, probes=6, k=10)
    _, _, n24 = eng.search(qw, probes=24, k=10)
    assert np.array_equal(np.asarray(n), np.asarray(n6) + np.asarray(n24))
    # the answer is the final rung's answer
    _, i24, _ = eng.search(qw, probes=24, k=10)
    assert np.array_equal(np.asarray(i), np.asarray(i24))


def test_escalation_noop_when_prediction_meets_floor(laddered_index,
                                                     engine_corpus):
    docs, _ = engine_corpus
    eng = get_engine(laddered_index, "reference")
    qw = docs[20:28]
    s, i, n, info = eng.search_escalating(qw, probes=6, k=10, min_recall=0.5)
    assert info == {"tier": "approx", "escalations": 0, "probes": 6,
                    "predicted_recall": pytest.approx(0.6)}
    s0, i0, n0 = eng.search(qw, probes=6, k=10)
    assert np.array_equal(np.asarray(i), np.asarray(i0))
    assert np.array_equal(np.asarray(n), np.asarray(n0))


def test_escalation_unreachable_floor_hits_exact(laddered_index,
                                                 engine_corpus):
    """A floor above the ladder's fitted maximum escalates to the exact
    tier: brute-force-identical ids, predicted recall exactly 1.0."""
    docs, _ = engine_corpus
    eng = get_engine(laddered_index, "reference")
    qw = docs[40:44]
    ex = jnp.arange(40, 44, dtype=jnp.int32)
    s, i, n, info = eng.search_escalating(
        qw, probes=6, k=10, min_recall=0.99, exclude=ex
    )
    assert info["tier"] == "exact"
    assert info["predicted_recall"] == 1.0
    t, kc = laddered_index.counts.shape
    assert info["probes"] == int(t) * int(kc)
    _, gt_i = _gt(laddered_index, qw, 10, ex)
    assert np.array_equal(np.asarray(i), np.asarray(gt_i))


def test_escalation_without_ladder_goes_exact(built_index, engine_corpus):
    """No ladder => no prediction can state the floor; the only honest
    answer is the exact tier, after the requested approximate pass."""
    docs, _ = engine_corpus
    assert built_index.ladder is None
    eng = get_engine(built_index, "reference")
    s, i, n, info = eng.search_escalating(
        docs[20:24], probes=6, k=10, min_recall=0.9
    )
    assert info["tier"] == "exact" and info["escalations"] == 1
    _, gt_i = _gt(built_index, docs[20:24], 10,
                  jnp.full((4,), -1, jnp.int32))
    assert np.array_equal(np.asarray(i), np.asarray(gt_i))


def test_escalation_validates_floor(built_index, engine_corpus):
    docs, _ = engine_corpus
    with pytest.raises(ValueError, match="min_recall"):
        get_engine(built_index, "reference").search_escalating(
            docs[:2], probes=6, k=10, min_recall=1.5
        )


# ------------------------------------------------------- sharded-fused path
def test_sharded_navigation_runs_once(built_index, engine_corpus):
    """The sharded engine computes leader top-p ONCE per search: the same
    flat probe tensor feeds the replicated probe-dedup schedule and the
    n_scored accounting (the old path navigated in the shard_map body AND
    again on host for the cost numbers)."""
    docs, _ = engine_corpus
    eng = get_engine(built_index, "sharded", interpret=True)
    calls = {"n": 0}
    orig = type(eng)._flat_probes

    def counting(self, nav, probes_t):
        calls["n"] += 1
        return orig(self, nav, probes_t)

    try:
        type(eng)._flat_probes = counting
        eng.search(docs[20:28], probes=6, k=10)
    finally:
        type(eng)._flat_probes = orig
    assert calls["n"] == 1


def test_sharded_lazy_repack_on_mutation(engine_corpus):
    """One engine object across add/remove: the shard-local pack re-places
    itself on the first search after a version bump and stays in parity
    with a fresh reference engine."""
    docs, spec = engine_corpus
    idx = ClusterPruneIndex.build(
        docs, spec, 16, n_clusterings=3, method="fpf",
        key=jax.random.PRNGKey(0),
    )
    eng = get_engine(idx, "sharded", interpret=True)
    qw = docs[20:28]
    eng.search(qw, probes=6, k=10)
    v0 = eng._pack_version
    idx.add_documents(jax.random.normal(jax.random.PRNGKey(5),
                                        (3, spec.total_dim)))
    out = eng.search(qw, probes=6, k=10)
    assert eng._pack_version == idx.version != v0
    ref = get_engine(idx, "reference").search(qw, probes=6, k=10)
    _assert_parity(ref, out, "post-add sharded")
    idx.remove_documents([0, 1])
    out = eng.search(qw, probes=6, k=10)
    ref = get_engine(idx, "reference").search(qw, probes=6, k=10)
    _assert_parity(ref, out, "post-remove sharded")


def test_sharded_engine_cached_and_opts_keyed(built_index):
    """Sharded engines cache on the index like every backend, keyed by
    opts (the default mesh is constructed inside __init__, so the opts
    key stays hashable)."""
    e1 = get_engine(built_index, "sharded", interpret=True)
    e2 = get_engine(built_index, "sharded", interpret=True)
    e3 = get_engine(built_index, "sharded", interpret=True, query_tile=8)
    assert e1 is e2 and e1 is not e3


@pytest.mark.parametrize("nq", [1, 5])
def test_sharded_quantised_rescore_recovers_fp32(built_index, int8_index,
                                                 engine_corpus, nq):
    """int8 shard-local storage + the sharded rescore tail returns the
    fp32 reference's exact ids and scores — the distributed rescore
    (ownership masks + pmax all-reduce) is score-identical to the
    single-device gather rescore."""
    docs, _ = engine_corpus
    qw = docs[30:30 + nq]
    ref = get_engine(built_index, "reference").search(
        qw, probes=6, k=5, rescore=25
    )
    out = get_engine(int8_index, "sharded", interpret=True).search(
        qw, probes=6, k=5, rescore=25
    )
    _assert_parity(ref, out, "sharded int8 rescore")
