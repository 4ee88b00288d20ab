"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (
    bucket_score, bucket_score_ref, bucket_score_tiled,
    build_probe_schedule, build_probe_schedule_device,
    dequantize_bucket_major, embed_bag, embed_bag_ref,
    fpf_centers_fused, fpf_iter, fpf_iter_ref,
    pack_bucket_major, pick_query_tile,
    quantize_bucket_major, schedule_length,
    topk_score, topk_score_ref,
)
from repro.core import fpf_centers


@pytest.mark.parametrize("nq,n,d,k", [
    (1, 64, 32, 4), (5, 333, 96, 10), (16, 1024, 128, 32), (3, 50, 257, 8),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_topk_score_sweep(nq, n, d, k, dtype):
    kq, kd = jax.random.split(jax.random.PRNGKey(n + d))
    q = jax.random.normal(kq, (nq, d), jnp.float32).astype(dtype)
    docs = jax.random.normal(kd, (n, d), jnp.float32).astype(dtype)
    ex = jnp.arange(nq, dtype=jnp.int32) % n
    s, i = topk_score(q, docs, k=k, exclude=ex, block_q=8, block_n=64)
    rs, ri = topk_score_ref(q, docs, k, ex)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(s), np.asarray(rs), atol=tol,
                               rtol=tol)
    # ids may permute among ties under bf16; compare as sets per row
    for a, b in zip(np.asarray(i), np.asarray(ri)):
        assert set(a.tolist()) == set(b.tolist()) or dtype == jnp.bfloat16


@pytest.mark.parametrize("K,B,D,P,k", [
    (8, 16, 32, 2, 4), (12, 24, 64, 3, 8), (20, 40, 128, 6, 16),
])
def test_bucket_score_sweep(K, B, D, P, k):
    ks = jax.random.split(jax.random.PRNGKey(K * B), 5)
    bd = jax.random.normal(ks[0], (K, B, D))
    bi = jax.random.permutation(ks[1], K * B).reshape(K, B).astype(jnp.int32)
    bi = jnp.where(jax.random.uniform(ks[2], (K, B)) < 0.25, -1, bi)
    q = jax.random.normal(ks[3], (4, D))
    probes = jax.random.randint(ks[4], (4, P), 0, K)
    s, i = bucket_score(q, bd, bi, probes, k=k)
    rs, ri = bucket_score_ref(q, bd, bi, probes, k)
    np.testing.assert_allclose(np.asarray(s), np.asarray(rs), atol=1e-5)
    assert np.array_equal(np.sort(np.asarray(i)), np.sort(np.asarray(ri)))


@pytest.mark.parametrize("K,B,D,P,k", [
    (8, 16, 32, 2, 4), (12, 24, 64, 3, 8), (20, 40, 128, 6, 16),
])
@pytest.mark.parametrize("nq", [1, 7, 8, 9, 29])
def test_bucket_score_tiled_sweep(K, B, D, P, k, nq):
    """v2 tiled kernel over a dedup'd schedule == the v1 oracle on the same
    per-query probe lists, at every ragged batch shape."""
    ks = jax.random.split(jax.random.PRNGKey(K * B + nq), 5)
    bd = jax.random.normal(ks[0], (K, B, D))
    bi = jax.random.permutation(ks[1], K * B).reshape(K, B).astype(jnp.int32)
    bi = jnp.where(jax.random.uniform(ks[2], (K, B)) < 0.25, -1, bi)
    q = jax.random.normal(ks[3], (nq, D))
    probes = jax.random.randint(ks[4], (nq, P), 0, K)
    ex = jnp.where(
        jnp.arange(nq) % 2 == 0, jnp.abs(bi[0, 0]), -1
    ).astype(jnp.int32)
    sched, member = build_probe_schedule(np.asarray(probes), 8)
    s, i = bucket_score_tiled(
        q, bd, bi, jnp.asarray(sched), jnp.asarray(member), k=k, exclude=ex
    )
    rs, ri = bucket_score_ref(q, bd, bi, probes, k, exclude=ex)
    # rtol: the tiled (QT, D)x(D, B) matmul accumulates in a different
    # order than the oracle's einsum — fp32 reassociation noise only
    np.testing.assert_allclose(
        np.asarray(s), np.asarray(rs), atol=1e-5, rtol=1e-6
    )
    assert np.array_equal(np.sort(np.asarray(i)), np.sort(np.asarray(ri)))


def test_build_probe_schedule_dedups_shared_buckets():
    """A bucket probed by several queries of a tile appears ONCE in the
    tile's schedule (the HBM read amortises), membership reproduces each
    query's probe set exactly, and padded rows probe nothing."""
    probes = np.asarray([
        [3, 7, 1],
        [7, 3, 2],
        [3, 7, 1],
        [9, 3, 7],
        [5, 0, 4],
    ])
    sched, member = build_probe_schedule(probes, 4)
    assert sched.shape[0] == 2 and member.shape == (2, sched.shape[1], 4)
    for ti in range(2):
        live = member[ti].any(axis=1)
        row = sched[ti][live]
        assert len(set(row.tolist())) == len(row)         # dedup'd
        for q in range(4):
            gi = ti * 4 + q
            want = set(probes[gi].tolist()) if gi < len(probes) else set()
            got = set(sched[ti][member[ti, :, q] != 0].tolist())
            assert got == want, (ti, q)
    # tile 0 probes {3, 7} three times over -> one schedule slot each
    assert np.sum(sched[0][member[0].any(axis=1)] == 3) == 1
    assert np.sum(sched[0][member[0].any(axis=1)] == 7) == 1


@pytest.mark.parametrize("nq", [1, 7, 8, 9, 29])
@pytest.mark.parametrize("qt,p,nb", [(8, 3, 20), (8, 6, 12), (4, 5, 40)])
def test_build_probe_schedule_device_matches_host(nq, qt, p, nb):
    """The jittable device scheduler is semantically identical to the host
    numpy oracle at every ragged batch shape: same deduplicated live
    schedule per tile (both ascending), same per-query membership sets,
    zero membership on padded slots and padded query rows."""
    probes = jax.random.randint(
        jax.random.PRNGKey(nq * 31 + qt + p), (nq, p), 0, nb
    )
    hs, hm = build_probe_schedule(np.asarray(probes), qt)
    s_len = schedule_length(qt, p, nb)
    ds, dm = build_probe_schedule_device(probes, query_tile=qt, s_len=s_len)
    ds, dm = np.asarray(ds), np.asarray(dm)
    assert ds.shape == (hs.shape[0], s_len) and dm.shape[2] == qt
    assert s_len >= hs.shape[1] - 8 + 1        # host pads to 8, device to 2^j
    for ti in range(hs.shape[0]):
        h_live = hm[ti].any(axis=1)
        d_live = dm[ti].any(axis=1)
        # same dedup'd bucket set, both sorted ascending over live slots
        assert np.array_equal(hs[ti][h_live], ds[ti][d_live])
        for q in range(qt):
            want = set(hs[ti][hm[ti, :, q] != 0].tolist())
            got = set(ds[ti][dm[ti, :, q] != 0].tolist())
            assert got == want, (ti, q)
        # padded slots: bucket 0 with zero membership — consecutive equal
        # block indices, so the Pallas pipeline skips their repeat DMAs
        assert np.all(ds[ti][~d_live] == 0)


def _padded(sched, member, layout):
    """The same schedule with three dead slots (bucket 0, no member query)
    per live one: after the live slots (``tail``, the builders' layout), or
    before each of them (``between``, a layout no builder makes)."""
    n_tiles, s_len = sched.shape
    qt = member.shape[-1]
    ps = np.zeros((n_tiles, 4 * s_len), np.int32)
    pm = np.zeros((n_tiles, 4 * s_len, qt), np.int32)
    at = np.arange(s_len) if layout == "tail" else 4 * np.arange(s_len) + 3
    ps[:, at], pm[:, at] = sched, member
    return ps, pm


@pytest.mark.parametrize("layout", ["tail", "between"])
@pytest.mark.parametrize(
    "pack", [None, jnp.bfloat16, jnp.int8],
    ids=["float32", "bfloat16", "int8"],
)
def test_bucket_score_tiled_padded_slots_change_no_answer(pack, layout):
    """Dead schedule slots are skipped, not scored, and the answer is the
    tight schedule's bit for bit: every pack dtype, a per-query exclude, a
    ragged last tile, and a live slot after a dead one still scored."""
    K, B, D, P, k, nq, qt = 12, 24, 64, 3, 8, 13, 8
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    docs = jax.random.normal(ks[0], (K * B, D)) / np.sqrt(D)
    buckets = jax.random.permutation(ks[1], K * B).reshape(K, B)
    buckets = jnp.where(
        jax.random.uniform(ks[2], (K, B)) < 0.25, -1, buckets
    ).astype(jnp.int32)
    bd, bi, sc = pack_bucket_major(docs, buckets, dtype=pack)
    q = jax.random.normal(ks[3], (nq, D)) / np.sqrt(D)
    probes = np.asarray(jax.random.randint(ks[4], (nq, P), 0, K))
    ex = jnp.asarray(np.where(np.arange(nq) % 2 == 0,
                              np.asarray(bi)[probes[:, 0], 1], -1),
                     jnp.int32)
    sched, member = build_probe_schedule(probes, qt, pad_multiple=1)
    got = [
        bucket_score_tiled(q, bd, bi, jnp.asarray(a), jnp.asarray(m), k=k,
                           exclude=ex, scales=sc)
        for a, m in [(sched, member), _padded(sched, member, layout)]
    ]
    (s0, i0), (s1, i1) = (tuple(map(np.asarray, g)) for g in got)
    assert np.array_equal(s0, s1) and np.array_equal(i0, i1)
    assert np.isfinite(s0).all() and (i0 >= 0).all()
    # the exclusion held: no query's excluded document is in its answer
    assert not (i0 == np.asarray(ex)[:, None]).any()


def test_bucket_score_tiled_tile_with_no_live_slot():
    """A tile none of whose queries probes anything (query padding, probes
    all -1) comes back ``(-inf, -1)`` in every rank; the tile before it is
    answered as the oracle answers."""
    K, B, D, P, k, qt = 10, 16, 32, 3, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    bd = jax.random.normal(ks[0], (K, B, D))
    bi = jnp.arange(K * B, dtype=jnp.int32).reshape(K, B)
    q = jax.random.normal(ks[1], (2 * qt, D))
    probes = jnp.concatenate([
        jax.random.randint(ks[2], (qt, P), 0, K),
        jnp.full((qt, P), -1, jnp.int32),
    ])
    sched, member = build_probe_schedule_device(
        probes, query_tile=qt, s_len=schedule_length(qt, P, K))
    assert not np.asarray(member)[1].any()
    s, i = (np.asarray(x) for x in bucket_score_tiled(
        q, bd, bi, sched, member, k=k))
    assert np.all(s[qt:] == -np.inf) and np.all(i[qt:] == -1)
    rs, ri = bucket_score_ref(q[:qt], bd, bi, probes[:qt], k)
    np.testing.assert_allclose(s[:qt], np.asarray(rs), atol=1e-5, rtol=1e-6)
    assert np.array_equal(np.sort(i[:qt]), np.sort(np.asarray(ri)))


@pytest.mark.parametrize("seed", range(4))
def test_probe_schedules_put_live_slots_first(seed):
    """Both schedule builders put every live slot (one some query of the
    tile probes) before every dead one: the prefix the tiled kernel skips
    the rest of. Random probe lists with ``-1`` entries, ragged tiles."""
    rng = np.random.default_rng(seed)
    nq, qt, p, nb = int(rng.integers(1, 40)), 8, 5, 30
    probes = rng.integers(0, nb, (nq, p)).astype(np.int32)
    probes[rng.random((nq, p)) < 0.4] = -1
    probes[: min(nq, 3)] = -1                  # queries that probe nothing
    built = [
        build_probe_schedule(probes, qt),
        build_probe_schedule_device(jnp.asarray(probes), query_tile=qt,
                                    s_len=schedule_length(qt, p, nb)),
    ]
    for _, member in built:
        live = np.asarray(member).any(axis=-1)            # (n_tiles, S)
        n_live = live.sum(axis=1)
        prefix = np.arange(live.shape[1])[None, :] < n_live[:, None]
        assert np.array_equal(live, prefix)
    # both builders find the same live count per tile
    assert np.array_equal(*(np.asarray(m).any(-1).sum(1) for _, m in built))


def test_quantize_bucket_major_error_bound():
    """Property test for the symmetric per-bucket int8 quantiser: every
    element round-trips within scale/2 (round-to-nearest), scales are
    strictly positive, values stay in [-127, 127], and an all-zero bucket
    takes scale 1 (finite dequant)."""
    for seed in range(4):
        x = jax.random.normal(jax.random.PRNGKey(seed), (6, 16, 24))
        x = x * (10.0 ** jax.random.randint(
            jax.random.PRNGKey(100 + seed), (6, 1, 1), -2, 3))
        x = x.at[0].set(0.0)                          # empty-bucket edge
        q, scales = quantize_bucket_major(x)
        assert q.dtype == jnp.int8 and scales.shape == (6,)
        sc = np.asarray(scales)
        assert np.all(sc > 0) and sc[0] == 1.0
        qn = np.asarray(q, np.int32)
        assert qn.min() >= -127 and qn.max() <= 127
        deq = np.asarray(dequantize_bucket_major(q, scales))
        err = np.abs(deq - np.asarray(x))
        assert np.all(err <= sc[:, None, None] / 2 + 1e-7)


@pytest.mark.parametrize("nq", [1, 8, 29])
def test_bucket_score_tiled_int8_vs_dequant_oracle(nq):
    """The int8 tiled kernel (int8→bf16 operands, fp32 accumulation,
    per-bucket scale on the score block) tracks the fp32 oracle over the
    DEQUANTISED values: ids overlap near-perfectly and scores agree to the
    kernel's bf16 query-cast tolerance."""
    K, B, D, P, k = 12, 24, 64, 3, 8
    ks = jax.random.split(jax.random.PRNGKey(nq + 100), 5)
    docs = jax.random.normal(ks[0], (K * B, D)) / np.sqrt(D)
    buckets = jax.random.permutation(ks[1], K * B).reshape(K, B)
    buckets = jnp.where(
        jax.random.uniform(ks[2], (K, B)) < 0.25, -1, buckets
    ).astype(jnp.int32)
    q = jax.random.normal(ks[3], (nq, D)) / np.sqrt(D)
    probes = jax.random.randint(ks[4], (nq, P), 0, K)
    ex = jnp.where(jnp.arange(nq) % 2 == 0, jnp.abs(buckets[0, 0]), -1
                   ).astype(jnp.int32)

    d8, i8, sc = pack_bucket_major(docs, buckets, dtype=jnp.int8)
    assert d8.dtype == jnp.int8 and sc is not None
    s_len = schedule_length(8, P, K)
    sched, member = build_probe_schedule_device(probes, query_tile=8,
                                                s_len=s_len)
    s, i = bucket_score_tiled(q, d8, i8, sched, member, k=k, exclude=ex,
                              scales=sc)
    rs, ri = bucket_score_ref(q, d8, i8, probes, k, exclude=ex, scales=sc)
    # scores: kernel casts the fp32 query to bf16; the oracle does not
    finite = np.isfinite(np.asarray(rs))
    np.testing.assert_allclose(
        np.asarray(s)[finite], np.asarray(rs)[finite], atol=5e-3
    )
    overlap = np.mean([
        len(set(a.tolist()) & set(b.tolist())) / k
        for a, b in zip(np.asarray(i), np.asarray(ri))
    ])
    assert overlap >= 0.95, overlap


def test_bucket_score_tiled_int8_requires_scales():
    d8, i8, _ = pack_bucket_major(
        jnp.ones((8, 4)), jnp.arange(8, dtype=jnp.int32).reshape(2, 4),
        dtype=jnp.int8,
    )
    sched, member = build_probe_schedule(np.asarray([[0, 1]]), 8)
    with pytest.raises(ValueError, match="scales"):
        bucket_score_tiled(
            jnp.ones((1, 4)), d8, i8, jnp.asarray(sched),
            jnp.asarray(member), k=2,
        )
    with pytest.raises(ValueError, match="scales"):
        bucket_score_ref(
            jnp.ones((1, 4)), d8, i8, jnp.asarray([[0, 1]]), 2
        )


def test_pick_query_tile_respects_vmem_budget():
    """QT solves QT·D + B·D·(itemsize/4) + QT·B + 2·QT·k_pad <= budget
    words, clamped to [8, max_tile] and a sublane multiple of 8."""
    qt = pick_query_tile(512, 128, k_pad=64, budget_bytes=2**20)
    words = qt * 512 + 128 * 512 + qt * 128 + 2 * qt * 64
    assert words * 4 <= 2**20 and qt % 8 == 0 and qt >= 8
    # a bucket block that alone overflows the budget still yields the floor
    assert pick_query_tile(4096, 4096, budget_bytes=2**20) == 8
    assert pick_query_tile(64, 8, max_tile=32) == 32


def test_pick_query_tile_reduced_pack_buys_larger_tile():
    """The bucket-block term of the VMEM formula scales with the pack
    itemsize: bf16 halves it and int8 quarters it, so at a budget the fp32
    block nearly fills, the quantised packs free words for MORE queries per
    tile (monotone in itemsize) while staying within budget."""
    d, b, k_pad, budget = 512, 512, 64, 2**20
    qts = {
        sz: pick_query_tile(
            d, b, k_pad=k_pad, budget_bytes=budget, max_tile=1024,
            pack_itemsize=sz,
        )
        for sz in (4, 2, 1)
    }
    assert qts[1] >= qts[2] >= qts[4]
    # the fp32 block alone fills this budget -> clamp floor; int8 frees 3/4
    # of it and buys a real tile
    assert qts[4] == 8 and qts[1] > qts[4]
    for sz in (1, 2):                          # quantised packs stay in budget
        qt = qts[sz]
        words = qt * d + (b * d * sz) // 4 + qt * b + 2 * qt * k_pad
        assert words * 4 <= budget


def test_schedule_length_bucketing():
    """Static S is the power-of-two ceiling of the tight per-tile bound
    min(QT·P, n_buckets) — monotone in both arguments and never below a
    tile's possible unique-bucket count."""
    assert schedule_length(8, 6, 48) == 64           # QT·P=48 <= 48 -> 64
    assert schedule_length(8, 6, 30) == 32           # capped by n_buckets
    assert schedule_length(8, 1, 1000) == 8
    assert schedule_length(1, 1, 1) == 1
    assert schedule_length(16, 9, 10_000) == 256     # pow2ceil(144)
    for qt, p, nb in [(8, 3, 20), (16, 6, 48), (8, 12, 36)]:
        s = schedule_length(qt, p, nb)
        assert s >= min(qt * p, nb) and (s & (s - 1)) == 0


def test_pack_bucket_major_bf16_halves_bytes():
    """The bf16 pack stores the SAME layout at half the HBM bytes."""
    docs = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    buckets = jnp.arange(64, dtype=jnp.int32).reshape(8, 8)
    d32, i32, sc32 = pack_bucket_major(docs, buckets)
    d16, i16, sc16 = pack_bucket_major(docs, buckets, dtype=jnp.bfloat16)
    assert d16.dtype == jnp.bfloat16 and d32.dtype == jnp.float32
    assert d16.nbytes * 2 == d32.nbytes
    assert sc32 is None and sc16 is None
    # int8: quarter the fp32 packed bytes, same layout, per-bucket scales
    d8, i8_, sc8 = pack_bucket_major(docs, buckets, dtype=jnp.int8)
    assert d8.dtype == jnp.int8 and d8.nbytes * 4 == d32.nbytes
    assert np.array_equal(np.asarray(i8_), np.asarray(i32))
    assert sc8.shape == (8,) and sc8.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(d8, np.float32) * np.asarray(sc8)[:, None, None],
        np.asarray(d32), atol=float(np.max(np.asarray(sc8))) / 2 + 1e-7,
    )
    assert np.array_equal(np.asarray(i16), np.asarray(i32))
    np.testing.assert_allclose(
        np.asarray(d16, np.float32), np.asarray(d32), atol=1e-2
    )


def test_bucket_score_dedups_across_clusterings():
    """The same doc id in two probed buckets must be returned once."""
    D = 16
    doc = jnp.ones((1, D)) / jnp.sqrt(D)
    bd = jnp.tile(doc, (2, 4, 1))          # 2 buckets, same vectors
    bi = jnp.asarray([[7, -1, -1, -1], [7, 3, -1, -1]], jnp.int32)
    q = doc
    s, i = bucket_score(q, bd, bi, jnp.asarray([[0, 1]]), k=4)
    live = [x for x in np.asarray(i)[0].tolist() if x >= 0]
    assert sorted(live) == [3, 7]


def test_bucket_score_tiled_ties_keep_accumulator_then_lane_order():
    """Every candidate scores the same: the tiled kernel's merge must keep
    the accumulator ahead of the block and the lower lane ahead within
    each, drop the id already held (cross-clustering dup), and fill the
    accumulator's last ``-inf`` slot from the block."""
    D = 16
    doc = jnp.ones((1, D)) / jnp.sqrt(D)
    bd = jnp.tile(doc, (3, 4, 1))                  # 3 buckets, equal vectors
    bi = jnp.asarray(
        [[10, 11, 12, 13], [20, 11, 22, 23], [30, 31, 32, 33]], jnp.int32
    )
    sched, member = build_probe_schedule(np.asarray([[0, 1, 2]]), 8)
    s, i = bucket_score_tiled(
        doc, bd, bi, jnp.asarray(sched), jnp.asarray(member), k=8
    )
    assert np.asarray(i)[0].tolist() == [10, 11, 12, 13, 20, 22, 23, 30]
    assert np.all(np.asarray(s) == np.asarray(s)[0, 0])


@pytest.mark.parametrize("tied", [(70, 100), (70, 100, 150), (130, 20)])
def test_fpf_iter_tie_takes_lowest_index(tied):
    """Points equally far from the center set, inside one tile and across
    tiles: the next center is the lowest index, as ``jnp.argmin`` picks."""
    m, d = 200, 16
    x = jax.random.normal(jax.random.PRNGKey(1), (m, d))
    x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
    x = x.at[jnp.asarray(tied)].set(-x[0])         # the farthest points
    ms = jnp.full((m,), -jnp.inf)
    _, idx, _ = fpf_iter(x, x[0], ms, block_m=64)
    _, ridx, _ = fpf_iter_ref(x, x[0], ms)
    assert int(idx) == int(ridx) == min(tied)


@pytest.mark.parametrize("m,d", [(64, 16), (200, 32), (1000, 128)])
def test_fpf_iter_sweep(m, d):
    x = jax.random.normal(jax.random.PRNGKey(m), (m, d))
    x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
    ms = jnp.full((m,), -jnp.inf)
    for c_idx in (0, m // 2):
        nm, idx, val = fpf_iter(x, x[c_idx], ms, block_m=64)
        rm, ridx, rval = fpf_iter_ref(x, x[c_idx], ms)
        np.testing.assert_allclose(np.asarray(nm), np.asarray(rm), atol=1e-5)
        assert int(idx) == int(ridx)
        ms = nm


def test_fpf_fused_full_loop_matches_core():
    x = jax.random.normal(jax.random.PRNGKey(0), (150, 24))
    x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
    key = jax.random.PRNGKey(4)
    assert np.array_equal(
        np.asarray(fpf_centers_fused(x, 6, key)),
        np.asarray(fpf_centers(x, 6, key)),
    )


@pytest.mark.parametrize("V,E,B,L", [(50, 8, 4, 3), (200, 32, 16, 7),
                                     (1000, 128, 8, 20)])
@pytest.mark.parametrize("combiner", ["sum", "mean"])
def test_embed_bag_sweep(V, E, B, L, combiner):
    ks = jax.random.split(jax.random.PRNGKey(V + L), 3)
    tbl = jax.random.normal(ks[0], (V, E))
    idx = jax.random.randint(ks[1], (B, L), -1, V)
    out = embed_bag(tbl, idx, combiner=combiner)
    ref = embed_bag_ref(tbl, idx, combiner=combiner)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_embed_bag_weighted_and_empty_bag():
    tbl = jnp.eye(4, dtype=jnp.float32)
    idx = jnp.asarray([[0, 1], [-1, -1]], jnp.int32)
    w = jnp.asarray([[2.0, 3.0], [1.0, 1.0]])
    out = embed_bag(tbl, idx, w)
    np.testing.assert_allclose(np.asarray(out[0]), [2, 3, 0, 0], atol=1e-6)
    np.testing.assert_allclose(np.asarray(out[1]), [0, 0, 0, 0], atol=1e-6)


def test_pack_bucket_major_roundtrip(random_corpus):
    docs, spec = random_corpus
    from repro.core import ClusterPruneIndex

    idx = ClusterPruneIndex.build(docs, spec, 10, n_clusterings=1)
    buckets = jnp.where(idx.buckets[0] < docs.shape[0], idx.buckets[0], -1)
    data, ids, _ = pack_bucket_major(docs, buckets)
    live = np.asarray(ids) >= 0
    gathered = np.asarray(data)[live]
    expected = np.asarray(docs)[np.asarray(ids)[live]]
    np.testing.assert_allclose(gathered, expected, atol=1e-6)
