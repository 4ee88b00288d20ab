"""``shard_map`` substrate of the **sharded** search backend.

This module is no longer a parallel, self-standing search API: it supplies
the collective primitives, the shard-local bucket-major packing, and the
doc-sharded search kernels that :class:`repro.core.engine.ShardedEngine`
wraps. Consumers should go through ``get_engine(index, "sharded")`` (or
``backend="sharded"`` on ``ClusterPruneIndex.search``), which layers the
shared probe-splitting, exclude-masking, and ``n_scored`` accounting on
top; the functions here stay public for the distributed tests and for the
exact brute-force baseline used by the ``retrieval_cand`` serving cells.

Layout (DESIGN.md §4/§6):

* **docs** row-sharded over the ``shard_axes`` (``("pod", "data")`` on the
  production mesh) — every device owns an ``n/devices`` slice. Corpora that
  do not divide evenly are padded with sentinel rows (zero vectors that no
  bucket ever references), so ANY corpus size shards cleanly.
* **leaders** replicated: ``T*K`` representatives are tiny (K ~ sqrt(n)).
* **buckets** are *local*: each device packs its own slice of every cluster,
  so probing cluster ``c`` touches every shard's local members of ``c`` —
  search work stays embarrassingly parallel and perfectly balanced. The
  fused path additionally packs each shard's slice **bucket-major**
  (:func:`pack_local_bucket_major`): a ``(S, T·K, B_l, D)`` tensor in the
  index's ``pack_dtype`` storage precision (bf16 halves, int8 quarters the
  per-shard HBM bytes, with per-``(shard, bucket)`` dequantisation scales),
  so a probed bucket is a contiguous device-local block DMA feeding a
  ``(QT, D)×(D, B_l)`` MXU matmul — the single-device fused v2 hot path,
  run shard-locally (:func:`distributed_bucket_score`).
* **navigation is replicated and runs ONCE**: leaders are global, so the
  probe sets (and the fused path's probe-dedup schedule) are identical on
  every shard — they are computed outside the ``shard_map`` body and passed
  in, never re-derived per shard.
* the only collective is the final **top-k merge**: ``all_gather`` of
  ``(k,)`` scores+ids per device (2·k·4 bytes each — collective-light by
  construction), then a replicated merge.

The same module provides the brute-force distributed top-k used by the
``retrieval_cand`` serving cells and as the exact baseline, plus the
sharded exact-rescore tail (:func:`distributed_exact_rescore`): candidates
are re-scored against the row-sharded fp32 corpus — each shard scores the
candidates it owns, a single ``pmax`` all-reduce merges the score matrix —
so quantised sharded packs meet the same quality floors as single-device
packs without ever gathering the corpus.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "local_topk",
    "merge_topk",
    "distributed_brute_topk",
    "distributed_index_search",
    "distributed_bucket_score",
    "distributed_exact_rescore",
    "pack_local_bucket_major",
    "shard_docs",
    "shard_rows",
]


def shard_rows(n: int, n_shards: int) -> int:
    """Rows per shard for an ``n``-row corpus: ``ceil(n / n_shards)``.

    The padded total ``n_local · n_shards`` is what actually lands on the
    mesh; the pad rows are sentinels no bucket references, so they are
    never scored and never appear in ``n_scored``.
    """
    return -(-int(n) // int(n_shards))


def shard_docs(docs: jnp.ndarray, mesh: Mesh, axes: Sequence[str]):
    """Place a (n, D) corpus row-sharded over ``axes`` of ``mesh``.

    ``n`` not divisible by the shard count is padded with zero sentinel
    rows at the end (ids past the true corpus never enter any bucket, so
    the pad is dead weight on the last shard only).
    """
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    n_pad = shard_rows(docs.shape[0], n_shards) * n_shards - docs.shape[0]
    if n_pad:
        docs = jnp.pad(docs, ((0, n_pad), (0, 0)))
    return jax.device_put(docs, NamedSharding(mesh, P(tuple(axes), None)))


def local_topk(scores: jnp.ndarray, ids: jnp.ndarray, k: int):
    """Top-k of a local score set; ids carried along. (..., m) -> (..., k)."""
    top_s, pos = jax.lax.top_k(scores, k)
    return top_s, jnp.take_along_axis(ids, pos, axis=-1)


def merge_topk(
    s_parts: jnp.ndarray, i_parts: jnp.ndarray, k: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Merge gathered per-shard top-k blocks ``(..., shards, k)`` -> (..., k)."""
    flat_s = s_parts.reshape(*s_parts.shape[:-2], -1)
    flat_i = i_parts.reshape(*i_parts.shape[:-2], -1)
    return local_topk(flat_s, flat_i, k)


def _brute_local(docs_l, qw, exclude, offset, *, k, n_valid):
    """Score a local shard exhaustively and return its top-k (global ids)."""
    n_l = docs_l.shape[0]
    ids = offset + jnp.arange(n_l, dtype=jnp.int32)
    s = jnp.dot(qw, docs_l.T, precision=jax.lax.Precision.HIGHEST)  # (nq, n_l)
    s = jnp.where(ids[None, :] >= n_valid, -jnp.inf, s)  # sentinel pad rows
    s = jnp.where(ids[None, :] == exclude[:, None], -jnp.inf, s)
    return local_topk(s, jnp.broadcast_to(ids, s.shape), k)


def distributed_brute_topk(
    mesh: Mesh,
    docs: jnp.ndarray,       # (n, D) — row-sharded or to-be-sharded
    qw: jnp.ndarray,         # (nq, D) replicated queries
    *,
    k: int,
    shard_axes: Sequence[str] = ("data",),
    exclude: jnp.ndarray | None = None,
    n_valid: int | None = None,
):
    """Exact distributed top-k: local score+top-k, all-gather 2k words, merge.

    ``n_valid`` marks the true corpus length when ``docs`` carries sentinel
    pad rows (see :func:`shard_docs`) — rows at or past it score ``-inf``.
    Returns replicated ``(scores (nq, k), ids (nq, k))``.
    """
    axes = tuple(shard_axes)
    nq = qw.shape[0]
    if exclude is None:
        exclude = jnp.full((nq,), -1, jnp.int32)
    if n_valid is None:
        n_valid = int(docs.shape[0])
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    rows = docs.shape[0] // n_shards

    def kernel(docs_l, qw_r, ex_r):
        idx = jax.lax.axis_index(axes)
        offset = (idx * rows).astype(jnp.int32)
        s, i = _brute_local(docs_l, qw_r, ex_r, offset, k=k, n_valid=n_valid)
        s_all = jax.lax.all_gather(s, axes, axis=0, tiled=False)  # (S, nq, k)
        i_all = jax.lax.all_gather(i, axes, axis=0, tiled=False)
        s_all = jnp.moveaxis(s_all, 0, -2)                         # (nq, S, k)
        i_all = jnp.moveaxis(i_all, 0, -2)
        return merge_topk(s_all, i_all, k)

    fn = jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(P(axes, None), P(None, None), P(None)),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )
    return jax.jit(fn)(docs, qw, exclude)


def make_projection(d: int, proj_dim: int, key=None):
    """Random JL projection ``R (D, pd)`` for two-stage scoring."""
    if key is None:
        key = jax.random.PRNGKey(42)
    return (
        jax.random.normal(key, (d, proj_dim), jnp.float32) * proj_dim ** -0.5
    )


def _navigate(leaders, nav, probes_t):
    """Replicated leader navigation -> flat ``(nq, P)`` probe list.

    Leaders are global and tiny, so this runs ONCE outside any
    ``shard_map`` body — probe sets are identical on every shard and ride
    in as a replicated operand instead of being re-derived per shard.
    """
    k_clusters = leaders.shape[1]
    lsims = jnp.einsum("tkd,qd->qtk", leaders, nav,
                       precision=jax.lax.Precision.HIGHEST)
    parts = []
    for t, p in enumerate(probes_t):
        if p == 0:
            continue
        _, top_c = jax.lax.top_k(lsims[:, t, :], p)
        parts.append(top_c + t * k_clusters)
    return jnp.concatenate(parts, axis=-1).astype(jnp.int32)


def distributed_index_search(
    mesh: Mesh,
    docs: jnp.ndarray,        # (n, D) row-sharded corpus (n divisible by shards)
    leaders: jnp.ndarray,     # (T, K, D) replicated
    buckets_local: jnp.ndarray,  # (S, T, K, B_l) LOCAL ids per shard, sentinel n_l
    qw: jnp.ndarray,          # (nq, D) replicated weighted queries
    *,
    probes_t: tuple[int, ...],
    k: int,
    shard_axes: Sequence[str] = ("data",),
    exclude: jnp.ndarray | None = None,
    docs_proj: jnp.ndarray | None = None,   # (n, pd) projected corpus
    qw_proj: jnp.ndarray | None = None,     # (nq, pd) projected queries
    shortlist: int = 64,
    nav: jnp.ndarray | None = None,         # (nq, D) navigation queries
):
    """Distributed cluster-prune search over a doc-sharded corpus (gather
    path — the pure-JAX oracle for :func:`distributed_bucket_score`).

    ``buckets_local[s]`` packs shard ``s``'s members of every (clustering,
    cluster) pair with sentinel ``n_local``. Navigation is computed ONCE on
    replicated leaders (outside the ``shard_map`` body) and the flat probe
    list is passed in; scoring is local; a single all-gather of the
    per-shard top-k merges the answer. ``nav`` optionally separates the
    LEADER-navigation query from the scoring query (CellDec semantics,
    matching the other backends); defaults to ``qw``.

    **Two-stage scoring (beyond-paper, §Perf)**: when ``docs_proj``/
    ``qw_proj`` are given, candidates are first scored against the
    ``pd``-dim JL projection (8-16x fewer HBM bytes), the per-shard top
    ``shortlist`` survive to exact full-D scoring. Recall impact is bounded
    by the JL distortion and validated in tests/test_distributed_prefilter.
    """
    axes = tuple(shard_axes)
    nq = qw.shape[0]
    if exclude is None:
        exclude = jnp.full((nq,), -1, jnp.int32)
    if nav is None:
        nav = qw
    n_shards, t_cl, k_clusters, b_l = (int(x) for x in buckets_local.shape)
    n_local = docs.shape[0] // n_shards
    two_stage = docs_proj is not None
    flat = _navigate(leaders, nav, probes_t)               # (nq, P) replicated

    def kernel(docs_l, bkt_l, flat_r, qw_r, ex_r, *proj):
        sidx = jax.lax.axis_index(axes)
        offset = (sidx * n_local).astype(jnp.int32)
        bkt = bkt_l[0].reshape(t_cl * k_clusters, b_l)   # (T*K, B_l)
        cand = bkt[flat_r].reshape(nq, -1)               # (nq, m) local ids
        valid = cand < n_local

        if two_stage:
            docs_proj_l, qw_proj_r = proj
            safe = jnp.where(valid, cand, 0)
            cp = docs_proj_l[safe]                        # (nq, m, pd)
            s1 = jnp.einsum(
                "qmp,qp->qm", cp, qw_proj_r,
                preferred_element_type=jnp.float32,
            )
            s1 = jnp.where(valid, s1, -jnp.inf)
            _, keep_pos = jax.lax.top_k(s1, min(shortlist, s1.shape[-1]))
            cand = jnp.take_along_axis(cand, keep_pos, axis=-1)
            valid = jnp.take_along_axis(valid, keep_pos, axis=-1)

        safe = jnp.where(valid, cand, 0)
        cvec = docs_l[safe]                               # (nq, m|L, D)
        s = jnp.einsum(
            "qmd,qd->qm", cvec, qw_r, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        gids = jnp.where(valid, cand + offset, -1)
        s = jnp.where(valid, s, -jnp.inf)
        s = jnp.where(gids == ex_r[:, None], -jnp.inf, s)
        # local dedup across overlapping clusterings
        order = jnp.argsort(cand, axis=-1)
        c_s = jnp.take_along_axis(cand, order, axis=-1)
        s_s = jnp.take_along_axis(s, order, axis=-1)
        g_s = jnp.take_along_axis(gids, order, axis=-1)
        dup = c_s == jnp.pad(c_s[:, :-1], ((0, 0), (1, 0)), constant_values=-1)
        s_s = jnp.where(dup, -jnp.inf, s_s)
        s_loc, i_loc = local_topk(s_s, g_s, k)
        s_all = jnp.moveaxis(jax.lax.all_gather(s_loc, axes, axis=0), 0, -2)
        i_all = jnp.moveaxis(jax.lax.all_gather(i_loc, axes, axis=0), 0, -2)
        return merge_topk(s_all, i_all, k)

    in_specs = [
        P(axes, None),
        P(axes, None, None, None), P(None, None), P(None, None), P(None),
    ]
    args = [docs, buckets_local, flat, qw, exclude]
    if two_stage:
        in_specs += [P(axes, None), P(None, None)]
        args += [docs_proj, qw_proj]
    fn = jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )
    return jax.jit(fn)(*args)


# ------------------------------------------------- fused shard-local scoring
@functools.lru_cache(maxsize=128)
def _bucket_score_fn(mesh, axes, k, k_out, n_local, interpret):
    """Build (once per static config) the jitted shard_map fused scorer.

    Caching the callable is what makes the hot path trace-stable: ``jit``
    keys on function identity, so a fresh closure per search would retrace
    every call. The cache key is tiny (mesh + axes + static ints) and the
    jit cache below it handles shape variation.
    """
    from ..kernels.bucket_score import bucket_score_tiled

    def kernel(data_l, ids_l, sc_l, qw_r, sched_r, member_r, ex_r):
        sidx = jax.lax.axis_index(axes)
        offset = (sidx * n_local).astype(jnp.int32)
        # global -> local exclusion: only the shard owning the excluded id
        # masks it (every other shard maps it to the no-op -1)
        exl = ex_r - offset
        exl = jnp.where((exl >= 0) & (exl < n_local), exl, -1)
        s, i = bucket_score_tiled(
            qw_r, data_l[0], ids_l[0], sched_r, member_r,
            k=k, exclude=exl, scales=sc_l[0], interpret=interpret,
        )
        gi = jnp.where(i >= 0, i + offset, -1)           # local -> global ids
        s_all = jnp.moveaxis(jax.lax.all_gather(s, axes, axis=0), 0, -2)
        i_all = jnp.moveaxis(jax.lax.all_gather(gi, axes, axis=0), 0, -2)
        return merge_topk(s_all, i_all, k_out)

    return jax.jit(jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(
            P(axes, None, None, None), P(axes, None, None), P(axes, None),
            P(None, None), P(None, None), P(None, None, None), P(None),
        ),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    ))


def distributed_bucket_score(
    mesh: Mesh,
    data: jnp.ndarray,       # (S, T·K, B_l, D) shard-local bucket-major pack
    ids: jnp.ndarray,        # (S, T·K, B_l) LOCAL ids, -1 padding
    scales: jnp.ndarray | None,  # (S, T·K) fp32 int8 scales (None -> ones)
    qw: jnp.ndarray,         # (nq, D) replicated scoring queries
    schedule: jnp.ndarray,   # (n_tiles, S_len) replicated probe-dedup schedule
    member: jnp.ndarray,     # (n_tiles, S_len, QT) replicated membership
    *,
    k: int,
    n_local: int,
    shard_axes: Sequence[str] = ("data",),
    exclude: jnp.ndarray | None = None,
    interpret: bool | None = None,
):
    """Fused v2 scoring run shard-locally: the multi-device fused hot path.

    Each shard runs :func:`~repro.kernels.bucket_score.ops
    .bucket_score_tiled` over ITS slice of every probed bucket — the same
    query-tiled ``(QT, D)×(D, B_l)`` MXU matmuls, one HBM block read per
    scheduled bucket per tile, membership/exclude/cross-clustering-dedup
    masking in-kernel — then converts its local top-k to global ids. The
    schedule and membership masks are replicated (probed buckets are
    identical across shards — navigation is global), so the only collective
    is the 2k-word ``all_gather`` + merge. A per-shard candidate union is
    exactly the shard's slice of the global candidate set, so the merged
    top-k equals the single-device fused answer.

    ``scales`` carries the per-``(shard, bucket)`` dequantisation factors
    of an int8 pack (quantised shard-locally — see
    :func:`pack_local_bucket_major`); None means an fp32/bf16 pack.
    Returns replicated ``(scores (nq, k'), ids (nq, k'))`` with
    ``k' = min(k, shards · per-shard columns)`` (k is only ever clipped
    when it exceeds every candidate the schedule can surface, mirroring the
    single-device kernel's ``k_pad`` clip).
    """
    from ..kernels.common import pad_to

    axes = tuple(shard_axes)
    nq = qw.shape[0]
    if exclude is None:
        exclude = jnp.full((nq,), -1, jnp.int32)
    if scales is None:
        scales = jnp.ones(data.shape[:2], jnp.float32)
    n_shards, _, b_l, _ = (int(x) for x in data.shape)
    s_len = int(schedule.shape[1])
    # per-shard output columns after the kernel's k_pad clip
    cols = min(min(pad_to(k, 8), b_l * s_len), k)
    k_out = min(k, n_shards * cols)
    fn = _bucket_score_fn(
        mesh, axes, int(k), int(k_out), int(n_local),
        None if interpret is None else bool(interpret),
    )
    return fn(
        data, ids, scales.astype(jnp.float32), qw,
        schedule.astype(jnp.int32), member.astype(jnp.int32),
        exclude.astype(jnp.int32),
    )


# ------------------------------------------------------ sharded rescore tail
@functools.lru_cache(maxsize=128)
def _exact_rescore_fn(mesh, axes, k, n_local):
    """Jitted shard_map exact-rescore (cached per static config)."""

    def kernel(docs_l, qw_r, ids_r):
        sidx = jax.lax.axis_index(axes)
        offset = (sidx * n_local).astype(jnp.int32)
        loc = ids_r - offset
        owned = (ids_r >= 0) & (loc >= 0) & (loc < n_local)
        safe = jnp.where(owned, loc, 0)
        cvecs = docs_l[safe]                             # (nq, R, D) local
        s = jnp.einsum(
            "qrd,qd->qr", cvecs, qw_r, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        s = jnp.where(owned, s, -jnp.inf)
        # every candidate is owned by exactly one shard: a max all-reduce
        # of the (nq, R) score matrix IS the exact fp32 score everywhere
        s = jax.lax.pmax(s, axes)
        top_s, pos = jax.lax.top_k(s, k)
        top_i = jnp.take_along_axis(ids_r, pos, axis=-1)
        top_i = jnp.where(jnp.isfinite(top_s), top_i, -1)
        extra = jnp.sum(ids_r >= 0, axis=-1).astype(jnp.int32)
        return top_s, top_i, extra

    return jax.jit(jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(P(axes, None), P(None, None), P(None, None)),
        out_specs=(P(None, None), P(None, None), P(None)),
        check_vma=False,
    ))


def distributed_exact_rescore(
    mesh: Mesh,
    docs_sh: jnp.ndarray,    # (S·n_local, D) row-sharded fp32 corpus (padded)
    qw: jnp.ndarray,         # (nq, D) replicated queries
    ids: jnp.ndarray,        # (nq, R) candidate ids (-1 fillers allowed)
    *,
    k: int,
    n_local: int,
    shard_axes: Sequence[str] = ("data",),
):
    """Sharded exact-rescore tail: fp32 re-rank without gathering the corpus.

    The candidates of a pruned depth-``R`` search are re-scored against the
    row-sharded fp32 ``docs`` — each shard gathers and scores only the
    candidates it owns (everything else is ``-inf``), a single ``pmax``
    all-reduce of the ``(nq, R)`` score matrix recovers the exact scores
    everywhere, and the final top-k cut happens replicated. Communication
    is ``nq·R`` words — independent of corpus size and D, so quantised
    sharded packs get the same exactness guarantee as single-device packs
    at collective-light cost. Returns ``(scores (nq, k), ids (nq, k),
    n_rescored (nq,))`` matching
    :func:`repro.core.engine._exact_rescore`'s contract.
    """
    axes = tuple(shard_axes)
    fn = _exact_rescore_fn(mesh, axes, int(k), int(n_local))
    # the candidates may come committed to one device (the engine hands its
    # answers on from the corpus's device): replicate them onto the mesh
    qw, ids = jax.device_put((qw, ids.astype(jnp.int32)),
                             NamedSharding(mesh, P()))
    return fn(docs_sh, qw, ids)


# --------------------------------------------------- shard-local bucket packs
def build_local_buckets(assign_global, n, n_shards, k_clusters):
    """Host-side: split global assignments into per-shard local bucket packs.

    ``assign_global`` is ``(T, n)`` (one row per clustering; entries < 0 —
    tombstoned or pad docs — are skipped). ``n`` must be divisible by
    ``n_shards`` (pad the assignment with ``-1`` columns first — see
    :func:`shard_rows`). Returns ``(S, T, K, B_l)`` padded id tensors with
    LOCAL row ids and sentinel ``n_local``, ready for
    :func:`distributed_index_search` / :func:`pack_local_bucket_major`.
    """
    from .index import pack_buckets

    assign_global = np.atleast_2d(np.asarray(assign_global))
    t_clusterings = assign_global.shape[0]
    if n % n_shards:
        raise ValueError(
            f"build_local_buckets needs n ({n}) divisible by n_shards "
            f"({n_shards}); pad the assignment with -1 columns first"
        )
    n_local = n // n_shards
    packs = [[None] * t_clusterings for _ in range(n_shards)]
    b_max = 8
    for s in range(n_shards):
        for t in range(t_clusterings):
            a = assign_global[t, s * n_local : (s + 1) * n_local]
            ids, _ = pack_buckets(a, k_clusters, n_local)
            packs[s][t] = ids
            b_max = max(b_max, ids.shape[1])
    out = np.full((n_shards, t_clusterings, k_clusters, b_max), n_local, np.int32)
    for s in range(n_shards):
        for t in range(t_clusterings):
            p = packs[s][t]
            out[s, t, :, : p.shape[1]] = p
    return out


@functools.partial(jax.jit, static_argnames=("dtype",))
def _pack_shard(docs_l, ids_l, *, dtype):
    """One shard's slice of the pack: ``(n_local, D)`` rows + ``(T·K, B_l)``
    local ids -> ``(1, T·K, B_l, D)`` data and ``(1, T·K)`` int8 scales |
    None, with the shard axis already in place so the slice can join a
    global array without a copy."""
    from ..kernels.bucket_score.ops import quantize_bucket_major

    data = docs_l[jnp.where(ids_l >= 0, ids_l, 0)][None]
    if dtype == "int8":
        return quantize_bucket_major(data)
    if dtype is not None:
        data = data.astype(jnp.dtype(dtype))
    return data, None


def pack_local_bucket_major(
    docs: jnp.ndarray,       # (n, D) fp32 corpus
    assign: np.ndarray,      # (T, n) global assignments (-1 = removed)
    k_clusters: int,
    n_shards: int,
    *,
    dtype=None,
    sharding: NamedSharding | None = None,
):
    """Shard-local bucket-major pack: the fused v2 layout, one slice per shard.

    Reuses :func:`build_local_buckets`' each-device-owns-its-slice-of-every-
    cluster layout, then materialises each shard's slice bucket-major:

    - ``data (S, T·K, B_l, D)`` — shard ``s``'s members of every bucket as
      contiguous blocks, stored in ``dtype`` precision (``bfloat16`` halves
      the per-shard HBM bytes, ``int8`` quarters them via symmetric
      per-``(shard, bucket)`` quantisation — each shard's absmax over ITS
      slice of the bucket, so quantisation error never crosses shards);
    - ``ids (S, T·K, B_l)`` — LOCAL row ids, ``-1`` padding (the kernels'
      mask convention);
    - ``scales (S, T·K)`` fp32 — int8 dequantisation factors (None
      otherwise);
    - ``n_local`` — rows per shard (``ceil(n / n_shards)``; the corpus pads
      with sentinel rows that never enter a bucket, so ANY ``n`` shards
      cleanly).

    ``B_l`` is the max local bucket size over all shards (sublane-padded),
    typically ``~B / n_shards`` — a smaller per-shard block, which buys the
    fused kernel a LARGER query tile out of the same VMEM budget.

    ``sharding`` (a ``NamedSharding`` of the 4-D data over the shard axes)
    builds each shard's slice ON the device that holds it and returns
    arrays already placed on the mesh, so no device ever holds more than
    its own share of the pack. Without it the slices are stacked on the
    default device.
    """
    from .index import validate_pack_dtype

    dtype = validate_pack_dtype(dtype)
    assign = np.atleast_2d(np.asarray(assign))
    t_cl, n = assign.shape
    n_local = shard_rows(n, n_shards)
    n_pad = n_local * n_shards
    a_pad = np.pad(assign, ((0, 0), (0, n_pad - n)), constant_values=-1)
    bl = build_local_buckets(a_pad, n_pad, n_shards, k_clusters)
    b_l = bl.shape[-1]
    bk = bl.reshape(n_shards, t_cl * k_clusters, b_l)
    ids = np.where(bk < n_local, bk, -1).astype(np.int32)
    targets = [(s, None) for s in range(n_shards)]
    if sharding is not None:   # (shard, device) for every device, replicas too
        shape = (n_shards, t_cl * k_clusters, b_l, int(docs.shape[1]))
        targets = [
            (idx[0].start or 0, dev)
            for dev, idx in sharding.devices_indices_map(shape).items()
        ]
    parts = []
    for s, dev in targets:
        rows = docs[s * n_local:(s + 1) * n_local]
        if rows.shape[0] < n_local:               # sentinel pad rows
            rows = jnp.pad(rows, ((0, n_local - rows.shape[0]), (0, 0)))
        ids_s = ids[s]
        if dev is not None:
            rows, ids_s = jax.device_put((rows, ids_s), dev)
        parts.append(_pack_shard(rows, jnp.asarray(ids_s), dtype=dtype))
    scales = None
    if sharding is None:
        data = jnp.concatenate([d for d, _ in parts])
        if dtype == "int8":
            scales = jnp.concatenate([sc for _, sc in parts])
        return data, jnp.asarray(ids), scales, n_local
    mesh, spec = sharding.mesh, sharding.spec
    data = jax.make_array_from_single_device_arrays(
        (n_shards, *parts[0][0].shape[1:]), sharding, [d for d, _ in parts]
    )
    if dtype == "int8":
        scales = jax.make_array_from_single_device_arrays(
            (n_shards, t_cl * k_clusters),
            NamedSharding(mesh, P(spec[0], None)), [sc for _, sc in parts],
        )
    ids = jax.device_put(ids, NamedSharding(mesh, P(spec[0], None, None)))
    return data, ids, scales, n_local
