"""Cluster-prune inner loop: probed-bucket gather → score → top-k merge.

TPU adaptation of "visit cluster = walk its posting list" (DESIGN.md §4): the
corpus is stored **bucket-major** as a padded ``(K, B, D)`` tensor, so a probe
is a *contiguous block read* selected by a scalar-prefetched probe list — no
row gather.

Two generations of the kernel live here:

``bucket_score_kernel`` (v1)
    Grid ``(nq, P)`` with a ``(1, D)`` query block — every step is a
    ``(1, D)×(D, B)`` matvec, and a batch re-reads a shared bucket once per
    query that probes it. Kept as the single-query baseline and for the
    kernels benchmark; it is off the serving path and does not lower on TPU.

``bucket_score_tiled_kernel`` (v2)
    Grid ``(nq/QT, S)`` with a ``(QT, D)`` query block: each step scores one
    DMA'd bucket against a whole *query tile* as a ``(QT, D)×(D, B)`` MXU
    matmul with fp32 accumulation (``preferred_element_type`` — the bucket
    tensor may be stored bf16 or int8; an int8 pack additionally carries
    per-bucket dequantisation scales applied to the score block, see below).
    ``S`` indexes a per-tile **deduplicated probe schedule** built
    engine-side (see
    :func:`repro.kernels.bucket_score.ops.build_probe_schedule_device`):
    the union of the tile's flat probe lists, each shared bucket appearing
    ONCE, so a bucket probed by many queries of the tile is read from HBM
    once per tile instead of once per query. A scalar-prefetched schedule
    selects the block; a per-step ``(QT, 1)`` membership mask keeps each
    query's candidate set exactly its own probed buckets.

Quantised packs: an int8 bucket block stores symmetric per-bucket
quantised values ``q = round(v / scale)`` with ``scale = absmax / 127``.
Every int8 value is exactly representable in bf16, so the kernel casts
both operands to bf16, lets the MXU accumulate fp32, then multiplies the
``(QT, B)`` score block by the bucket's scalar scale — algebraically
``scale · Σ qᵀv``, i.e. the fp32 dot of the *dequantised* vectors with no
extra rounding beyond the quantisation itself. Navigation never sees the
quantised data (fp32 leaders), so probe sets and ``n_scored`` are
bit-identical across pack dtypes. An fp32 pack is scored at
``Precision.HIGHEST``, the precision of every fp32 scoring path of the
engine, so the kernel, the reference engine and brute force rank alike.

Both kernels keep running top-k accumulators in VMEM (``(1, k_pad)`` /
``(QT, k_pad)``) and suppress duplicate ids across the T overlapping
clusterings by masking candidates already present in the accumulator. That
dedup is sound because the merge breaks ties toward the lower position of
``concat([accumulator, block])`` (``lax.top_k`` in v1, :func:`_merge_topk`
in v2): a candidate whose score was masked to ``-inf`` can never displace
an ``(-inf, -1)`` accumulator slot, so the accumulator never holds a real
id at ``-inf`` — and therefore never masks a live candidate it did not
beat.

VMEM per v2 step is sized by
:func:`repro.kernels.bucket_score.ops.tile_vmem_bytes`, which
:func:`repro.kernels.bucket_score.ops.pick_query_tile` solves for QT.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["bucket_score_kernel", "bucket_score_tiled_kernel"]


def bucket_score_kernel(
    probes_ref,   # (nq, P) int32 — scalar-prefetched probe lists
    q_ref,        # (1, D)  VMEM — this query
    bd_ref,       # (1, B, D) VMEM — the probed bucket's member vectors
    bi_ref,       # (1, B) int32 VMEM — the probed bucket's global doc ids (-1 pad)
    ex_ref,       # (1, 1) int32 — excluded doc id
    s_out,        # (1, K) VMEM accumulator
    i_out,        # (1, K) VMEM accumulator
):
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        s_out[...] = jnp.full_like(s_out, -jnp.inf)
        i_out[...] = jnp.full_like(i_out, -1)

    data = bd_ref[0]                                   # (B, D)
    ids = bi_ref[...]                                  # (1, B)
    s = jnp.dot(
        q_ref[...], data.T, preferred_element_type=jnp.float32
    )                                                  # (1, B)
    s = jnp.where(ids >= 0, s, -jnp.inf)               # bucket padding
    s = jnp.where(ids == ex_ref[...], -jnp.inf, s)     # query-self exclusion
    # Overlap dedup (multi-clustering): drop ids already in the running top-k.
    dup = jnp.any(ids[0][None, :, None] == i_out[...][0][None, None, :], axis=-1)
    s = jnp.where(dup, -jnp.inf, s)

    k = s_out.shape[-1]
    cat_s = jnp.concatenate([s_out[...], s], axis=-1)
    cat_i = jnp.concatenate([i_out[...], ids], axis=-1)
    top_s, pos = jax.lax.top_k(cat_s, k)
    s_out[...] = top_s
    i_out[...] = jnp.take_along_axis(cat_i, pos, axis=-1)


def bucket_score_tiled_kernel(
    sched_ref,    # (n_tiles, S) int32 SMEM — scalar-prefetched schedules
    sc_ref,       # (n_buckets,) fp32 SMEM — per-bucket dequantisation scales
    q_ref,        # (QT, D) VMEM — this tile's queries (fp32)
    bd_ref,       # (1, B, D) VMEM — the scheduled bucket (fp32/bf16/int8)
    bi_ref,       # (1, 1, B) int32 VMEM — its global doc ids (-1 pad)
    mb_ref,       # (1, QT, 1) int32 VMEM — which tile queries probe it
    ex_ref,       # (QT, 1) int32 — per-query excluded doc id
    s_out,        # (QT, k_pad) VMEM accumulator
    i_out,        # (QT, k_pad) VMEM accumulator
):
    t_idx = pl.program_id(0)
    s_idx = pl.program_id(1)

    @pl.when(s_idx == 0)
    def _init():
        s_out[...] = jnp.full_like(s_out, -jnp.inf)
        i_out[...] = jnp.full_like(i_out, -1)

    data = bd_ref[0]                                   # (B, D)
    ids = bi_ref[0]                                    # (1, B)
    q = q_ref[...]                                     # (QT, D)
    precision = None
    if data.dtype == jnp.int8:
        # int8 pack: values in [-127, 127] are exact in bf16 — cast both
        # operands, accumulate fp32, then dequantise the score block with
        # the bucket's scalar scale (scale · Σ qᵀv, no extra rounding).
        q = q.astype(jnp.bfloat16)
        data = data.astype(jnp.bfloat16)
    elif data.dtype != q.dtype:
        # Half-precision pack: feed the MXU the storage dtype on both sides
        # and accumulate fp32 (preferred_element_type).
        q = q.astype(data.dtype)
    else:
        precision = jax.lax.Precision.HIGHEST
    s = jax.lax.dot_general(                           # (QT, B) = q · dataᵀ
        q, data, (((1,), (1,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32,
    )
    if bd_ref.dtype == jnp.int8:
        s = s * sc_ref[sched_ref[t_idx, s_idx]]
    member = mb_ref[0] != 0                            # (QT, 1)
    s = jnp.where(member, s, -jnp.inf)                 # not this query's probe
    s = jnp.where(ids >= 0, s, -jnp.inf)               # bucket padding
    s = jnp.where(ids == ex_ref[...], -jnp.inf, s)     # per-query exclusion
    s_out[...], i_out[...] = _merge_topk(s_out[...], i_out[...], s, ids)


def _merge_topk(acc_s, acc_i, s, ids):
    """Top-``k_pad`` of the accumulator ``(QT, k_pad)`` and a score block
    ``(QT, B)`` with ids ``(1, B)`` (Mosaic lowers no ``lax.top_k``).

    First the overlap dedup (multi-clustering): a block candidate whose id
    the query's accumulator already holds is dropped. Then ``k_pad`` rounds
    of max extraction, in the same order as ``lax.top_k`` over
    ``concat([acc, s])``: descending, and a tie goes to the lower position —
    the accumulator before the block, the lower lane within each. Slots no
    finite score reaches come back as ``(-inf, -1)``, so the accumulator
    never holds a real id at ``-inf``. Both loops are ``fori_loop``s, so the
    kernel's VMEM does not grow with ``k_pad``.
    """
    k_pad = acc_s.shape[-1]
    width = s.shape[-1]
    lane_a = jax.lax.broadcasted_iota(jnp.int32, acc_s.shape, 1)
    lane_b = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)

    def column(x, j, fill):                            # x[:, j:j+1]
        return jnp.max(jnp.where(lane_a == j, x, fill), -1, keepdims=True)

    def dedup(j, s):
        return jnp.where(ids == column(acc_i, j, -1), -jnp.inf, s)

    def extract(j, carry):
        acc_s, s, out_s, out_i = carry
        ma = jnp.max(acc_s, axis=-1, keepdims=True)    # (QT, 1)
        mb = jnp.max(s, axis=-1, keepdims=True)
        from_acc = ma >= mb
        pa = jnp.min(jnp.where(acc_s == ma, lane_a, k_pad), -1, keepdims=True)
        pb = jnp.min(jnp.where(s == mb, lane_b, width), -1, keepdims=True)
        ia = jnp.max(jnp.where(lane_a == pa, acc_i, -1), -1, keepdims=True)
        ib = jnp.max(jnp.where(lane_b == pb, ids, -1), -1, keepdims=True)
        best = jnp.maximum(ma, mb)
        best_i = jnp.where(best > -jnp.inf, jnp.where(from_acc, ia, ib), -1)
        out_s = jnp.where(lane_a == j, best, out_s)
        out_i = jnp.where(lane_a == j, best_i, out_i)
        acc_s = jnp.where(from_acc & (lane_a == pa), -jnp.inf, acc_s)
        s = jnp.where(~from_acc & (lane_b == pb), -jnp.inf, s)
        return acc_s, s, out_s, out_i

    s = jax.lax.fori_loop(0, k_pad, dedup, s)
    out_s = jnp.full(acc_s.shape, -jnp.inf, jnp.float32)
    out_i = jnp.full(acc_i.shape, -1, jnp.int32)
    _, _, out_s, out_i = jax.lax.fori_loop(
        0, k_pad, extract, (acc_s, s, out_s, out_i)
    )
    return out_s, out_i
