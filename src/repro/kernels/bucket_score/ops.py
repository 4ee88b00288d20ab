"""Jit'd public wrappers for the bucket gather-score-merge kernels.

``bucket_score``
    v1 per-query path: grid ``(nq, P)``, one ``(1, D)×(D, B)`` matvec per
    step. Kept as the baseline and for single-query microbenchmarks
    (fp32/bf16 packs only).
``bucket_score_tiled``
    v2 query-tiled path: grid ``(nq/QT, S)`` over a per-tile deduplicated
    probe *schedule*, one ``(QT, D)×(D, B)`` MXU matmul per step, fp32
    accumulation over fp32 / bf16 / int8 bucket storage (int8 packs carry a
    per-bucket dequantisation ``scales`` operand — see
    :func:`quantize_bucket_major`). This is what
    :class:`repro.core.engine.FusedEngine` serves.

Schedules come in two flavours with identical semantics:

``build_probe_schedule``
    Host numpy — kept for benchmarks/tests that want the tight data-derived
    ``S`` (max per-tile unique count), and as the oracle for the device path.
``build_probe_schedule_device``
    Jittable segmented dedup (sort → first-occurrence scan → scatter) over a
    *bucketed static* schedule length ``S`` (:func:`schedule_length`, powers
    of two) — the serving path, so large-batch search never round-trips the
    probe tensor HBM→host→HBM. Padded slots all point at bucket 0 with zero
    membership and follow every live slot of their tile.

``bucket_score_tiled`` counts each tile's live slots from ``member`` and
skips the grid steps past them: no DMA, no matmul, no merge. A padded slot
is one no query of the tile probes, so skipping it changes no answer.

``pick_query_tile`` sizes QT from the per-step VMEM bytes the TPU compiler
counts (:func:`tile_vmem_bytes`); ``pack_bucket_major`` materialises the
bucket-major tensor (optionally quantised / reduced precision).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import pad_to, use_interpret
from .kernel import bucket_score_kernel, bucket_score_tiled_kernel

__all__ = [
    "bucket_score",
    "bucket_score_tiled",
    "build_probe_schedule",
    "build_probe_schedule_device",
    "schedule_length",
    "pick_query_tile",
    "tile_vmem_bytes",
    "schedule_block_reads",
    "pack_bucket_major",
    "quantize_bucket_major",
    "dequantize_bucket_major",
]


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def bucket_score(
    queries: jnp.ndarray,        # (nq, D)
    bucket_data: jnp.ndarray,    # (K, B, D) bucket-major corpus
    bucket_ids: jnp.ndarray,     # (K, B) int32, -1 padding
    probes: jnp.ndarray,         # (nq, P) int32 cluster ids
    *,
    k: int,
    exclude: jnp.ndarray | None = None,
    interpret: bool | None = None,
):
    """Cluster-prune inner loop (v1): ``(nq, k)`` scores + ids, one query
    per grid row.

    The probe list rides in as a scalar-prefetch operand, so the bucket block
    for step ``(q, p)`` is DMA'd ahead of the matmul of step ``(q, p-1)`` —
    gather latency hides behind MXU work.
    """
    if interpret is None:
        interpret = use_interpret()
    nq, d = queries.shape
    n_clusters, b, _ = bucket_data.shape
    p = probes.shape[1]
    if exclude is None:
        exclude = jnp.full((nq,), -1, jnp.int32)
    k_pad = min(pad_to(k, 8), b * p)

    grid = (nq, p)
    s, i = pl.pallas_call(
        bucket_score_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, d), lambda q, pp, pr: (q, 0)),
                pl.BlockSpec((1, b, d), lambda q, pp, pr: (pr[q, pp], 0, 0)),
                pl.BlockSpec((1, b), lambda q, pp, pr: (pr[q, pp], 0)),
                pl.BlockSpec((1, 1), lambda q, pp, pr: (q, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, k_pad), lambda q, pp, pr: (q, 0)),
                pl.BlockSpec((1, k_pad), lambda q, pp, pr: (q, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((nq, k_pad), jnp.float32),
            jax.ShapeDtypeStruct((nq, k_pad), jnp.int32),
        ],
        interpret=interpret,
    )(
        probes.astype(jnp.int32),
        queries,
        bucket_data,
        bucket_ids.astype(jnp.int32),
        exclude.astype(jnp.int32)[:, None],
    )
    return s[:, :k], i[:, :k]


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def bucket_score_tiled(
    queries: jnp.ndarray,        # (nq, D) fp32
    bucket_data: jnp.ndarray,    # (..., B, D) bucket-major (fp32/bf16/int8)
    bucket_ids: jnp.ndarray,     # (K, B) int32, -1 padding
    schedule: jnp.ndarray,       # (n_tiles, S) int32 dedup'd bucket schedule
    member: jnp.ndarray,         # (n_tiles, S, QT) int32 membership mask
    *,
    k: int,
    exclude: jnp.ndarray | None = None,
    scales: jnp.ndarray | None = None,   # (K,) fp32 — required for int8 pack
    interpret: bool | None = None,
):
    """Cluster-prune inner loop (v2): query-tiled ``(nq, k)`` scores + ids.

    ``schedule`` and ``member`` come from :func:`build_probe_schedule` or
    :func:`build_probe_schedule_device`: row ``t`` of the schedule is the
    deduplicated union of the flat probe lists of queries ``[t·QT,
    (t+1)·QT)``, and ``member[t, s, q]`` says whether tile query ``q``
    actually probes ``schedule[t, s]``. Each grid step DMAs ONE bucket block
    and scores it against the whole tile as a ``(QT, D)×(D, B)`` MXU matmul
    — a bucket shared by many queries of the tile is read from HBM once per
    tile instead of once per query.

    ``scales`` carries the per-bucket dequantisation factors of an int8
    pack (:func:`quantize_bucket_major`); the kernel feeds the MXU the
    int8 values via an exact int8→bf16 cast, accumulates fp32, and applies
    the scale to the ``(QT, B)`` score block — required iff ``bucket_data``
    is int8, ignored otherwise.

    Queries, exclude, and outputs are ragged-tail padded to ``n_tiles·QT``
    internally; the pad rows have an all-zero membership mask, so they score
    nothing and come back as ``(-inf, -1)`` before being sliced off.

    The kernel runs the body of a grid step only up to the tile's last slot
    that some query probes (``n_live``, counted here from ``member``), and
    the steps past it repeat that slot's block indices, so the pipeline
    fetches nothing for them. Both schedule builders put every live slot
    before every padded one, so the steps skipped are exactly the padding.

    ``bucket_data`` may keep leading axes (the index's ``(T, K, B, D)``
    pack): they are flattened to ``K`` inside this jit, where the reshape is
    free, rather than by the caller, where it would copy the whole pack.
    """
    if interpret is None:
        interpret = use_interpret()
    nq, d = queries.shape
    b = bucket_data.shape[-2]
    bucket_data = bucket_data.reshape(-1, b, d)
    n_clusters = bucket_data.shape[0]
    n_tiles, s_len = schedule.shape
    qt = member.shape[-1]
    if n_tiles * qt < nq:
        raise ValueError(
            f"schedule covers {n_tiles}x{qt} query rows, batch has {nq}"
        )
    if bucket_data.dtype == jnp.int8 and scales is None:
        raise ValueError(
            "int8 bucket_data requires the per-bucket scales= operand "
            "(see quantize_bucket_major)"
        )
    if scales is None:
        scales = jnp.ones((n_clusters,), jnp.float32)
    if exclude is None:
        exclude = jnp.full((nq,), -1, jnp.int32)
    member = member.astype(jnp.int32)
    # one past each tile's last live slot (its live count: live slots come
    # first); a slot past it has no member query and its step is skipped
    n_live = jnp.max(
        jnp.where(member.any(axis=-1),
                  jnp.arange(1, s_len + 1, dtype=jnp.int32), 0),
        axis=-1,
    )

    def live(t, ss, nl):         # a dead step repeats the last live block
        return jnp.minimum(ss, jnp.maximum(nl[t] - 1, 0))

    pad = n_tiles * qt - nq
    qp = jnp.pad(queries, ((0, pad), (0, 0)))
    ep = jnp.pad(exclude.astype(jnp.int32), (0, pad), constant_values=-1)
    k_pad = min(pad_to(k, 8), b * s_len)

    grid = (n_tiles, s_len)
    s, i = pl.pallas_call(
        bucket_score_tiled_kernel,
        name="bucket_score_tiled",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((qt, d), lambda t, ss, sc, _, nl: (t, 0)),
                pl.BlockSpec(
                    (1, b, d),
                    lambda t, ss, sc, _, nl: (sc[t, live(t, ss, nl)], 0, 0),
                ),
                pl.BlockSpec(
                    (1, 1, b),
                    lambda t, ss, sc, _, nl: (sc[t, live(t, ss, nl)], 0, 0),
                ),
                pl.BlockSpec(
                    (1, qt, 1),
                    lambda t, ss, sc, _, nl: (
                        t * s_len + live(t, ss, nl), 0, 0),
                ),
                pl.BlockSpec((qt, 1), lambda t, ss, sc, _, nl: (t, 0)),
            ],
            out_specs=[
                pl.BlockSpec((qt, k_pad), lambda t, ss, sc, _, nl: (t, 0)),
                pl.BlockSpec((qt, k_pad), lambda t, ss, sc, _, nl: (t, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n_tiles * qt, k_pad), jnp.float32),
            jax.ShapeDtypeStruct((n_tiles * qt, k_pad), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=tile_vmem_bytes(
                qt, d, b, k_pad, bucket_data.dtype.itemsize
            ) + TILE_VMEM_HEADROOM,
        ),
        interpret=interpret,
    )(
        schedule.astype(jnp.int32),
        scales.astype(jnp.float32),
        n_live,
        qp,
        bucket_data,
        bucket_ids.astype(jnp.int32)[:, None, :],
        member.reshape(n_tiles * s_len, qt, 1),
        ep[:, None],
    )
    return s[:nq, :k], i[:nq, :k]


# VMEM one grid step of the tiled kernel may claim, as counted by
# :func:`tile_vmem_bytes`: a quarter of a v5e core's 128 MiB of VMEM.
TILE_VMEM_BUDGET = 32 * 2**20

# What the tiled kernel's scoped-VMEM limit adds to :func:`tile_vmem_bytes`.
TILE_VMEM_HEADROOM = 2 * 2**20


def tile_vmem_bytes(
    qt: int, d: int, b: int, k_pad: int, pack_itemsize: int = 4
) -> int:
    """VMEM bytes one step of the tiled kernel needs, counted the way the
    TPU compiler counts them against the scoped-VMEM limit:

    - the ``(B, D)`` bucket block in the pack dtype, double-buffered by the
      pipeline;
    - the ``(QT, D)`` fp32 query tile;
    - the ``(QT, B)`` score block, the ``(QT, k_pad)`` accumulators and the
      merge's temporaries, four fp32 words per element;
    - for an fp32 pack, the ``Precision.HIGHEST`` matmul's temporaries:
      the query tile split into bf16 parts, and a fixed MiB.

    Fitted to the smallest limits an ahead-of-time compile for v5e at
    D=4096 accepts, over B in {312, 568}, QT in {8, 128} and every pack
    dtype; ``tests/test_tpu_compile.py`` compiles at
    :func:`pick_query_tile`'s choice."""
    n = 2 * b * d * pack_itemsize + 4 * qt * d + 16 * qt * (b + k_pad)
    if pack_itemsize == 4:
        n += 13 * qt * d + 2**20
    return n


def pick_query_tile(
    d: int,
    b: int,
    *,
    k_pad: int = 64,
    budget_bytes: int = TILE_VMEM_BUDGET,
    max_tile: int = 128,
    pack_itemsize: int = 4,
) -> int:
    """Size the query tile QT from the v2 kernel's VMEM working set.

    Solves :func:`tile_vmem_bytes` ``<= budget_bytes`` for QT, then clamps
    to ``[8, max_tile]`` and rounds down to a sublane multiple of 8. A
    reduced-precision pack shrinks the bucket block and so buys a LARGER
    tile at the same budget. A bucket block larger than the whole budget
    still yields the minimum tile; the kernel's scoped-VMEM limit follows
    the tile either way.
    """
    fixed = tile_vmem_bytes(0, d, b, k_pad, pack_itemsize)
    per_query = tile_vmem_bytes(1, d, b, k_pad, pack_itemsize) - fixed
    free = budget_bytes - fixed
    qt = free // per_query if free > 0 else 0
    qt = max(8, min(max_tile, (qt // 8) * 8))
    return int(qt)


def schedule_length(query_tile: int, n_probes: int, n_buckets: int) -> int:
    """Bucketed static schedule length for the device-side scheduler.

    A tile of ``QT`` queries with ``P`` probes each can reference at most
    ``min(QT·P, n_buckets)`` distinct buckets (there are only ``T·K``
    buckets in total — a large batch of overlapping probe lists saturates
    that long before the dedup-free ``QT·P`` worst case). Rounding up to a
    power of two buckets the static ``S`` so kernel/schedule traces are
    shared across every batch whose tight bound lands in the same bucket,
    instead of re-tracing per data-dependent unique count.
    """
    tight = max(1, min(int(query_tile) * int(n_probes), int(n_buckets)))
    return 1 << (tight - 1).bit_length()


def build_probe_schedule(
    probes: np.ndarray, query_tile: int, *, pad_multiple: int = 8
) -> tuple[np.ndarray, np.ndarray]:
    """Probe-dedup scheduler (host numpy): flat probe lists -> tile schedule.

    ``probes`` is the ``(nq, P)`` flat (``t·K + cluster``) probe tensor the
    engine navigates to (entries < 0 are ignored — used for ragged-tail
    query padding). Queries are tiled in groups of ``query_tile``; each
    tile's schedule row is the **deduplicated union** of its members' probe
    lists, so a bucket probed by several queries of the tile appears once —
    the HBM block read amortises across the tile. Under skewed probe
    distributions (popular clusters), ``S`` collapses well below
    ``QT·P``.

    Returns ``(schedule (n_tiles, S) int32, member (n_tiles, S, QT) int32)``
    with ``S`` the max per-tile unique count rounded up to ``pad_multiple``
    (bounds kernel re-tracing across batches). Padded schedule slots point
    at bucket 0 with an all-zero membership mask and come after every live
    slot of their tile — the prefix :func:`bucket_score_tiled` relies on to
    skip them; padded query rows (``n_tiles·QT > nq``) have zero membership
    everywhere.

    This is the data-derived-``S`` variant (and the oracle the device path
    is tested against); serving goes through
    :func:`build_probe_schedule_device`, which never leaves the device.
    """
    probes = np.asarray(probes)
    nq, _ = probes.shape
    qt = int(query_tile)
    n_tiles = max(1, -(-nq // qt))
    pad = n_tiles * qt - nq
    pp = np.pad(probes, ((0, pad), (0, 0)), constant_values=-1)
    tiles = pp.reshape(n_tiles, qt, -1)
    uniq = [np.unique(t[t >= 0]) for t in tiles]
    s_len = pad_to(max(1, max(u.size for u in uniq)), pad_multiple)
    sched = np.zeros((n_tiles, s_len), np.int32)
    member = np.zeros((n_tiles, s_len, qt), np.int32)
    for ti, u in enumerate(uniq):
        sched[ti, : u.size] = u
        member[ti, : u.size] = np.any(
            tiles[ti][None, :, :] == u[:, None, None], axis=-1
        )
    return sched, member


@functools.partial(jax.jit, static_argnames=("query_tile", "s_len"))
def build_probe_schedule_device(
    probes: jnp.ndarray, *, query_tile: int, s_len: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Jittable probe-dedup scheduler — the sync-free serving path.

    Same contract as :func:`build_probe_schedule` (deduplicated, ascending
    per-tile schedule + membership masks; entries < 0 ignored) but built
    entirely on device as a segmented dedup, so ``FusedEngine.search`` never
    synchronises the probe tensor to the host:

    1. sort each tile's ``QT·P`` flat probe list (invalid ``-1`` entries
       sink to the front),
    2. mark first occurrences (``v[i] != v[i-1]``) and prefix-sum them into
       compacted schedule slots,
    3. scatter values to ``schedule`` (first occurrences) and ones to
       ``member`` (every occurrence, at its value's slot).

    ``s_len`` is STATIC — callers size it with :func:`schedule_length`
    (power-of-two bucket of ``min(QT·P, n_buckets)``, an upper bound on any
    tile's unique count, so the scatter can never overflow). Unused slots
    keep bucket 0 with zero membership, exactly like the host builder, and
    the cumsum compaction puts every live slot before them: the kernel
    skips the steps past a tile's live slots, fetching and computing
    nothing for the padding of the static ``S``.
    """
    nq, p = probes.shape
    qt = int(query_tile)
    n_tiles = max(1, -(-nq // qt))
    pad = n_tiles * qt - nq
    pp = jnp.pad(
        probes.astype(jnp.int32), ((0, pad), (0, 0)), constant_values=-1
    )
    flat = pp.reshape(n_tiles, qt * p)
    qidx = jnp.broadcast_to(
        jnp.repeat(jnp.arange(qt, dtype=jnp.int32), p), (n_tiles, qt * p)
    )

    def one_tile(f, qi):
        order = jnp.argsort(f)
        v = f[order]                                     # ascending, -1s first
        q = qi[order]
        valid = v >= 0
        prev = jnp.concatenate([jnp.full((1,), -2, v.dtype), v[:-1]])
        first = valid & (v != prev)
        pos = jnp.cumsum(first.astype(jnp.int32)) - 1    # slot of v's unique
        pos = jnp.where(valid, pos, s_len)               # invalid -> dump row
        sched = (
            jnp.zeros((s_len + 1,), jnp.int32)
            .at[jnp.where(first, pos, s_len)].set(v)[:s_len]
        )
        member = (
            jnp.zeros((s_len + 1, qt), jnp.int32).at[pos, q].set(1)[:s_len]
        )
        return sched, member

    return jax.vmap(one_tile)(flat, qidx)


def schedule_block_reads(member: jnp.ndarray) -> int:
    """Live HBM block reads a probe-dedup schedule performs.

    ``member`` is the ``(n_tiles, S_len, QT)`` membership tensor of
    :func:`build_probe_schedule_device`; a slot with no member query is
    schedule padding whose repeat DMA the pipeline skips, so the number of
    slots with ANY member is exactly the bucket blocks the kernel reads
    from HBM. Benchmarks multiply by the per-shard block size
    ``B · D · itemsize`` (and by the shard count for the sharded path —
    every shard reads ITS slice of each scheduled bucket) to report
    packed bytes per query.
    """
    return int(jnp.asarray(member).any(axis=-1).sum())


def quantize_bucket_major(data: jnp.ndarray):
    """Symmetric per-bucket int8 quantisation of a bucket-major tensor.

    ``data`` is ``(..., B, D)`` fp32 (one bucket per leading index); each
    bucket gets ONE scale ``max|v| / 127`` over its ``(B, D)`` block, so
    dequantisation is a scalar multiply per scheduled bucket and the
    elementwise error is bounded by ``scale / 2`` (round-to-nearest).
    All-empty buckets (absmax 0) take scale 1 so dequantisation stays
    finite. Returns ``(int8 values, fp32 scales (...,))``.
    """
    absmax = jnp.max(jnp.abs(data), axis=(-2, -1))
    scales = jnp.where(absmax > 0, absmax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(
        jnp.round(data / scales[..., None, None]), -127, 127
    ).astype(jnp.int8)
    return q, scales


def dequantize_bucket_major(
    values: jnp.ndarray, scales: jnp.ndarray
) -> jnp.ndarray:
    """Inverse of :func:`quantize_bucket_major` (to fp32)."""
    return values.astype(jnp.float32) * scales[..., None, None]


def pack_bucket_major(docs, buckets, *, dtype=None):
    """Host helper: (n, D) corpus + (K, B) id pack -> (K, B, D) bucket-major.

    Padded slots point at row 0 but carry id -1, so kernels mask them.
    ``dtype`` selects the storage precision of the packed tensor:

    - ``None`` keeps the corpus dtype (fp32);
    - ``jnp.bfloat16`` halves the HBM bytes (plain cast);
    - ``jnp.int8`` quarters them via :func:`quantize_bucket_major` — the
      third return value then carries the per-bucket fp32 scales the
      scoring kernel needs.

    The kernels accumulate fp32 regardless (``preferred_element_type``), and
    navigation keeps the fp32 leaders. Returns ``(data, ids, scales)`` with
    ``scales=None`` for non-int8 packs.
    """
    safe = jnp.where(buckets >= 0, buckets, 0)
    data = docs[safe]                                  # (K, B, D)
    ids = jnp.where(buckets >= 0, buckets, -1)
    scales = None
    if dtype is not None and jnp.dtype(dtype) == jnp.dtype(jnp.int8):
        data, scales = quantize_bucket_major(data)
    elif dtype is not None:
        data = data.astype(dtype)
    return data, ids, scales
