"""Fused FPF round — the preprocessing hot loop of the paper's clusterer.

One Gonzalez round = (1) distances of all points to the newest center,
(2) running-min update of the point→center-set distance, (3) argmax of the
updated distances (the next center). In naive form that is three passes over
``(m, D)``; here it is ONE VMEM-resident pass per tile: matvec on the MXU,
elementwise max-with-carry, and a tile-local argmin folded into an SMEM
running reduction. HBM traffic per round drops from ``3·m·D`` reads +
``2·m`` writes to exactly ``m·D + m`` reads + ``m`` writes — the kernel-level
version of the paper's 30× preprocessing win (DESIGN.md §4).

Grid: ``(m/TM,)``. The scalar (value, index) running argmin lives in SMEM
scratch and is written to the 1-element outputs at the last step.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["fpf_iter_kernel"]


def fpf_iter_kernel(
    x_ref,        # (TM, D) VMEM — point tile
    c_ref,        # (1, D)  VMEM — the newest center
    ms_ref,       # (TM, 1) VMEM — running max-similarity (min-distance dual)
    out_ms,       # (TM, 1) VMEM — updated max-similarity
    out_idx,      # (1, 1) int32 — argmin over ALL points (next center)
    out_val,      # (1, 1) f32   — its similarity value
    run_val,      # SMEM (1,) f32 scratch — running min value
    run_idx,      # SMEM (1,) i32 scratch — running min index
    *,
    m_points: int,
    block_m: int,
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        run_val[0] = jnp.inf
        run_idx[0] = -1

    sim = jax.lax.dot_general(                         # (TM, 1) = x · cᵀ
        x_ref[...], c_ref[...], (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    new_ms = jnp.maximum(ms_ref[...], sim)
    out_ms[...] = new_ms

    # Tile-local argmin of max-similarity == furthest point in this tile:
    # a masked min over the index iota, so a tie goes to the lowest index.
    ids = i * block_m + jax.lax.broadcasted_iota(jnp.int32, new_ms.shape, 0)
    masked = jnp.where(ids < m_points, new_ms, jnp.inf)   # padding mask
    tile_min = jnp.min(masked)
    tile_arg = jnp.min(jnp.where(masked == tile_min, ids, m_points))

    better = tile_min < run_val[0]
    run_val[0] = jnp.where(better, tile_min, run_val[0])
    run_idx[0] = jnp.where(better, tile_arg, run_idx[0])

    @pl.when(i == pl.num_programs(0) - 1)
    def _fin():
        out_idx[...] = jnp.full(out_idx.shape, run_idx[0], jnp.int32)
        out_val[...] = jnp.full(out_val.shape, run_val[0], jnp.float32)
