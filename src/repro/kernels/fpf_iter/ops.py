"""Jit'd wrapper for the fused FPF round + a full FPF loop built on it."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import pad_to, use_interpret
from .kernel import fpf_iter_kernel

__all__ = ["fpf_iter", "fpf_centers_fused", "pick_block_m"]


# VMEM the double-buffered (TM, D) point tile may take: half of the 16 MiB
# scoped VMEM default, leaving the rest to the (TM, 1) columns.
FPF_TILE_VMEM_BYTES = 8 * 2**20


def pick_block_m(d: int, *, max_rows: int = 1024) -> int:
    """Rows per FPF tile: the most (a multiple of 8, at most ``max_rows``)
    whose double-buffered fp32 ``(TM, D)`` tile fits
    :data:`FPF_TILE_VMEM_BYTES`."""
    rows = FPF_TILE_VMEM_BYTES // (2 * 4 * int(d))
    return int(max(8, min(max_rows, rows // 8 * 8)))


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def fpf_iter(
    x: jnp.ndarray,        # (m, D)
    center: jnp.ndarray,   # (D,)
    maxsim: jnp.ndarray,   # (m,)
    *,
    block_m: int | None = None,
    interpret: bool | None = None,
):
    """One fused FPF round. Returns ``(new_maxsim (m,), next_idx, next_val)``.

    ``block_m`` defaults to :func:`pick_block_m` of the point width."""
    if interpret is None:
        interpret = use_interpret()
    m, d = x.shape
    if block_m is None:
        block_m = pick_block_m(d)
    block_m = min(block_m, pad_to(m, 8))
    m_p = pad_to(m, block_m)
    x_p = jnp.pad(x, ((0, m_p - m), (0, 0)))
    ms_p = jnp.pad(maxsim, (0, m_p - m))[:, None]

    new_ms, idx, val = pl.pallas_call(
        functools.partial(fpf_iter_kernel, m_points=m, block_m=block_m),
        name="fpf_iter",
        grid=(m_p // block_m,),
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((block_m, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m_p, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.SMEM((1,), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
        ],
        interpret=interpret,
    )(x_p, center[None, :], ms_p)
    return new_ms[:m, 0], idx[0, 0], val[0, 0]


def fpf_centers_fused(
    x: jnp.ndarray, k: int, key: jax.Array, *, interpret: bool | None = None,
):
    """Full Gonzalez FPF on the fused round kernel (drop-in for
    :func:`repro.core.cluster.fpf_centers` — the ``fpf_fused`` registered
    clusterer drives every build round through this)."""
    m = x.shape[0]
    first = jax.random.randint(key, (), 0, m, dtype=jnp.int32)
    idxs = [first]
    maxsim = jnp.full((m,), -jnp.inf, jnp.float32)
    cur = first
    for _ in range(k - 1):
        maxsim, nxt, _ = fpf_iter(x, x[cur], maxsim, interpret=interpret)
        idxs.append(nxt)
        cur = nxt
    return jnp.stack(idxs)
