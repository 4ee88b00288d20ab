"""Pure-jnp oracle for one fused FPF round."""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["fpf_iter_ref"]


def fpf_iter_ref(
    x: jnp.ndarray,        # (m, D) unit points
    center: jnp.ndarray,   # (D,) the newest center
    maxsim: jnp.ndarray,   # (m,) running max-similarity to the center set
):
    """Returns (new_maxsim (m,), next_idx (), next_val ())."""
    sim = jnp.dot(
        x, center, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    new = jnp.maximum(maxsim, sim)
    idx = jnp.argmin(new).astype(jnp.int32)
    return new, idx, new[idx]
