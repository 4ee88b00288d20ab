"""Query-side dynamic weight embedding (the paper's §4 theorem, executable).

Given per-field query vectors ``q_i`` (unit norm) and positive weights ``w_i``
summing to 1, the aggregate weighted similarity against a record
``p = [p_1, ..., p_s]`` is, by linearity,

    WS(w, q, p) = sum_i w_i (q_i · p_i) = Q_w · p,
    Q_w = [w_1 q_1, ..., w_s q_s].

Normalising ``Q'_w = Q_w / |Q_w|`` turns the *weighted multi-field* problem
into a plain cosine-distance search of the *unweighted* concatenated corpus:

    NWD(w, q, p) = 1 - Q'_w · p = D(Q'_w, p).

``1/|Q_w|`` is a positive per-query constant, so the top-k ranking under
``WS`` and under ``Q'_w · p`` are identical — the index can be built once,
with no knowledge of the weights. ``tests/test_weights.py`` checks this
exactly (property-based).

The cosine distance ``d(x, y) = 1 - x·y`` satisfies the extended triangle
inequality ``d(x,y)^a + d(y,z)^a >= d(x,z)^a`` with ``a = 1/2`` (because
``|x - y|^2 = 2 d(x,y)`` for unit vectors), which is what makes the
cluster-pruning bound sound for the reduced problem.
"""

from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp
import numpy as np

from .fields import FieldSpec, concat_fields, split_fields

__all__ = [
    "weighted_query",
    "aggregate_similarity",
    "nwd",
    "cosine_distance",
    "expand_weights",
    "validate_weights",
]

_EPS = 1e-12


def validate_weights(w, spec: FieldSpec | None = None) -> np.ndarray:
    """Check per-field weights at the API boundary; return them as float32.

    The §4 reduction assumes *non-negative* weights with at least one
    strictly positive entry: a negative weight breaks the theorem's ranking
    equivalence (the weighted query is no longer a conic combination), and an
    all-zero vector normalises ``Q_w`` to garbage (``0 / eps``) — both
    previously flowed silently into :func:`weighted_query` and produced
    NaN-ish rankings. Accepts ``(s,)`` or ``(nq, s)``; raises ``ValueError``
    with the offending row, never silently repairs.
    """
    arr = np.asarray(w, np.float32)
    if spec is not None and (arr.ndim == 0 or arr.shape[-1] != spec.s):
        raise ValueError(
            f"weights must have one entry per field "
            f"({spec.s}: {list(spec.names)}), got shape {arr.shape}"
        )
    if not np.all(np.isfinite(arr)):
        raise ValueError(f"field weights must be finite, got {arr.tolist()}")
    if np.any(arr < 0):
        raise ValueError(
            f"field weights must be non-negative, got {arr.tolist()}"
        )
    if np.any(np.sum(arr, axis=-1) <= 0):
        raise ValueError(
            "field weights must include at least one positive entry "
            f"(all-zero weights have no defined ranking), got {arr.tolist()}"
        )
    return arr


def expand_weights(w: jnp.ndarray, spec: FieldSpec) -> jnp.ndarray:
    """Expand per-field weights ``(..., s)`` to concat coords ``(..., D)``.

    The repeat counts stay a host constant, so a traced caller compiles
    them into its program instead of uploading them on every call."""
    return jnp.repeat(
        w, np.asarray(spec.dims), axis=-1, total_repeat_length=spec.total_dim
    )


def weighted_query(
    q: jnp.ndarray | Sequence[jnp.ndarray],
    w: jnp.ndarray,
    spec: FieldSpec,
    *,
    normalize: bool = True,
) -> jnp.ndarray:
    """Build the (normalised) weighted query vector ``Q'_w``.

    Args:
      q: concatenated query ``(..., D)`` (each field block unit-norm) or a
        sequence of per-field arrays.
      w: weights ``(..., s)``, positive. Need not sum to one — the
        normalisation absorbs any positive scale (ranking invariant).
      spec: the corpus field spec.
      normalize: if False returns raw ``Q_w`` (used by tests/the theorem).
    """
    if isinstance(q, (list, tuple)):
        # Only genuine sequences are per-field lists. A bare np.ndarray is a
        # concatenated (..., D) query — iterating it would concat the batch
        # rows into one giant flat vector.
        q = concat_fields(list(q))
    else:
        q = jnp.asarray(q)
    qw = q * expand_weights(w, spec)
    if not normalize:
        return qw
    norm = jnp.linalg.norm(qw, axis=-1, keepdims=True)
    return qw / jnp.maximum(norm, _EPS)


def aggregate_similarity(
    q: jnp.ndarray, w: jnp.ndarray, p: jnp.ndarray, spec: FieldSpec
) -> jnp.ndarray:
    """Direct ``WS(w,q,p) = sum_i w_i (q_i · p_i)`` — the definitional form.

    ``q``: (D,), ``w``: (s,), ``p``: (..., D). Used as the oracle against the
    reduced form in tests and for final exact re-scoring of candidates.
    """
    sims = []
    q_f = split_fields(q, spec)
    p_f = split_fields(p, spec)
    for i in range(spec.s):
        sims.append(w[..., i] * jnp.sum(q_f[i] * p_f[i], axis=-1))
    return sum(sims)


def cosine_distance(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """``d(x,y) = 1 - x·y`` for unit vectors (sqrt(d) is a metric)."""
    return 1.0 - jnp.sum(x * y, axis=-1)


def nwd(
    q: jnp.ndarray, w: jnp.ndarray, p: jnp.ndarray, spec: FieldSpec
) -> jnp.ndarray:
    """Normalised weighted distance ``NWD(w,q,p) = 1 - Q'_w · p``."""
    qn = weighted_query(q, w, spec)
    return 1.0 - jnp.einsum("...d,...d->...", jnp.broadcast_to(qn, p.shape), p)
