"""Output-quality metrics of the paper (§6) + brute-force ground truth.

Two indexes, used identically in [Singitham et al. VLDB'04], [Chierichetti et
al. PODS'07] and the paper:

* **Competitive recall** ``CR = |A ∩ GT|`` in ``[0, k]`` — how many of the
  true k nearest neighbours the algorithm found.
* **Normalized aggregate goodness** ``NAG ∈ [0, 1]`` — aggregate distance of
  the returned set, normalised between the ground-truth optimum (→1) and the
  k *farthest* points (→0), which factors out per-query distance-range
  idiosyncrasies.

Ground truth / farthest sets come from exhaustive scoring, chunked so the
``(nq, n)`` score matrix never materialises.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = [
    "brute_force_topk",
    "brute_force_bottomk",
    "competitive_recall",
    "recall_fraction",
    "normalized_aggregate_goodness",
    "quality_report",
]


@functools.partial(jax.jit, static_argnames=("k", "largest", "chunk"))
def _exhaustive_topk(
    docs: jnp.ndarray,      # (n, D)
    qw: jnp.ndarray,        # (nq, D) pre-weighted normalised queries
    exclude: jnp.ndarray,   # (nq,) doc id to drop (or -1)
    mask: jnp.ndarray,      # (n,) bool, False = doc ineligible (tombstoned)
    *,
    k: int,
    largest: bool = True,
    chunk: int = 8192,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Streaming exact top-k (or bottom-k) by similarity over doc chunks."""
    n, d = docs.shape
    nq = qw.shape[0]
    sign = 1.0 if largest else -1.0
    pad = (-n) % chunk
    docs_p = jnp.pad(docs, ((0, pad), (0, 0)))
    mask_p = jnp.pad(mask, (0, pad))
    n_chunks = docs_p.shape[0] // chunk

    def body(carry, i):
        best_s, best_i = carry
        block = jax.lax.dynamic_slice_in_dim(docs_p, i * chunk, chunk, 0)
        mk = jax.lax.dynamic_slice_in_dim(mask_p, i * chunk, chunk, 0)
        ids = i * chunk + jnp.arange(chunk, dtype=jnp.int32)
        s = sign * jnp.dot(
            qw, block.T, precision=jax.lax.Precision.HIGHEST
        )                                               # (nq, chunk)
        s = jnp.where((ids[None, :] < n) & mk[None, :], s, -jnp.inf)
        s = jnp.where(ids[None, :] == exclude[:, None], -jnp.inf, s)
        cat_s = jnp.concatenate([best_s, s], axis=-1)
        cat_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(ids, (nq, chunk))], axis=-1
        )
        top_s, pos = jax.lax.top_k(cat_s, k)
        top_i = jnp.take_along_axis(cat_i, pos, axis=-1)
        return (top_s, top_i), None

    init = (
        jnp.full((nq, k), -jnp.inf, qw.dtype),
        jnp.full((nq, k), -1, jnp.int32),
    )
    (best_s, best_i), _ = jax.lax.scan(body, init, jnp.arange(n_chunks))
    return sign * best_s, best_i


def _doc_mask(docs, mask):
    if mask is None:
        return jnp.ones((docs.shape[0],), bool)
    return jnp.asarray(mask, bool)


def brute_force_topk(docs, qw, k, *, exclude=None, mask=None,
                     chunk: int = 8192):
    """Exact k-NN ground truth ``GT(k, q, E)``: (sims (nq,k), ids (nq,k)).

    ``mask`` (optional ``(n,)`` bool) restricts eligibility — False rows
    never enter the answer. Used to keep tombstoned (removed) documents out
    of the ground truth of a mutated index (``mask=~index.removed``).
    """
    qw = jnp.atleast_2d(qw)
    if exclude is None:
        exclude = jnp.full((qw.shape[0],), -1, jnp.int32)
    return _exhaustive_topk(
        docs, qw, jnp.asarray(exclude, jnp.int32), _doc_mask(docs, mask),
        k=k, largest=True, chunk=chunk,
    )


def brute_force_bottomk(docs, qw, k, *, exclude=None, mask=None,
                        chunk: int = 8192):
    """The farthest set ``FS(k, q, E)`` (for the NAG normaliser)."""
    qw = jnp.atleast_2d(qw)
    if exclude is None:
        exclude = jnp.full((qw.shape[0],), -1, jnp.int32)
    return _exhaustive_topk(
        docs, qw, jnp.asarray(exclude, jnp.int32), _doc_mask(docs, mask),
        k=k, largest=False, chunk=chunk,
    )


def competitive_recall(ret_ids: jnp.ndarray, gt_ids: jnp.ndarray) -> jnp.ndarray:
    """``CR = |A ∩ GT|`` per query; inputs ``(nq, k)``; invalid ids are -1."""
    hit = (ret_ids[..., :, None] == gt_ids[..., None, :]) & (
        ret_ids[..., :, None] >= 0
    )
    return jnp.sum(jnp.any(hit, axis=-1), axis=-1).astype(jnp.float32)


def recall_fraction(ret_ids: jnp.ndarray, gt_ids: jnp.ndarray) -> jnp.ndarray:
    """``CR/k`` in ``[0, 1]`` per query — the planner-calibration target
    variable (a ``recall_target=`` promise is a statement about this)."""
    return competitive_recall(ret_ids, gt_ids) / gt_ids.shape[-1]


def normalized_aggregate_goodness(
    ret_sims: jnp.ndarray,   # (nq, k) similarities of the returned set
    gt_sims: jnp.ndarray,    # (nq, k) similarities of the true k-NN
    far_sims: jnp.ndarray,   # (nq, k) similarities of the k farthest points
) -> jnp.ndarray:
    """NAG per query, computed on distances ``mu = 1 - sim``.

    ``NAG = (W - sum_A mu) / (W - sum_GT mu)`` with ``W = sum_FS mu``.
    Missing retrieved slots (sim = -inf) are scored as worst-possible (the
    farthest-set mean), keeping NAG in [0, 1] and penalising short answers.
    """
    far_mu = 1.0 - far_sims
    w = jnp.sum(far_mu, axis=-1)
    fill = jnp.mean(far_mu, axis=-1, keepdims=True)
    ret_mu = jnp.where(jnp.isfinite(ret_sims), 1.0 - ret_sims, fill)
    gt_mu = 1.0 - gt_sims
    num = w - jnp.sum(ret_mu, axis=-1)
    den = w - jnp.sum(gt_mu, axis=-1)
    return jnp.where(den > 1e-9, num / den, jnp.ones_like(num))


def quality_report(ret_sims, ret_ids, gt_sims, gt_ids, far_sims):
    """Mean CR and mean NAG over a query set (the paper's Table-2 cells)."""
    cr = competitive_recall(ret_ids, gt_ids)
    nag = normalized_aggregate_goodness(ret_sims, gt_sims, far_sims)
    return {
        "mean_recall": float(jnp.mean(cr)),
        "mean_nag": float(jnp.mean(nag)),
    }
