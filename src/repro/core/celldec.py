"""CellDec — the weight-region baseline of [Singitham et al., VLDB'04].

Faithful reimplementation of the scheme the paper compares against (its
"Query Algorithm 3", §5.4 of [18]): the weight simplex
``T = {w : w_i >= 0, sum w_i = 1}`` is split into regions; for each region a
*composite* corpus is built by squeezing the fields that the region
down-weights by a factor ``theta`` (= 0.5, the best value in [18]); each
composite corpus gets its own cluster-prune index (k-means in [18]). At query
time the region containing the user's ``w`` is located and only that region's
index is searched.

For ``s = 3`` the paper's regular 4-split of the simplex triangle is used:
corner region ``T_i = {w : w_i >= 1/2}`` (incident to vertex ``e_i``) and the
central median triangle ``T_4`` otherwise. For general ``s`` we keep the same
rule (corner region where some ``w_i >= 1/2``, else central) — this
degenerates to exactly the paper's construction at ``s = 3``.

The final candidate scoring is exact (true weighted similarity) — only the
navigation structure is region-approximate, as in [18].
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .fields import FieldSpec, normalize_fields
from .index import ClusterPruneIndex
from .weights import expand_weights, weighted_query

__all__ = ["CellDecIndex", "region_of", "region_weights"]


def region_weights(spec: FieldSpec, theta: float = 0.5) -> np.ndarray:
    """Per-region squeeze vectors, shape ``(s + 1, s)``.

    Row ``r < s`` squeezes every field except ``r`` by ``theta`` (the paper's
    ``V(T_r) = V_r + theta * others``); row ``s`` is the central all-ones
    region (``V(T_4) = V_1 + V_2 + V_3``).
    """
    s = spec.s
    sq = np.full((s + 1, s), theta, dtype=np.float32)
    sq[np.arange(s), np.arange(s)] = 1.0
    sq[s, :] = 1.0
    return sq


def region_of(w: jnp.ndarray, s: int) -> jnp.ndarray:
    """Region id of weight vectors ``w (..., s)``: corner i if w_i >= 1/2
    (ties to the largest weight), else the central region ``s``."""
    big = w >= 0.5
    corner = jnp.argmax(jnp.where(big, w, -jnp.inf), axis=-1)
    return jnp.where(jnp.any(big, axis=-1), corner, s).astype(jnp.int32)


@dataclasses.dataclass
class CellDecIndex:
    """One cluster-prune index per weight region over squeezed composites."""

    spec: FieldSpec
    theta: float
    indexes: list[ClusterPruneIndex]   # len s+1, over composite corpora
    docs: jnp.ndarray                  # (n, D) the UN-squeezed corpus (exact rescore)

    @classmethod
    def build(
        cls,
        docs: jnp.ndarray,
        spec: FieldSpec,
        k_clusters: int,
        *,
        theta: float = 0.5,
        method: str = "kmeans",
        n_clusterings: int = 1,
        key: jax.Array | None = None,
        **clusterer_kwargs,
    ) -> "CellDecIndex":
        """[18] runs ONE k-means clustering per region (no multi-clustering)."""
        if key is None:
            key = jax.random.PRNGKey(0)
        sq = region_weights(spec, theta)
        indexes = []
        for r, sub in enumerate(jax.random.split(key, sq.shape[0])):
            squeeze = expand_weights(jnp.asarray(sq[r]), spec)
            comp = normalize_fields(docs * squeeze[None, :], spec)
            # Composite is renormalised per field then globally unit-scaled so
            # cosine geometry stays valid inside the region's index.
            comp = comp / jnp.maximum(
                jnp.linalg.norm(comp, axis=-1, keepdims=True), 1e-12
            )
            idx = ClusterPruneIndex.build(
                comp,
                spec,
                k_clusters,
                n_clusterings=n_clusterings,
                method=method,
                key=sub,
                **clusterer_kwargs,
            )
            # Faithful to [18]: the region index stores ONLY the squeezed
            # composite corpus — navigation AND bucket scoring happen in the
            # composite space ("uses q in the associated indexing data
            # structure"). This approximation vs the true weighted score is
            # exactly what the paper's method removes.
            indexes.append(idx)
        return cls(spec=spec, theta=theta, indexes=indexes, docs=docs)

    # ----------------------------------------------------------------- search
    def search_weighted(
        self,
        q: jnp.ndarray,      # (nq, D) per-field normalised queries
        w: jnp.ndarray,      # (nq, s)
        *,
        probes: int,
        k: int,
        exclude: jnp.ndarray | None = None,
    ):
        """Route each query to its weight region's index; rescore exactly.

        Queries are grouped by region on the host (regions are data-dependent
        but tiny in number) — mirrors [18], where each region is a separate
        on-disk structure.
        """
        q = jnp.atleast_2d(q)
        w = jnp.atleast_2d(w)
        nq = q.shape[0]
        if exclude is None:
            exclude = jnp.full((nq,), -1, jnp.int32)
        regions = np.asarray(region_of(w, self.spec.s))
        sq = region_weights(self.spec, self.theta)

        scores = np.zeros((nq, k), np.float32)
        ids = np.full((nq, k), -1, np.int32)
        scored = np.zeros((nq,), np.int64)
        for r in range(self.spec.s + 1):
            sel = np.nonzero(regions == r)[0]
            if sel.size == 0:
                continue
            idx = self.indexes[r]
            # Faithful to [18] §5.3/5.4 (Table-2 header "CellDec weights
            # 1-1-1"): BOTH navigation and bucket scoring run in the region's
            # squeezed-composite space; the true per-query weights never
            # touch the index. We re-score the RETURNED k ids exactly so the
            # reported sims are comparable (the ids are CellDec's answer).
            comp_q = weighted_query(
                q[sel],
                jnp.broadcast_to(jnp.asarray(sq[r]), (len(sel), self.spec.s)),
                self.spec,
            )
            _, i_r, n_r = idx.search(
                comp_q, probes=probes, k=k, exclude=exclude[sel]
            )
            qw = weighted_query(q[sel], w[sel], self.spec)
            safe = jnp.where(i_r >= 0, i_r, 0)
            exact = jnp.einsum("qkd,qd->qk", self.docs[safe], qw)
            exact = jnp.where(i_r >= 0, exact, -jnp.inf)
            order = jnp.argsort(-exact, axis=-1)
            scores[sel] = np.asarray(jnp.take_along_axis(exact, order, -1))
            ids[sel] = np.asarray(jnp.take_along_axis(i_r, order, -1))
            scored[sel] = np.asarray(n_r)
        return jnp.asarray(scores), jnp.asarray(ids), jnp.asarray(scored)
