"""Pluggable clusterer layer — ONE seam over the index build side.

The query side got its seam in the engine layer (:mod:`repro.core.engine`):
three execution mechanisms behind one protocol, with shared semantics and a
registry. The build side — where the paper's headline *preprocessing* claim
lives (FPF-on-sample builds the index >= 30x faster than CellDec's k-means,
5:28 vs 215:48 wall hours [Geraci et al., SPIRE'06]) — historically was a
bare dict of three loose functions, and the fused Pallas FPF round
(:mod:`repro.kernels.fpf_iter`) was never reachable from an index build.
This module mirrors the engine seam for clustering:

``fpf``
    The paper's clusterer: Gonzalez furthest-point-first on a
    ``sqrt(K*n)`` sample, pure-JAX rounds (:func:`fpf_centers`) — the
    portable reference and the semantics oracle for ``fpf_fused``.
``fpf_fused``
    The same algorithm with every FPF round driven through the Pallas
    ``fpf_iter`` kernel (one VMEM-resident pass per round: MXU matvec +
    running-min fold, vs three HBM passes in naive form). Runs interpreted
    off-TPU — bit-compatible with ``fpf``, so an index built with either
    backend is *identical* at a fixed seed (tests/test_cluster.py pins
    this), and ``pick_clusterer`` auto-selects it on TPU.
``kmeans``
    Full-corpus spherical Lloyd — CellDec's clusterer [Singitham et al.
    VLDB'04], kept as the expensive baseline Table 1 measures against.
``random``
    PODS'07 random leaders + centroid representatives [Chierichetti et
    al.], the cheap baseline.

All clusterers share ONE streaming-assignment + representative-adjust tail
(:func:`assign_refine`): chunked :func:`assign_to_centers` (the ``(n, K)``
similarity matrix never materialises) plus rounds of medoid or centroid
adjustment — so probe semantics downstream compare clusterings that were
finalised by the same code path. The same :func:`assign_to_centers` is what
:meth:`repro.core.index.ClusterPruneIndex.add_documents` streams new
documents through at serve time, so incremental maintenance and the initial
build agree on assignment semantics by construction.

Select a clusterer by name or let the platform pick::

    clusterer = get_clusterer("auto")        # fpf_fused on TPU, fpf elsewhere
    result = clusterer.cluster(x, k, key)    # ClusteringResult

Adding a clusterer = any class satisfying the :class:`Clusterer` protocol
(``cluster(x, k, key) -> ClusteringResult``, reusing :func:`assign_refine`
for the tail), decorated with ``@register_clusterer("name")`` —
``ClusterPruneIndex.build(method="name")`` and the Table-1 benchmark pick
it up from the registry (see ROADMAP.md, "Architecture: build pipeline";
``tests/test_cluster.py`` has the working template).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Protocol, runtime_checkable

import jax
import jax.numpy as jnp

__all__ = [
    "ClusteringResult",
    "Clusterer",
    "CLUSTERERS",
    "register_clusterer",
    "available_clusterers",
    "pick_clusterer",
    "get_clusterer",
    "fpf_centers",
    "assign_to_centers",
    "assign_to_centers_multi",
    "assign_refine",
    "fpf_cluster",
    "kmeans_cluster",
    "random_leader_cluster",
]


@dataclasses.dataclass
class ClusteringResult:
    """Output of any registered clusterer."""

    assign: jnp.ndarray      # (n,) int32 cluster id per point
    reps: jnp.ndarray        # (K, D) representative per cluster (unit norm)
    counts: jnp.ndarray      # (K,) points per cluster
    max_radius: jnp.ndarray  # () max cosine distance of a point to its rep

    @property
    def k(self) -> int:
        return self.reps.shape[0]


# ------------------------------------------------------------------ registry
@runtime_checkable
class Clusterer(Protocol):
    """What every registered clusterer provides: one full clustering."""

    name: str

    def cluster(
        self, x: jnp.ndarray, k: int, key: jax.Array
    ) -> ClusteringResult:
        """Cluster unit-norm points ``x (n, D)`` into ``k`` groups."""
        ...


CLUSTERERS: dict[str, type] = {}


def register_clusterer(name: str):
    """Class decorator: register a :class:`Clusterer` implementation."""

    def deco(cls):
        cls.name = name
        CLUSTERERS[name] = cls
        return cls

    return deco


def available_clusterers() -> tuple[str, ...]:
    return tuple(CLUSTERERS)


def pick_clusterer() -> str:
    """Platform auto-pick: the fused Pallas FPF path on TPU (where each
    round is a real one-pass kernel), the pure-JAX reference elsewhere
    (interpret-mode Pallas is bit-compatible but slow — tests only)."""
    return "fpf_fused" if jax.default_backend() == "tpu" else "fpf"


def get_clusterer(name: str = "auto", **opts) -> Clusterer:
    """Clusterer instance by registry name (``"auto"`` = platform pick).

    ``opts`` are the clusterer's constructor options (e.g. ``iters=`` for
    ``kmeans``, ``sample_size=`` / ``refine_iters=`` for the FPF pair).
    """
    resolved = pick_clusterer() if name in (None, "auto") else name
    if resolved not in CLUSTERERS:
        raise ValueError(
            f"unknown clusterer {name!r}; available: {sorted(CLUSTERERS)}"
        )
    return CLUSTERERS[resolved](**opts)


# ------------------------------------------------------- shared primitives
@functools.partial(jax.jit, static_argnames=("k",))
def fpf_centers(x: jnp.ndarray, k: int, key: jax.Array) -> jnp.ndarray:
    """Gonzalez FPF on unit-norm points ``x (m, D)`` -> center indices (k,).

    Iteratively picks the point furthest (in cosine distance) from the set of
    already-chosen centers. Maintains ``maxsim`` = max similarity of every
    point to any chosen center; the furthest point is ``argmin(maxsim)``.
    O(k·m·D) — one matvec per round. The Pallas ``fpf_iter`` kernel fuses
    exactly one round of this loop; ``fpf_centers_fused`` is the drop-in
    kernel-driven variant.
    """
    m = x.shape[0]
    first = jax.random.randint(key, (), 0, m, dtype=jnp.int32)
    idxs = jnp.zeros((k,), jnp.int32).at[0].set(first)
    maxsim = jnp.full((m,), -jnp.inf, x.dtype)

    def body(i, carry):
        idxs, maxsim = carry
        cvec = x[idxs[i - 1]]
        sim = jnp.dot(x, cvec, precision=jax.lax.Precision.HIGHEST)
        maxsim = jnp.maximum(maxsim, sim)
        nxt = jnp.argmin(maxsim).astype(jnp.int32)
        return idxs.at[i].set(nxt), maxsim

    idxs, _ = jax.lax.fori_loop(1, k, body, (idxs, maxsim))
    return idxs


@functools.partial(jax.jit, static_argnames=("chunk",))
def assign_to_centers(
    x: jnp.ndarray, reps: jnp.ndarray, *, chunk: int = 16384
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Assign every point to its most-similar representative.

    Chunked over rows so the (n, K) similarity matrix never fully
    materialises. Returns ``(assign (n,), sim (n,))``. This is the ONE
    assignment primitive: the build tail (:func:`assign_refine`) and
    incremental ``add_documents`` both stream through it — the single-
    clustering case of :func:`assign_to_centers_multi`, so the two can
    never drift in argmax/tie-break semantics.
    """
    a, s = assign_to_centers_multi(x, reps[None], chunk=chunk)
    return a[0], s[0]


def assign_to_centers_multi(
    x: jnp.ndarray, leaders: jnp.ndarray, *, chunk: int = 16384
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Assign every point under ALL T clusterings with one fused matmul.

    ``leaders`` is the index's ``(T, K, D)`` tensor; each chunk of rows is
    scored against the flattened ``(T·K, D)`` leader matrix in a single
    device call and the per-clustering argmax is taken over each K-segment
    — T times fewer kernel launches than looping :func:`assign_to_centers`
    over clusterings, and one big MXU matmul instead of T skinny ones.
    The segment reshape does not reorder within a clustering, so argmax
    tie-breaks match the single-clustering case by construction.
    Returns ``(assign (T, n) int32, sim (T, n))``. This is what
    :meth:`repro.core.index.ClusterPruneIndex.add_documents` streams
    batched ingests through.
    """
    t, k, d = leaders.shape
    flat = leaders.reshape(t * k, d)
    n = x.shape[0]
    pad = (-n) % chunk
    xp = jnp.pad(x, ((0, pad), (0, 0)))

    def one(block):
        sims = (block @ flat.T).reshape(block.shape[0], t, k)
        return jnp.argmax(sims, axis=-1).astype(jnp.int32), jnp.max(sims, -1)

    a, s = jax.lax.map(one, xp.reshape(-1, chunk, d))
    return (
        a.reshape(-1, t)[:n].T,
        s.reshape(-1, t)[:n].T,
    )


def _medoids(
    x: jnp.ndarray, assign: jnp.ndarray, k: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-cluster medoid = member most similar to the (normalised) centroid.

    The batch analogue of the paper's incremental medoid adjustment: compute
    the spherical centroid, then snap back to the nearest actual point so the
    representative stays a (sparse, in the paper) corpus vector.
    """
    n = x.shape[0]
    counts = jax.ops.segment_sum(jnp.ones((n,), x.dtype), assign, k)
    cent = jax.ops.segment_sum(x, assign, k)
    cent = cent / jnp.maximum(jnp.linalg.norm(cent, axis=-1, keepdims=True), 1e-12)
    score = jnp.sum(x * cent[assign], axis=-1)          # sim of each pt to its centroid
    best = jax.ops.segment_max(score, assign, k)        # (K,)
    is_best = score >= best[assign] - 1e-7
    cand = jnp.where(is_best, jnp.arange(n, dtype=jnp.int32), n)
    medoid_idx = jax.ops.segment_min(cand, assign, k)   # first argmax per cluster
    medoid_idx = jnp.clip(medoid_idx, 0, n - 1)         # empty cluster -> arbitrary
    return x[medoid_idx], counts


def _centroids(
    x: jnp.ndarray, assign: jnp.ndarray, k: int, prev: jnp.ndarray
) -> jnp.ndarray:
    """Unit-normalised per-cluster centroid; empty clusters keep ``prev``."""
    n = x.shape[0]
    counts = jax.ops.segment_sum(jnp.ones((n,), x.dtype), assign, k)
    cent = jax.ops.segment_sum(x, assign, k)
    norm = jnp.linalg.norm(cent, axis=-1, keepdims=True)
    return jnp.where(counts[:, None] > 0, cent / jnp.maximum(norm, 1e-12), prev)


def assign_refine(
    x: jnp.ndarray,
    k: int,
    reps: jnp.ndarray,
    *,
    refine_iters: int = 0,
    rep_update: str = "medoid",
    chunk: int = 16384,
) -> ClusteringResult:
    """The shared streaming-assignment + representative-adjust tail.

    Assign all points to ``reps`` (chunked), then ``refine_iters`` rounds of
    representative adjustment (``"medoid"`` — the paper's FPF pipeline —
    or ``"centroid"`` — Lloyd) each followed by re-assignment, so the
    returned ``assign`` is always consistent with the returned ``reps``.
    Every registered clusterer finalises through this one implementation.
    """
    if rep_update not in ("medoid", "centroid"):
        raise ValueError(
            f"rep_update must be 'medoid' or 'centroid', got {rep_update!r}"
        )
    n = x.shape[0]
    assign, sim = assign_to_centers(x, reps, chunk=chunk)
    for _ in range(refine_iters):
        if rep_update == "medoid":
            reps, _ = _medoids(x, assign, k)
        else:
            reps = _centroids(x, assign, k, reps)
        assign, sim = assign_to_centers(x, reps, chunk=chunk)
    counts = jax.ops.segment_sum(jnp.ones((n,), x.dtype), assign, k)
    return ClusteringResult(
        assign=assign, reps=reps, counts=counts, max_radius=1.0 - jnp.min(sim)
    )


# ---------------------------------------------------------------- clusterers
class _ClustererBase:
    """Shared option plumbing for registered clusterers."""

    def __init__(self, *, chunk: int = 16384):
        self.chunk = chunk

    def cluster(self, x, k, key) -> ClusteringResult:
        raise NotImplementedError


@register_clusterer("fpf")
class FPFClusterer(_ClustererBase):
    """The paper's full preprocessing pipeline for ONE clustering.

    1. sample ``m = ceil(sqrt(k*n))`` points (without replacement),
    2. FPF on the sample -> K centers,
    3. assign all points to the nearest center,
    4. ``refine_iters`` rounds of medoid adjustment + re-assignment
       (the shared :func:`assign_refine` tail).
    """

    def __init__(
        self,
        *,
        sample_size: int | None = None,
        refine_iters: int = 1,
        chunk: int = 16384,
    ):
        super().__init__(chunk=chunk)
        self.sample_size = sample_size
        self.refine_iters = refine_iters

    def _centers(self, xs: jnp.ndarray, k: int, key: jax.Array) -> jnp.ndarray:
        """The FPF rounds themselves — ``fpf_fused`` overrides ONLY this."""
        return fpf_centers(xs, k, key)

    def cluster(self, x, k, key) -> ClusteringResult:
        n = x.shape[0]
        sample_size = self.sample_size
        if sample_size is None:
            sample_size = int(jnp.ceil(jnp.sqrt(k * n)))
        sample_size = max(min(sample_size, n), k)
        skey, fkey = jax.random.split(key)
        sample_idx = jax.random.permutation(skey, n)[:sample_size]
        centers_in_sample = self._centers(x[sample_idx], k, fkey)
        reps = x[sample_idx[centers_in_sample]]
        return assign_refine(
            x, k, reps, refine_iters=self.refine_iters, rep_update="medoid",
            chunk=self.chunk,
        )


@register_clusterer("fpf_fused")
class FusedFPFClusterer(FPFClusterer):
    """FPF with every Gonzalez round driven through the Pallas ``fpf_iter``
    kernel (one fused VMEM pass per round instead of three HBM passes).

    Same sampling, same tail, same tie-breaking as ``fpf`` — an index built
    with either backend is identical at a fixed seed. ``interpret=None``
    defers to the platform (real kernel on TPU, interpreter elsewhere).
    """

    def __init__(
        self,
        *,
        sample_size: int | None = None,
        refine_iters: int = 1,
        chunk: int = 16384,
        interpret: bool | None = None,
    ):
        super().__init__(
            sample_size=sample_size, refine_iters=refine_iters, chunk=chunk
        )
        self.interpret = interpret

    def _centers(self, xs, k, key):
        from ..kernels.fpf_iter import fpf_centers_fused

        return fpf_centers_fused(xs, k, key, interpret=self.interpret)


@register_clusterer("kmeans")
class KMeansClusterer(_ClustererBase):
    """Spherical k-means (Lloyd) — the clusterer of the CellDec baseline.

    Faithful to what [Singitham et al. VLDB'04] run — full-corpus Lloyd
    iterations with dense centroids — expressed as ``iters`` centroid-adjust
    rounds of the shared tail. One deliberate change vs the pre-seam
    implementation: the tail re-assigns AFTER the final centroid update
    (``iters`` updates, ``iters + 1`` assignment passes), so the returned
    ``assign`` is consistent with the returned ``reps`` instead of lagging
    one half-step behind; the centroid sequence itself is unchanged at a
    fixed seed. This is the expensive preprocessing the paper's FPF
    replaces (Table 1: 30x+ gap).
    """

    def __init__(self, *, iters: int = 10, chunk: int = 16384):
        super().__init__(chunk=chunk)
        self.iters = iters

    def cluster(self, x, k, key) -> ClusteringResult:
        n = x.shape[0]
        init_idx = jax.random.permutation(key, n)[:k]
        return assign_refine(
            x, k, x[init_idx], refine_iters=self.iters, rep_update="centroid",
            chunk=self.chunk,
        )


@register_clusterer("random")
class RandomLeaderClusterer(_ClustererBase):
    """Random-leader clustering — the PODS'07 baseline [Chierichetti et al.].

    Pick ``K`` documents uniformly at random as leaders, assign every
    document to its closest leader, then use each group's *centroid* as the
    representative for cluster-prune search. Search keeps the ORIGINAL
    leader assignment (per the paper), so the tail is used only for the
    assignment pass, not for re-assignment after the centroid step.
    """

    def cluster(self, x, k, key) -> ClusteringResult:
        n = x.shape[0]
        leader_idx = jax.random.permutation(key, n)[:k]
        assign, _ = assign_to_centers(x, x[leader_idx], chunk=self.chunk)
        counts = jax.ops.segment_sum(jnp.ones((n,), x.dtype), assign, k)
        reps = _centroids(x, assign, k, x[leader_idx])
        # Re-derive point->centroid similarity for the radius statistic only.
        _, sim2 = assign_to_centers(x, reps, chunk=self.chunk)
        return ClusteringResult(
            assign=assign, reps=reps, counts=counts,
            max_radius=1.0 - jnp.min(sim2),
        )


# ------------------------------------------------------- function back-compat
def fpf_cluster(x, k, key, **opts) -> ClusteringResult:
    """Functional shim over ``get_clusterer("fpf")`` (pre-seam API)."""
    return get_clusterer("fpf", **opts).cluster(x, k, key)


def kmeans_cluster(x, k, key, **opts) -> ClusteringResult:
    """Functional shim over ``get_clusterer("kmeans")`` (pre-seam API)."""
    return get_clusterer("kmeans", **opts).cluster(x, k, key)


def random_leader_cluster(x, k, key, **opts) -> ClusteringResult:
    """Functional shim over ``get_clusterer("random")`` (pre-seam API)."""
    return get_clusterer("random", **opts).cluster(x, k, key)
