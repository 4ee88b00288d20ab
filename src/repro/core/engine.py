"""Pluggable search-engine layer — ONE seam over the three search paths.

The paper's hot path (probe T clusterings, score the probed buckets, merge a
deduplicated top-k) historically existed three times: a pure-JAX gather path,
a fused Pallas kernel that was never wired into serving, and a ``shard_map``
distributed path with its own API. This module unifies them behind a single
:class:`SearchEngine` protocol with three registered backends:

``reference``
    Pure-JAX doc-major gather (:func:`_search_block`) — the single-host
    portable path and the semantics oracle for the other two.
``fused``
    The query-tiled Pallas ``bucket_score`` v2 kernel over the bucket-major
    ``(T*K, B, D)`` corpus materialised at index build time (interpret-mode
    off-TPU): probes are contiguous block DMAs instead of row gathers, a
    per-tile probe-dedup schedule — built ON DEVICE under ``jit``
    (:func:`~repro.kernels.bucket_score.ops.build_probe_schedule_device`,
    no host round-trip in the hot path) — reads each shared bucket from HBM
    once per query tile, and each block is scored against the whole tile as
    one ``(QT, D)×(D, B)`` MXU matmul (optionally over bf16 or int8 bucket
    storage with fp32 accumulation; int8 packs dequantise per bucket via the
    index's ``bucket_scales``).
``sharded``
    The ``shard_map`` doc-sharded path of :mod:`repro.core.distributed`,
    running the SAME fused v2 kernel shard-locally: each device holds a
    bucket-major ``(T*K, B_local, D)`` pack of its slice of every cluster
    (``pack_dtype`` bf16/int8 supported, per-``(shard, bucket)`` scales),
    navigation and the probe-dedup schedule are computed once (replicated —
    probed buckets are identical across shards), and the only collective is
    the 2k-word per-shard top-k merge. Any corpus size shards cleanly
    (sentinel-row padding); the exact-rescore tail re-ranks against the
    row-sharded fp32 corpus without gathering it.

All backends share *identical* probe semantics (:func:`split_probes` divides
the budget evenly over the T clusterings), navigation-vs-scoring query split,
duplicate suppression across overlapping clusterings, ``exclude`` masking,
and the paper's Fig-1 ``n_scored`` distance-computation accounting — so
every consumer (serving, benchmarks, examples) measures the same algorithm
and differs only in the execution mechanism.

All backends also share the opt-in **exact-rescore tail**
(``search(..., rescore=R)``, ``R >= k``): the pruned search runs at depth
``R``, the surviving candidates are re-scored against the fp32 doc-major
corpus in one gather+matmul (:func:`_exact_rescore`), and the final top-k
cut happens on those exact scores. This bounds whatever noise a reduced
storage precision injected — the returned ORDER and SCORES are exact for
the candidate set the pruned search surfaced — and the re-scored
candidates are honestly charged to ``n_scored``.

On top of the budgeted path every backend exposes the **tiered exact
path** (``search_exact``): probe ALL T·K buckets, so every live document
is a candidate and the result is the true top-k — the same clustering
that prunes approximate search also organises exact search into
best-first bucket blocks (Dimond & Sanders). Backends that score from a
reduced-precision pack (``uses_packed_storage``) finish the exact tier
through the fp32 rescore tail so returned ids/scores stay exact. The
**escalation driver** (``search_escalating``) makes a calibrated recall
floor a guarantee instead of a prediction: run the planned budget, and
while the ladder's ``predicted_recall`` sits below the floor, re-run at
the next calibrated rung — ultimately the exact tier — charging every
tier's candidates cumulatively to ``n_scored``.

Select a backend by name or let :func:`pick_backend` choose from the
platform and the index's size (TPU -> ``fused`` while the pack fits one
device, ``sharded`` over the host's devices where it does not;
multi-device -> ``sharded``, else ``reference``)::

    engine = get_engine(index, "auto")
    scores, ids, n_scored = engine.search(qw, probes=12, k=10)

Adding a backend = subclass :class:`_EngineBase`, implement ``search``, and
decorate with ``@register_backend("name")`` (see ROADMAP.md, "Architecture:
search backends").
"""

from __future__ import annotations

import functools
import math
from typing import Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from .. import tracing
from ..tracing import span
from .weights import weighted_query

__all__ = [
    "SearchEngine",
    "BACKENDS",
    "register_backend",
    "available_backends",
    "pick_backend",
    "get_engine",
    "split_probes",
    "sweep_probes",
]


def split_probes(probes: int, t: int) -> tuple[int, ...]:
    """Distribute a total probe budget over T clusterings (paper: evenly)."""
    base, rem = divmod(probes, t)
    return tuple(base + (1 if i < rem else 0) for i in range(t))


@runtime_checkable
class SearchEngine(Protocol):
    """What every backend provides: batched pruned top-k over one index."""

    name: str

    def search(
        self,
        qw: jnp.ndarray,
        *,
        probes: int,
        k: int,
        exclude: jnp.ndarray | None = None,
        nav_query: jnp.ndarray | None = None,
        rescore: int | None = None,
    ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """-> (scores (nq, k), ids (nq, k), n_scored (nq,))."""
        ...

    def search_weighted(self, q, w, *, probes, k, exclude=None):
        ...


BACKENDS: dict[str, type] = {}


def register_backend(name: str):
    """Class decorator: register a :class:`SearchEngine` implementation."""

    def deco(cls):
        cls.name = name
        BACKENDS[name] = cls
        return cls

    return deco


def available_backends() -> tuple[str, ...]:
    return tuple(BACKENDS)


# Share of one device's memory the fused backend's serving state (the
# bucket-major pack at its dtype, plus the fp32 corpus) may take. The rest
# holds the kernel's working set, the probe schedule and XLA's temporaries;
# an index above it is sharded where the host has several devices.
_FUSED_MEMORY_SHARE = 0.75


def pack_bytes(index) -> int:
    """Bytes of ``index``'s whole bucket-major pack at its ``pack_dtype``:
    T·K·B·D·itemsize, from the shapes alone (nothing is materialised)."""
    itemsize = jnp.dtype(index.pack_dtype or index.docs.dtype).itemsize
    d = int(index.docs.shape[-1])
    return math.prod(int(x) for x in index.buckets.shape) * d * itemsize


def _host() -> tuple[str, int, int]:
    """``(platform, device count, bytes one device may hold)``; the limit is
    0 where the backend does not report it."""
    stats = jax.devices()[0].memory_stats() or {}
    return (jax.default_backend(), jax.device_count(),
            int(stats.get("bytes_limit", 0)))


def pick_backend(index=None) -> str:
    """The backend ``"auto"`` resolves to, by platform and by size.

    On a TPU: ``fused`` while ``index``'s serving state (its bucket-major
    pack at ``pack_dtype`` plus the fp32 corpus) fits one device within
    :data:`_FUSED_MEMORY_SHARE` of its byte limit, else ``sharded`` over all
    of the host's devices where it has several. Elsewhere: ``sharded`` on a
    multi-device host, else ``reference``. Without an index, or where the
    device reports no limit, the state counts as fitting. Leaves a
    :data:`~repro.tracing.ENGINE_PICK` marker with the backend, the pack's
    bytes, the device's byte limit and the device count.
    """
    platform, n_devices, limit = _host()
    pack = state = 0
    if index is not None:
        docs = index.docs
        pack = pack_bytes(index)
        state = pack + math.prod(docs.shape) * docs.dtype.itemsize
    if platform != "tpu":
        name = "sharded" if n_devices > 1 else "reference"
    elif n_devices > 1 and limit and state > _FUSED_MEMORY_SHARE * limit:
        name = "sharded"
    else:
        name = "fused"
    with span(tracing.ENGINE_PICK, backend=name, pack_bytes=pack,
              bytes_limit=limit, devices=n_devices):
        pass
    return name


def get_engine(index, backend: str = "auto", **opts) -> SearchEngine:
    """Engine for ``index``, cached on the index keyed by ``(name, opts)``.

    Keying the per-index cache by the opts (not just the backend name)
    means variant engines — a sweep's per-level ``qchunk``, an explicit
    ``query_tile`` or ``interpret`` override — are constructed and traced
    ONCE and then reused, instead of rebuilt per call (an L-level
    ``sweep_probes`` used to re-instantiate and re-trace the reference
    engine at every level). Unhashable opts (e.g. a ``mesh`` object) fall
    back to an uncached construction.
    """
    name = pick_backend(index) if backend in (None, "auto") else backend
    if name not in BACKENDS:
        raise ValueError(
            f"unknown backend {name!r}; available: {sorted(BACKENDS)}"
        )
    cls = BACKENDS[name]
    try:
        key = (name, tuple(sorted(opts.items())))
        hash(key)
    except TypeError:
        key = None
    if key is None:
        return cls(index, **opts)
    cache = getattr(index, "_engines", None)
    if cache is None:
        cache = {}
        index._engines = cache
    if key not in cache:
        cache[key] = cls(index, **opts)
    return cache[key]


# Memory cap for the reference backend's (qchunk, m, D) candidate gather
# during a sweep; high probe levels shrink the query chunk instead of
# materialising a multi-GB tensor.
_SWEEP_GATHER_BYTES = 512 * 2**20


def sweep_probes(
    index,
    qw: jnp.ndarray,
    *,
    probe_grid,
    k: int,
    exclude: jnp.ndarray | None = None,
    nav_query: jnp.ndarray | None = None,
    backend: str | None = None,
    engine_opts=None,
    rescore: int | None = None,
) -> list[tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]]:
    """Run ONE engine over a probe grid — the planner-calibration sweep.

    The engine (and with it the bucket-major pack, the sharded layout, every
    per-index cache) is resolved once and reused across all probe levels, so
    an L-level sweep costs L searches, not L index preparations. For the
    ``reference`` backend the query-chunk size is adapted per level so the
    ``(qchunk, candidates, D)`` gather stays within a fixed memory budget —
    high probe budgets would otherwise materialise multi-GB intermediates;
    the opts-keyed ``get_engine`` cache makes those per-level variants
    construct-and-trace once, so repeating a sweep (or sharing a qchunk
    between levels) pays no engine churn. ``engine_opts`` pass through to
    every ``get_engine`` resolution (e.g. ``query_tile=`` for the fused
    backend). ``rescore`` applies the exact-rescore tail at every level, so
    a planner calibrated for rescored serving measures the curve it will
    actually serve.

    Returns one ``(scores, ids, n_scored)`` tuple per grid entry, in grid
    order.
    """
    name = pick_backend(index) if backend in (None, "auto") else backend
    grid = [int(p) for p in probe_grid]
    if not grid:
        return []
    opts = dict(engine_opts or {})
    b = int(index.buckets.shape[-1])
    d = int(index.docs.shape[-1])
    out = []
    for probes in grid:
        level_opts = opts
        if name == "reference" and "qchunk" not in opts:
            qchunk = max(
                1, min(8, _SWEEP_GATHER_BYTES // max(1, probes * b * d * 4))
            )
            level_opts = {**opts, "qchunk": int(qchunk)}
        eng = get_engine(index, name, **level_opts)
        out.append(
            eng.search(qw, probes=probes, k=k, exclude=exclude,
                       nav_query=nav_query, rescore=rescore)
        )
    return out


# Exact tier on a quantised pack: the pack proposes candidates, the fp32
# rescore tail ranks them. A depth of a few k absorbs the storage noise.
_EXACT_RESCORE_FACTOR = 4


# --------------------------------------------------------------------- shared
class _EngineBase:
    """Shared canonicalisation, probe selection and cost accounting."""

    # True for backends that score from the (possibly bf16/int8) bucket-major
    # pack rather than the fp32 doc-major corpus; the exact tier then routes
    # through the fp32 rescore tail so returned ids/scores stay exact.
    uses_packed_storage = False

    def __init__(self, index):
        self.index = index

    # Every backend reduces (query, weights) identically (paper §4 theorem).
    # Query rank passes through so a 1-D query keeps the squeezed (k,) result
    # shape, matching ClusterPruneIndex.search_weighted.
    def search_weighted(self, q, w, *, probes, k, exclude=None):
        qw = weighted_query(q, w, self.index.spec)
        return self.search(qw, probes=probes, k=k, exclude=exclude)

    def _canonical(self, qw, nav_query, exclude):
        single = qw.ndim == 1
        qw = jnp.atleast_2d(qw)
        nav = qw if nav_query is None else jnp.atleast_2d(nav_query)
        nq = qw.shape[0]
        if exclude is None:
            exclude = jnp.full((nq,), -1, jnp.int32)
        exclude = jnp.broadcast_to(
            jnp.atleast_1d(exclude), (nq,)
        ).astype(jnp.int32)
        return qw, nav, exclude, single

    @staticmethod
    def _finish(single, scores, ids, n_scored):
        if single:
            return scores[0], ids[0], n_scored[0]
        return scores, ids, n_scored

    def _total_probes(self) -> int:
        """T·K — the budget at which pruned search degenerates to exact."""
        t, k_clusters = (int(x) for x in self.index.counts.shape)
        return t * k_clusters

    def _probes_t(self, probes: int) -> tuple[int, ...]:
        # Clamp to T·K: "probe everything" is exact search, and a larger
        # budget would push top_k(lsims, p) past K into an opaque XLA error.
        t = self.index.leaders.shape[0]
        return split_probes(min(int(probes), self._total_probes()), t)

    def _flat_probes(self, nav, probes_t):
        """Navigate: (nq, P) flattened (t*K + cluster) probe list."""
        leaders = self.index.leaders                       # (T, K, D)
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

    def _n_scored(self, flat_probes):
        """Fig-1 accounting: every member of a probed bucket is one distance
        computation (dups across clusterings included — they really are
        scored), plus the T*K leader comparisons."""
        t, k_clusters = self.index.counts.shape
        counts = self.index.counts.reshape(-1)
        return (
            jnp.sum(counts[flat_probes], axis=-1).astype(jnp.int32)
            + t * k_clusters
        )

    def search_exact(self, qw, *, k, exclude=None, nav_query=None,
                     rescore=None):
        """Clustered exact top-k: sweep ALL T·K buckets best-first.

        Every live document sits in a bucket of every clustering, so a
        budget of T·K probes makes the candidate set the whole corpus and
        the pruned machinery returns the true top-k (Dimond & Sanders:
        the clustering that prunes approximate search also organises
        exact search — leaders order buckets best-first, so the fused
        path's running top-k bound tightens early). Backends scoring
        from a bf16/int8 pack (``uses_packed_storage``) are forced
        through the fp32 rescore tail at depth ``max(rescore, 4k)`` so
        returned ids/scores match :func:`brute_force_topk` exactly.
        """
        quantised = self.uses_packed_storage and (
            getattr(self.index, "pack_dtype", None) not in (None, "float32")
        )
        if quantised:
            depth = max(int(rescore or 0), _EXACT_RESCORE_FACTOR * k)
            rescore = max(k, min(depth, int(self.index.n_docs)))
        return self.search(
            qw, probes=self._total_probes(), k=k, exclude=exclude,
            nav_query=nav_query, rescore=rescore,
        )

    def search_escalating(
        self, qw, *, probes, k, min_recall, exclude=None, nav_query=None,
        rescore=None,
    ):
        """Recall-floor escalation: approximate first, exact if needed.

        Runs the planned budget; while the calibrated ladder predicts
        recall below ``min_recall``, re-runs at the next calibrated rung —
        the first one the fit says meets the floor, so one escalation
        usually suffices — and at the exact tier once the rungs are
        exhausted (immediately, when no ladder exists to predict with).
        Every tier's candidates are charged cumulatively to ``n_scored``
        — the escalation really did score them.

        Returns ``(scores, ids, n_scored, info)`` where ``info`` carries
        ``tier`` ("approx" | "escalated" | "exact"), ``escalations``,
        the final ``probes`` and its ``predicted_recall``.
        """
        if not 0.0 < float(min_recall) <= 1.0:
            raise ValueError(
                f"min_recall must be in (0, 1], got {min_recall}"
            )
        ladder = getattr(self.index, "ladder", None)
        total = self._total_probes()
        qw2, nav, excl, single = self._canonical(qw, nav_query, exclude)
        p = min(int(probes), total)
        escalations = 0
        n_total = None
        while True:
            if p >= total:
                s, i, ns = self.search_exact(
                    qw2, k=k, exclude=excl, nav_query=nav, rescore=rescore
                )
                predicted = 1.0
            else:
                s, i, ns = self.search(
                    qw2, probes=p, k=k, exclude=excl, nav_query=nav,
                    rescore=rescore,
                )
                predicted = (
                    None if ladder is None
                    else float(ladder.predicted_recall(p))
                )
            n_total = ns if n_total is None else n_total + ns
            if p >= total or (
                predicted is not None and predicted >= float(min_recall)
            ):
                break
            nxt = total
            if ladder is not None:
                # first rung strictly above the budget just run, bumped to
                # the rung the fit says meets the floor (ladder.plan) so
                # the ladder is not climbed one wasted re-run at a time
                above = next(
                    (int(r) for r in ladder.probes if int(r) > p), total
                )
                nxt = min(
                    max(above, int(ladder.plan(float(min_recall)))), total
                )
            p = nxt if nxt > p else total
            escalations += 1
        tier = (
            "exact" if p >= total
            else ("escalated" if escalations else "approx")
        )
        info = {
            "tier": tier,
            "escalations": escalations,
            "probes": int(p),
            "predicted_recall": float(predicted),
        }
        s, i, n_total = self._finish(single, s, i, n_total)
        return s, i, n_total, info

    def _search_rescored(
        self, qw, *, probes, k, rescore, exclude=None, nav_query=None
    ):
        """Exact-rescore tail shared by every backend.

        Runs the backend's own pruned search at depth ``rescore`` (>= k),
        then re-scores the surviving candidates against the fp32 doc-major
        corpus in one gather+matmul and cuts the final top-k on those exact
        scores. On an fp32 pack this is an identity on the returned
        ``(scores, ids)`` (candidates were already scored exactly); on a
        bf16/int8 pack it removes the storage-precision noise from the
        returned order. The re-scored candidates are real distance
        computations, so they are added to ``n_scored``.
        """
        rescore = int(rescore)
        if rescore < k:
            raise ValueError(
                f"rescore depth {rescore} must be >= k ({k})"
            )
        qw2, nav, exclude, single = self._canonical(qw, nav_query, exclude)
        s, ids, n_scored = self.search(
            qw2, probes=probes, k=rescore, exclude=exclude, nav_query=nav
        )
        with span(tracing.ENGINE_RESCORE):
            rs, ri, extra = self._rescore_candidates(qw2, ids, k)
            return self._finish(single, rs, ri, n_scored + extra)

    def _rescore_candidates(self, qw, ids, k):
        """Exact fp32 re-rank of candidate ids — the rescore tail's scoring
        step, overridable per backend. The default gathers from the local
        doc-major corpus; the sharded backend re-ranks against the
        row-sharded corpus without gathering it
        (:func:`repro.core.distributed.distributed_exact_rescore`)."""
        return _exact_rescore(self.index.docs, qw, ids, k)


@functools.partial(jax.jit, static_argnames=("k",))
def _exact_rescore(docs, qw, ids, k):
    """Re-score candidate ids against the fp32 corpus; exact top-k cut.

    ``ids`` may contain ``-1`` fillers (pruned search found fewer than
    ``rescore`` live candidates) — they score ``-inf`` and return as ``-1``.
    Also returns the per-query count of candidates actually re-scored, for
    honest Fig-1 accounting.
    """
    valid = ids >= 0
    safe = jnp.where(valid, ids, 0)
    cvecs = docs[safe]                                   # (nq, R, D)
    s = jnp.einsum(
        "qrd,qd->qr", cvecs, qw, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    s = jnp.where(valid, s, -jnp.inf)
    top_s, pos = jax.lax.top_k(s, k)
    top_i = jnp.take_along_axis(ids, pos, axis=-1)
    top_i = jnp.where(jnp.isfinite(top_s), top_i, -1)
    extra = jnp.sum(valid, axis=-1).astype(jnp.int32)
    return top_s, top_i, extra


# ------------------------------------------------------------------ reference
@register_backend("reference")
class ReferenceEngine(_EngineBase):
    """Pure-JAX doc-major gather path — portable oracle, single-host fast."""

    def __init__(self, index, *, qchunk: int = 8):
        super().__init__(index)
        self.qchunk = qchunk

    def search(self, qw, *, probes, k, exclude=None, nav_query=None,
               rescore=None):
        if rescore is not None:
            return self._search_rescored(
                qw, probes=probes, k=k, rescore=rescore, exclude=exclude,
                nav_query=nav_query,
            )
        index = self.index
        qw, nav, exclude, single = self._canonical(qw, nav_query, exclude)
        nq = qw.shape[0]
        probes_t = self._probes_t(probes)
        fn = functools.partial(
            _search_block, index.docs, index.leaders, index.buckets,
            probes_t=probes_t, k=k,
        )
        qchunk = self.qchunk
        pad = (-nq) % qchunk
        qp = jnp.pad(qw, ((0, pad), (0, 0)))
        np_ = jnp.pad(nav, ((0, pad), (0, 0)))
        ep = jnp.pad(exclude, (0, pad), constant_values=-1)
        scores, ids, scored = jax.lax.map(
            lambda args: fn(*args),
            (
                qp.reshape(-1, qchunk, qp.shape[-1]),
                np_.reshape(-1, qchunk, np_.shape[-1]),
                ep.reshape(-1, qchunk),
            ),
        )
        return self._finish(
            single,
            scores.reshape(-1, k)[:nq],
            ids.reshape(-1, k)[:nq],
            scored.reshape(-1)[:nq],
        )


@functools.partial(jax.jit, static_argnames=("probes_t", "k"))
def _search_block(
    docs: jnp.ndarray,     # (n, D)
    leaders: jnp.ndarray,  # (T, K, D)
    buckets: jnp.ndarray,  # (T, K, B) sentinel n
    qw: jnp.ndarray,       # (bq, D) weighted, normalised queries (scoring)
    nav: jnp.ndarray,      # (bq, D) navigation queries (= qw unless CellDec)
    exclude: jnp.ndarray,  # (bq,) doc id to mask (or -1)
    *,
    probes_t: tuple[int, ...],
    k: int,
):
    """One query block: probe -> gather buckets -> score union -> dedup top-k."""
    n = docs.shape[0]
    lsims = jnp.einsum("tkd,qd->qtk", leaders, nav,
                       precision=jax.lax.Precision.HIGHEST)  # (bq, T, K)

    cand_parts = []
    for t, p in enumerate(probes_t):
        if p == 0:
            continue
        _, top_clusters = jax.lax.top_k(lsims[:, t, :], p)   # (bq, p)
        cand_parts.append(buckets[t][top_clusters].reshape(qw.shape[0], -1))
    cand = jnp.concatenate(cand_parts, axis=-1)              # (bq, m)

    valid = cand < n
    safe = jnp.where(valid, cand, 0)
    cvecs = docs[safe]                                        # (bq, m, D)
    scores = jnp.einsum("qmd,qd->qm", cvecs, qw,
                        precision=jax.lax.Precision.HIGHEST)
    scores = jnp.where(valid, scores, -jnp.inf)
    scores = jnp.where(cand == exclude[:, None], -jnp.inf, scores)

    # Dedup across overlapping clusterings: identical doc => identical score,
    # so sorting by id and masking equal neighbours keeps exactly one copy.
    order = jnp.argsort(cand, axis=-1)
    c_sorted = jnp.take_along_axis(cand, order, axis=-1)
    s_sorted = jnp.take_along_axis(scores, order, axis=-1)
    dup = c_sorted == jnp.pad(c_sorted[:, :-1], ((0, 0), (1, 0)), constant_values=-1)
    s_sorted = jnp.where(dup, -jnp.inf, s_sorted)

    top_s, pos = jax.lax.top_k(s_sorted, k)
    top_ids = jnp.take_along_axis(c_sorted, pos, axis=-1)
    top_ids = jnp.where(jnp.isfinite(top_s), top_ids, -1)

    # Cost accounting (paper Fig 1): every valid candidate is one distance
    # computation (dups included — they really are scored), plus all leaders.
    n_scored = jnp.sum(valid, axis=-1) + leaders.shape[0] * leaders.shape[1]
    return top_s, top_ids, n_scored


# ---------------------------------------------------------------------- fused
@register_backend("fused")
class FusedEngine(_EngineBase):
    """Query-tiled Pallas ``bucket_score`` v2 over the bucket-major corpus.

    Queries are grouped into tiles of ``query_tile`` (default: sized from
    the kernel's VMEM budget by
    :func:`repro.kernels.bucket_score.ops.pick_query_tile`); for each tile
    the engine builds a **probe-dedup schedule** — the union of the tile's
    flat probe lists with every shared bucket appearing once
    (:func:`~repro.kernels.bucket_score.ops.build_probe_schedule`) — and the
    kernel scores each DMA'd bucket block against the whole tile as one
    ``(QT, D)×(D, B)`` MXU matmul with per-query membership masking. A
    bucket probed by many queries of a tile is read from HBM once per tile
    instead of once per query, so batched throughput scales with the MXU
    rather than with redundant block reads; ragged batch tails are padded
    to the tile and sliced off. The in-kernel running top-k suppresses
    duplicates across overlapping clusterings exactly like the reference
    path, and the bucket-major tensor may be stored bf16 or int8
    (``ClusterPruneIndex`` ``pack_dtype``) with fp32 accumulation — the
    int8 pack's per-bucket ``bucket_scales`` ride along and dequantise each
    score block inside the kernel.

    The schedule is built ON DEVICE
    (:func:`~repro.kernels.bucket_score.ops.build_probe_schedule_device`):
    a jitted segmented dedup over a *bucketed static* schedule length
    ``S = pow2ceil(min(QT·P, n_buckets))``
    (:func:`~repro.kernels.bucket_score.ops.schedule_length`), so the hot
    path never synchronises the probe tensor HBM→host→HBM. Padded schedule
    slots all target bucket 0 with zero membership and follow the live
    ones; the kernel skips their grid steps (no DMA, no matmul, no merge),
    so the static upper bound costs only a predicate a step. The
    ``repro.engine.score`` span carries ``slots``, the grid steps launched
    (``n_tiles · S``), beside which the dispatch's live buckets give the
    share skipped. Runs interpreted off-TPU (tests only).
    """

    uses_packed_storage = True

    def __init__(
        self,
        index,
        *,
        interpret: bool | None = None,
        query_tile: int | None = None,
    ):
        super().__init__(index)
        self.interpret = interpret
        self.query_tile = query_tile

    def search(self, qw, *, probes, k, exclude=None, nav_query=None,
               rescore=None):
        if rescore is not None:
            return self._search_rescored(
                qw, probes=probes, k=k, rescore=rescore, exclude=exclude,
                nav_query=nav_query,
            )
        from ..kernels.bucket_score import bucket_score_tiled
        from ..kernels.bucket_score.ops import (
            build_probe_schedule_device, pick_query_tile, schedule_length,
        )
        from ..kernels.common import pad_to

        qw, nav, exclude, single = self._canonical(qw, nav_query, exclude)
        # (T, K, B, D), (T*K, B), (T*K,) | None
        data, ids, scales = self.index.ensure_bucket_major()
        with span(tracing.ENGINE_NAVIGATE):
            flat = self._flat_probes(nav, self._probes_t(probes))
        with span(tracing.ENGINE_SCHEDULE):
            n_buckets, b = (int(x) for x in ids.shape)
            d = int(data.shape[-1])
            qt = self.query_tile
            if qt is None:
                # VMEM budget caps the tile (a reduced-precision pack
                # shrinks the bucket block and buys a larger tile); the
                # batch floors it — a small batch padded to a large tile
                # would matmul and top-k mostly dead rows per scheduled
                # bucket.
                qt = min(
                    pick_query_tile(
                        d, b, k_pad=pad_to(k, 8),
                        pack_itemsize=data.dtype.itemsize,
                    ),
                    pad_to(qw.shape[0], 8),
                )
            # Jitted dedup with bucketed static S — no host numpy round-trip.
            s_len = schedule_length(qt, int(flat.shape[1]), n_buckets)
            sched, member = build_probe_schedule_device(
                flat, query_tile=qt, s_len=s_len
            )
        with span(tracing.ENGINE_SCORE, slots=sched.size):
            s, i = bucket_score_tiled(
                qw, data, ids, sched, member,
                k=k, exclude=exclude, scales=scales, interpret=self.interpret,
            )
            i = jnp.where(jnp.isfinite(s), i, -1)
            return self._finish(single, s, i, self._n_scored(flat))


# -------------------------------------------------------------------- sharded
@register_backend("sharded")
class ShardedEngine(_EngineBase):
    """Sharded-fused backend: the fused v2 hot path run shard-locally.

    Each device of the mesh holds a bucket-major ``(T*K, B_local, D)`` pack
    of ITS row-slice of every cluster (``ClusterPruneIndex.
    ensure_local_bucket_major`` — ``pack_dtype`` bf16 halves, int8 quarters
    the per-shard HBM bytes via per-``(shard, bucket)`` scales). A search
    navigates ONCE on the replicated fp32 leaders, builds the probe-dedup
    schedule ONCE on device (probed buckets are identical across shards, so
    schedule and membership masks replicate), then every shard runs
    :func:`~repro.kernels.bucket_score.ops.bucket_score_tiled` over its
    local blocks — the same ``(QT, D)×(D, B_l)`` MXU tiles as the
    single-device fused path, on a smaller ``B_l`` block (which buys a
    LARGER query tile out of the same VMEM budget). The only collective is
    the 2k-word per-shard top-k ``all_gather`` + merge; the same flat probe
    tensor drives ``n_scored`` accounting, so navigation never runs twice.

    Any corpus size shards cleanly: rows pad to ``ceil(n / shards)`` per
    shard with sentinel rows no bucket references (never scored, never in
    ``n_scored``). Mutations invalidate lazily — the pack re-materialises
    on the first search after an ``index.version`` bump. The exact-rescore
    tail (and with it the quantised exact tier) re-ranks candidates
    against the row-sharded fp32 corpus via a ``pmax`` all-reduce
    (:func:`~repro.core.distributed.distributed_exact_rescore`) — the
    corpus is never gathered onto one device.
    """

    uses_packed_storage = True

    def __init__(
        self,
        index,
        *,
        mesh=None,
        shard_axes=None,
        interpret: bool | None = None,
        query_tile: int | None = None,
    ):
        super().__init__(index)
        if mesh is None:
            mesh = jax.make_mesh((jax.device_count(),), ("data",))
            shard_axes = ("data",)
        self.mesh = mesh
        self.shard_axes = tuple(
            shard_axes if shard_axes is not None else mesh.axis_names
        )
        n_shards = 1
        for a in self.shard_axes:
            n_shards *= mesh.shape[a]
        self.n_shards = n_shards
        self.interpret = interpret
        self.query_tile = query_tile
        self._pack_version = None   # index.version the placed pack reflects

    def _ensure_placed(self):
        """Device-resident shard-local state, repacked lazily on mutation.

        Returns ``(data, ids, scales, n_local)`` placed shard-major on the
        mesh plus the row-sharded fp32 corpus for the rescore tail. Keyed
        on ``index.version``: the first search after an add/remove pays the
        repack + placement once, steady-state searches touch nothing.
        """
        if self._pack_version == self.index.version:
            return self._placed
        from jax.sharding import NamedSharding, PartitionSpec as P

        from .distributed import shard_docs

        mesh, axes = self.mesh, self.shard_axes
        sh = lambda *spec: NamedSharding(mesh, P(*spec))
        # each device builds its own slice: the pack never sits whole on one
        # device (the puts below are no-ops for a pack placed that way)
        data, ids, scales, n_local = self.index.ensure_local_bucket_major(
            self.n_shards, sharding=sh(axes, None, None, None)
        )
        data = jax.device_put(data, sh(axes, None, None, None))
        ids = jax.device_put(ids, sh(axes, None, None))
        if scales is not None:
            scales = jax.device_put(scales, sh(axes, None))
        self._docs_sh = shard_docs(self.index.docs, mesh, axes)
        self._n_local = n_local
        self._placed = (data, ids, scales, n_local)
        self._pack_version = self.index.version
        return self._placed

    def _rescore_candidates(self, qw, ids, k):
        # fp32 re-rank against the row-sharded corpus: each shard scores
        # the candidates it owns, one pmax all-reduce merges — nq·R words
        # of communication, corpus never gathered.
        from .distributed import distributed_exact_rescore

        self._ensure_placed()
        return distributed_exact_rescore(
            self.mesh, self._docs_sh, qw, ids,
            k=k, n_local=self._n_local, shard_axes=self.shard_axes,
        )

    def search(self, qw, *, probes, k, exclude=None, nav_query=None,
               rescore=None):
        if rescore is not None:
            return self._search_rescored(
                qw, probes=probes, k=k, rescore=rescore, exclude=exclude,
                nav_query=nav_query,
            )
        from ..kernels.bucket_score.ops import (
            build_probe_schedule_device, pick_query_tile, schedule_length,
        )
        from ..kernels.common import pad_to
        from .distributed import distributed_bucket_score

        qw, nav, exclude, single = self._canonical(qw, nav_query, exclude)
        data, ids, scales, n_local = self._ensure_placed()
        # Navigate ONCE: the flat probe tensor feeds the (replicated)
        # schedule AND the n_scored accounting below.
        with span(tracing.ENGINE_NAVIGATE):
            flat = self._flat_probes(nav, self._probes_t(probes))
        with span(tracing.ENGINE_SCHEDULE):
            _, n_buckets, b_l, d = (int(x) for x in data.shape)
            qt = self.query_tile
            if qt is None:
                qt = min(
                    pick_query_tile(
                        d, b_l, k_pad=pad_to(k, 8),
                        pack_itemsize=data.dtype.itemsize,
                    ),
                    pad_to(qw.shape[0], 8),
                )
            s_len = schedule_length(qt, int(flat.shape[1]), n_buckets)
            sched, member = build_probe_schedule_device(
                flat, query_tile=qt, s_len=s_len
            )
        with span(tracing.ENGINE_SCORE, shards=self.n_shards,
                  slots=sched.size):
            s, i = distributed_bucket_score(
                self.mesh, data, ids, scales, qw, sched, member,
                k=k, n_local=n_local, shard_axes=self.shard_axes,
                exclude=exclude, interpret=self.interpret,
            )
            if s.shape[-1] < k:   # shards × schedule can't surface k
                pad = k - s.shape[-1]
                s = jnp.pad(s, ((0, 0), (0, pad)), constant_values=-jnp.inf)
                i = jnp.pad(i, ((0, 0), (0, pad)), constant_values=-1)
            # The merge leaves the answer replicated over the mesh. Hand it
            # on from the corpus's device: the field decomposition that
            # reads it next would otherwise copy the corpus to every chip.
            docs = self.index.docs
            if isinstance(docs, jax.Array) and len(docs.devices()) == 1:
                s, i = jax.device_put((s, i), docs.sharding)
            i = jnp.where(jnp.isfinite(s), i, -1)
            return self._finish(single, s, i, self._n_scored(flat))
