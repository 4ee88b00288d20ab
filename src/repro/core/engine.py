"""The search engines: one seam, two backends.

Both run the paper's hot path: probe the T clusterings, score the probed
buckets, and merge a deduplicated top-k.

``reference``
    Pure-JAX doc-major gather (:func:`_search_block`), the semantics
    oracle.
``fused``
    The query-tiled Pallas ``bucket_score`` kernel over a mesh of S >= 1
    devices. Each device holds a bucket-major ``(T*K, B_l, D)`` pack of its
    row slice of every bucket, in the index's ``pack_dtype``. A search is
    one device program: navigation and the probe-dedup schedule run once,
    replicated, each shard scores its blocks, and one ``all_gather`` of the
    per-shard top-k merges the answer. At S=1 the pack is the whole
    corpus's and the merge is over one shard.

Both backends share the probe semantics (:func:`split_probes` divides the
budget evenly over the T clusterings), the navigation-vs-scoring query
split, duplicate suppression across overlapping clusterings, ``exclude``
masking, and the paper's Fig-1 ``n_scored`` accounting.

Both also share the opt-in **exact-rescore tail**
(``search(..., rescore=R)``, ``R >= k``): the pruned search runs at depth
``R``, the candidates are re-scored against the fp32 corpus, and the final
top-k is cut on those exact scores. The re-scored candidates are charged to
``n_scored``.

On top of the budgeted path every backend exposes the **tiered exact
path** (``search_exact``): probe all T·K buckets, so every live document is
a candidate and the result is the true top-k. Backends that score from a
reduced-precision pack (``uses_packed_storage``) finish the exact tier
through the fp32 rescore tail. The **escalation driver**
(``search_escalating``) makes a calibrated recall floor a guarantee: run the
planned budget, and while the ladder's ``predicted_recall`` sits below the
floor, re-run at the next calibrated rung, ultimately the exact tier,
charging every tier's candidates to ``n_scored``.

Select a backend by name or let :func:`pick_backend` choose it and the
shard count from the platform and the index's size::

    engine = get_engine(index, "auto")
    scores, ids, n_scored = engine.search(qw, probes=12, k=10)

Adding a backend = subclass :class:`_EngineBase`, implement ``search``, and
decorate with ``@register_backend("name")``.
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
    "resolve_backend",
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


# Share of one device's memory the kernel path's serving state (the
# bucket-major pack at its dtype, plus the fp32 corpus) may take on one
# device. The rest holds the kernel's working set, the probe schedule and
# XLA's temporaries; an index above it is sharded where the host has several
# devices.
_FUSED_MEMORY_SHARE = 0.75

# The name ``bench/`` still gives the kernel path over all of the host's
# devices. ``get_engine`` resolves it to ``fused`` on that mesh.
_ALL_DEVICES_ALIAS = "sharded"


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


def _pick(index) -> tuple[str, int, int, int, int]:
    """``(backend, shards, pack bytes, byte limit, devices)``: see
    :func:`pick_backend`."""
    platform, n_devices, limit = _host()
    pack = state = 0
    if index is not None:
        docs = index.docs
        pack = pack_bytes(index)
        state = pack + math.prod(docs.shape) * docs.dtype.itemsize
    if platform != "tpu":
        name = "fused" if n_devices > 1 else "reference"
        shards = n_devices
    else:
        name = "fused"
        fits = not limit or state <= _FUSED_MEMORY_SHARE * limit
        shards = 1 if fits else n_devices
    return name, shards, pack, limit, n_devices


def pick_backend(index=None) -> tuple[str, int]:
    """The backend ``"auto"`` resolves to and the shard count of its mesh,
    by platform and by size.

    On a TPU: ``fused`` on one device while ``index``'s serving state (its
    bucket-major pack at ``pack_dtype`` plus the fp32 corpus) fits
    :data:`_FUSED_MEMORY_SHARE` of the device's byte limit, else over all of
    the host's devices. Elsewhere: ``fused`` over all devices on a
    multi-device host, else ``reference``. Without an index, or where the
    device reports no limit, the state counts as fitting. Leaves a
    :data:`~repro.tracing.ENGINE_PICK` marker with the backend, the shard
    count, the pack's bytes, the device's byte limit and the device count.
    """
    name, shards, pack, limit, n_devices = _pick(index)
    with span(tracing.ENGINE_PICK, backend=name, shards=shards,
              pack_bytes=pack, bytes_limit=limit, devices=n_devices):
        pass
    return name, shards


def resolve_backend(index, backend: str | None) -> str:
    """``backend`` as named, or the pick's where it is None or ``"auto"``."""
    return pick_backend(index)[0] if backend in (None, "auto") else backend


def get_engine(index, backend: str = "auto", **opts) -> SearchEngine:
    """Engine for ``index``, cached on the index keyed by ``(name, opts)``.

    Keying the per-index cache by the opts (not just the backend name)
    means variant engines — a sweep's per-level ``qchunk``, an explicit
    ``query_tile``, ``interpret`` or ``mesh`` — are constructed and traced
    once and then reused. Unhashable opts fall back to an uncached
    construction.
    """
    name = resolve_backend(index, backend)
    if name == _ALL_DEVICES_ALIAS:
        name = "fused"
        opts = {"mesh": jax.make_mesh((jax.device_count(),), ("data",)),
                **opts}
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

    The engine (and with it the bucket-major pack and every per-index
    cache) is resolved once and reused across all probe levels, so
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
    name = resolve_backend(index, backend)
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
def _canonical(qw, nav_query, exclude):
    """``(qw (nq, D), nav (nq, D), exclude (nq,) int32, single)``: a 1-D
    query as a batch of one, navigation on ``qw`` unless ``nav_query`` is
    given, and ``exclude`` (None, a scalar or ``(nq,)``) with ``-1`` for
    "none". Pure, so it runs eagerly or inside a trace."""
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


def _finish(single, scores, ids, n_scored):
    if single:
        return scores[0], ids[0], n_scored[0]
    return scores, ids, n_scored


def _n_scored(counts, flat_probes):
    """Fig-1 accounting: every member of a probed bucket is one distance
    computation (dups across clusterings included — they really are
    scored), plus the T*K leader comparisons. ``counts`` is the index's
    ``(T·K,)`` bucket sizes, flattened as the probes are."""
    return (
        jnp.sum(counts[flat_probes], axis=-1).astype(jnp.int32)
        + counts.shape[0]
    )


class _EngineBase:
    """Shared probe selection, the exact tier, escalation and the rescore
    tail."""

    # True for backends that score from the (possibly bf16/int8) bucket-major
    # pack rather than the fp32 doc-major corpus; the exact tier then routes
    # through the fp32 rescore tail so returned ids/scores stay exact.
    uses_packed_storage = False

    def __init__(self, index):
        self.index = index

    def tile_for(self, k: int = 10) -> int | None:
        """The query tile a batch of top-``k`` searches is cut into, or None
        for a backend that does not tile."""
        return None

    # Every backend reduces (query, weights) identically (paper §4 theorem).
    # Query rank passes through so a 1-D query keeps the squeezed (k,) result
    # shape, matching ClusterPruneIndex.search_weighted.
    def search_weighted(self, q, w, *, probes, k, exclude=None):
        qw = weighted_query(q, w, self.index.spec)
        return self.search(qw, probes=probes, k=k, exclude=exclude)

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
        from .distributed import _navigate

        return _navigate(self.index.leaders, nav, probes_t)

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
        qw2, nav, excl, single = _canonical(qw, nav_query, exclude)
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
        s, i, n_total = _finish(single, s, i, n_total)
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
        qw2, nav, exclude, single = _canonical(qw, nav_query, exclude)
        s, ids, n_scored = self.search(
            qw2, probes=probes, k=rescore, exclude=exclude, nav_query=nav
        )
        with span(tracing.ENGINE_RESCORE):
            rs, ri, extra = self._rescore_candidates(qw2, ids, k)
            return _finish(single, rs, ri, n_scored + extra)

    def _rescore_candidates(self, qw, ids, k):
        """Exact fp32 re-rank of candidate ids — the rescore tail's scoring
        step, overridable per backend. The default gathers from the local
        doc-major corpus; ``fused`` re-ranks against the row-sharded corpus
        without gathering it
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
        qw, nav, exclude, single = _canonical(qw, nav_query, exclude)
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
        return _finish(
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
    """The Pallas ``bucket_score`` kernel over a mesh of S >= 1 devices.

    Each device holds a bucket-major ``(T*K, B_l, D)`` pack of its row slice
    of every bucket (:meth:`ClusterPruneIndex.ensure_local_bucket_major`),
    in ``pack_dtype`` precision with per-``(shard, bucket)`` int8 scales. A
    search is one jitted program (:func:`_fused_search_fn`). It navigates
    once on the fp32 leaders, replicated on the mesh, and builds the
    probe-dedup schedule
    (:func:`~repro.kernels.bucket_score.ops.build_probe_schedule_device`):
    the union of a query tile's flat probe lists, each shared bucket once.
    Probed buckets are the same on every shard, so schedule and membership
    masks replicate. Every shard then runs
    :func:`~repro.kernels.bucket_score.ops.bucket_score_tiled` over its
    blocks, one ``(QT, D)×(D, B_l)`` MXU matmul a scheduled bucket, and one
    ``all_gather`` of the per-shard top-k merges the answer
    (:func:`~repro.core.distributed._bucket_score_fn`). The same flat probe
    tensor drives ``n_scored``.

    The mesh is ``mesh``/``shard_axes`` where given, else the pick's
    (:func:`pick_backend`): one device while the index fits it, all of the
    host's devices where it does not. At S=1 the pack is the whole corpus's
    and local ids are global ids.

    The schedule's static length ``S = pow2ceil(min(QT·P, n_buckets))``
    (:func:`~repro.kernels.bucket_score.ops.schedule_length`) pads each tile
    with slots no query probes; the kernel skips their grid steps. The
    ``repro.engine.score`` span, around the program's launch, carries
    ``slots`` (``n_tiles · S``) and ``shards``.

    Any corpus size shards: rows pad to ``ceil(n / shards)`` per shard with
    sentinel rows no bucket references. Mutations repack lazily on the first
    search after an ``index.version`` bump. The exact-rescore tail re-ranks
    candidates against the row-sharded fp32 corpus with a ``pmax``
    all-reduce (:func:`~repro.core.distributed.distributed_exact_rescore`),
    built the first time a rescore needs it. Runs interpreted off-TPU.
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
            shards = _pick(index)[1]
            mesh = jax.make_mesh((shards,), ("data",),
                                 devices=jax.devices()[:shards])
            shard_axes = ("data",)
        self.mesh = mesh
        self.shard_axes = tuple(
            shard_axes if shard_axes is not None else mesh.axis_names
        )
        self.n_shards = math.prod(mesh.shape[a] for a in self.shard_axes)
        self.interpret = interpret
        self.query_tile = query_tile
        self._pack_version = None   # index.version the placed pack reflects
        self._docs_sh = None        # row-sharded corpus, for the rescore tail

    def _ensure_placed(self):
        """The shard-local pack ``(data, ids, scales, n_local)`` placed
        shard-major on the mesh, and beside it ``self._navigation``: the
        fp32 ``leaders (T, K, D)`` and the flat bucket ``counts (T·K,)``
        replicated on it. All are made again lazily on mutation: keyed on
        ``index.version``, so the first search after an add/remove pays the
        repack once and steady-state searches move nothing. fp32 and bf16
        packs carry unit scales."""
        if self._pack_version == self.index.version:
            return self._placed
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = lambda *spec: NamedSharding(self.mesh, P(*spec))
        axes = self.shard_axes
        # each device builds its own slice: the pack never sits whole on one
        # device (the puts below are no-ops for a pack placed that way)
        data, ids, scales, n_local = self.index.ensure_local_bucket_major(
            self.n_shards, sharding=sh(axes, None, None, None)
        )
        data = jax.device_put(data, sh(axes, None, None, None))
        ids = jax.device_put(ids, sh(axes, None, None))
        if scales is None:
            scales = jnp.ones(ids.shape[:2], jnp.float32)
        scales = jax.device_put(scales, sh(axes, None))
        self._navigation = jax.device_put(
            (self.index.leaders, self.index.counts.reshape(-1)), sh()
        )
        self._placed = (data, ids, scales, n_local)
        self._docs_sh = None
        self._pack_version = self.index.version
        return self._placed

    def tile_for(self, k: int = 10) -> int:
        """The query tile for top-``k``: ``query_tile`` where given, else
        the largest the kernel's VMEM budget allows for this engine's bucket
        block. The block is the placed pack's, or before packing ``B`` split
        over the shards (``ceil(B / S)`` rows, sublane-padded) in
        ``pack_dtype``."""
        if self.query_tile:
            return int(self.query_tile)
        from ..kernels.bucket_score.ops import pick_query_tile
        from ..kernels.common import pad_to

        if self._pack_version == self.index.version:
            data = self._placed[0]
            b, d, itemsize = (int(data.shape[2]), int(data.shape[3]),
                              data.dtype.itemsize)
        else:
            rows = -(-int(self.index.buckets.shape[-1]) // self.n_shards)
            b = max(8, pad_to(rows, 8))
            d = int(self.index.docs.shape[-1])
            itemsize = jnp.dtype(
                self.index.pack_dtype or self.index.docs.dtype
            ).itemsize
        return pick_query_tile(d, b, k_pad=pad_to(k, 8),
                               pack_itemsize=itemsize)

    def _rescore_candidates(self, qw, ids, k):
        # fp32 re-rank against the row-sharded corpus: each shard scores
        # the candidates it owns, one pmax all-reduce merges — nq·R words
        # of communication, corpus never gathered.
        from .distributed import distributed_exact_rescore, shard_docs

        n_local = self._ensure_placed()[3]
        if self._docs_sh is None:
            self._docs_sh = shard_docs(self.index.docs, self.mesh,
                                       self.shard_axes)
        return distributed_exact_rescore(
            self.mesh, self._docs_sh, qw, ids,
            k=k, n_local=n_local, shard_axes=self.shard_axes,
        )

    def search(self, qw, *, probes, k, exclude=None, nav_query=None,
               rescore=None):
        if rescore is not None:
            return self._search_rescored(
                qw, probes=probes, k=k, rescore=rescore, exclude=exclude,
                nav_query=nav_query,
            )
        from ..kernels.bucket_score.ops import schedule_length
        from ..kernels.common import pad_to
        from .distributed import _bucket_scorer

        data, ids, scales, n_local = self._ensure_placed()
        leaders, counts = self._navigation
        with span(tracing.ENGINE_SCORE, shards=self.n_shards) as score_span:
            probes_t = self._probes_t(probes)
            nq = 1 if qw.ndim == 1 else int(qw.shape[0])
            qt = self.tile_for(k)
            if not self.query_tile:
                # a small batch padded to a large tile would matmul and
                # top-k mostly dead rows per scheduled bucket
                qt = min(qt, pad_to(nq, 8))
            s_len = schedule_length(qt, sum(probes_t), int(ids.shape[1]))
            score_span.set_metadata(slots=max(1, -(-nq // qt)) * s_len)
            scorer = _bucket_scorer(
                self.mesh, self.shard_axes, data.shape, s_len, k=k,
                n_local=n_local, interpret=self.interpret,
            )
            program = _fused_search_fn(scorer, probes_t, k, qt, s_len)
            s, i, n_scored = program(data, ids, scales, leaders, counts, qw,
                                     nav_query, exclude)
            # The merge leaves the answer replicated over the mesh. Hand it
            # on from the corpus's device: the field decomposition that
            # reads it next would otherwise copy the corpus to every chip.
            docs = self.index.docs
            if isinstance(docs, jax.Array) and len(docs.devices()) == 1:
                s, i = jax.device_put((s, i), docs.sharding)
            return s, i, n_scored


@functools.lru_cache(maxsize=128)
def _fused_search_fn(scorer, probes_t, k, query_tile, s_len):
    """Build (once per static config) the fused engine's whole search, the
    program ``jit_fused_search``: canonicalise the exclusions, navigate on
    the replicated fp32 leaders, build the probe-dedup schedule, run
    ``scorer`` (the cached ``shard_score_merge`` of
    :func:`~repro.core.distributed._bucket_score_fn`: each shard's kernel,
    the all-gather and the merge), pad the answer to ``k``, and count
    ``n_scored`` from the same flat probe list.

    One program, so a search launches once: the navigation's and the
    schedule's small steps run on the device inside it instead of as eager
    dispatches from the host. The key holds the scorer itself (mesh, axes,
    ``k_out``, ``n_local``, ``interpret``), so a new scorer is a new
    program; ``jit`` keys the rest on the argument shapes.
    """
    from ..kernels.bucket_score.ops import build_probe_schedule_device
    from .distributed import _navigate

    def fused_search(data, ids, scales, leaders, counts, qw, nav_query,
                     exclude):
        qw, nav, exclude, single = _canonical(qw, nav_query, exclude)
        flat = _navigate(leaders, nav, probes_t)
        sched, member = build_probe_schedule_device(
            flat, query_tile=query_tile, s_len=s_len
        )
        s, i = scorer(data, ids, scales, qw, sched, member, exclude)
        if s.shape[-1] < k:   # shards × schedule can't surface k
            pad = k - s.shape[-1]
            s = jnp.pad(s, ((0, 0), (0, pad)), constant_values=-jnp.inf)
            i = jnp.pad(i, ((0, 0), (0, pad)), constant_values=-1)
        i = jnp.where(jnp.isfinite(s), i, -1)
        return _finish(single, s, i, _n_scored(counts, flat))

    return jax.jit(fused_search)
