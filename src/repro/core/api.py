"""Typed retrieval API — the user-facing contract of the paper's system.

The paper's query model is *dynamic, user-defined* similarity: a query is "a
simple sequence of keywords or the identifier of a full document", and the
per-field weights are chosen at query time, not index time. The engine layer
(:mod:`repro.core.engine`) deliberately speaks pre-weighted arrays and raw
``(scores, ids, n_scored)`` tuples — the right currency for kernels, the
wrong one for users. This module is the seam between the two:

:class:`SearchRequest`
    A frozen description of ONE query: either a ``query`` vector (the
    keyword-embedding form — concatenated ``(D,)`` or per-field blocks) or
    ``like=doc_id`` (more-like-this, resolved against the index corpus),
    weights given **by field name** and validated against the corpus
    :class:`~repro.core.fields.FieldSpec`, plus ``k``, an explicit ``probes``
    budget *or* a ``recall_target`` that :func:`plan_probes` maps to one,
    an ``exclude`` id, and an optional ``backend`` override.

:class:`SearchResponse` / :class:`Hit`
    The answer: ranked :class:`Hit` objects carrying the doc id, the
    aggregate score, and the **per-field score decomposition** (the split of
    ``qw·p`` over ``spec.slices()`` — cheap, exact, and it explains *why* a
    document matched under these weights), plus batch stats — ``n_scored``
    distance-computation accounting, wall latency of the engine call, the
    backend that served, and the realised probe budget.

:class:`Retriever`
    The facade that owns index + engine lifecycle. ``Retriever.build(...)``
    constructs the :class:`~repro.core.index.ClusterPruneIndex`;
    ``retriever.search(request | [requests])`` resolves doc-id vs. vector
    queries, validates weights, plans probes, **batches heterogeneous
    requests** that share an execution shape ``(backend, probes, k,
    rescore, tier, min_recall)`` into one engine call each, and decomposes
    scores on the way out.
    ``retriever.add(docs)`` / ``retriever.remove(ids)`` mutate the index
    in place (incremental bucket maintenance, no rebuild) and invalidate
    every retriever-level cache.

    The facade memoises complete responses for byte-identical repeat
    more-like-this requests. The cache keys off ``index.version``, so a
    mutation — through this facade or directly on the index — flushes it,
    and so does a ladder refit (planned budgets change). Every miss of a
    batch resolves its weighted query in one step: an all-``like=`` batch
    is one program over the whole batch (:func:`_mlt_weighted_query`).

The raw tuple surface survives only inside :mod:`repro.core.engine`; every
consumer above it (serving driver, examples, benchmarks) speaks requests and
responses. Future batching and async serving extend this layer — an engine
never needs to know.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import time
import warnings
from typing import Callable, Iterable, Mapping, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import tracing
from ..tracing import span
from .fields import FieldSpec, normalize_fields
from .index import ClusterPruneIndex
from .weights import validate_weights, weighted_query

__all__ = [
    "SearchRequest",
    "Hit",
    "SearchResponse",
    "Retriever",
    "ExecShape",
    "exec_shape",
    "plan_probes",
    "decompose_scores",
]


# ---------------------------------------------------------------- the planner
# STATIC FALLBACK ladder: recall_target -> fraction of the T*K clusters to
# probe, calibrated ONCE on the synthetic Citeseer-like corpus at the Table-2
# operating points (quick scale, FPF x3). The recall-vs-probes curve depends
# on the clustering and the weight draw (PODS'07), so this constant is only
# honest on corpora resembling that one — a Retriever consults the index's
# fitted per-index ProbeLadder (repro.core.calibrate) first and warns when it
# has to fall back here. Targets above the last rung mean "probe everything"
# = exact search.
_RECALL_LADDER: tuple[tuple[float, float], ...] = (
    (0.50, 0.04),
    (0.80, 0.10),
    (0.90, 0.20),
    (0.95, 0.35),
    (0.99, 0.60),
)


def plan_probes(
    recall_target: float, n_clusterings: int, k_clusters: int
) -> int:
    """Map a recall target in (0, 1] to a total probe budget (STATIC ladder).

    Monotone in the target, clamped to ``[n_clusterings, n_clusterings *
    k_clusters]`` (at least one probe per clustering; at most all clusters,
    which degenerates to exact search). This is the uncalibrated fallback —
    an index carrying a fitted :class:`~repro.core.calibrate.ProbeLadder`
    plans from measured recall on its own data instead.
    """
    if not 0.0 < recall_target <= 1.0:
        raise ValueError(
            f"recall_target must be in (0, 1], got {recall_target}"
        )
    total = n_clusterings * k_clusters
    frac = 1.0
    for target, f in _RECALL_LADDER:
        if recall_target <= target:
            frac = f
            break
    probes = math.ceil(frac * total)
    return max(n_clusterings, min(total, probes))


# ------------------------------------------------------------ execution shape
class ExecShape(NamedTuple):
    """The grouping key for batchable requests — ONE engine call per shape.

    Two requests can ride the same engine call exactly when they agree on
    the serving backend, the realised probe budget, ``k``, the rescore
    depth AND the retrieval tier (the engine's batch dimension covers
    everything else: query vector, weights, exclude id). This is the
    single definition of that contract — :meth:`Retriever._search_batch`
    groups a synchronous batch by it and the async serving tier
    (:mod:`repro.serving`) keys its micro-batching queues by it, so the
    two paths can never drift.

    ``tier`` is ``"approx"`` (the plain budgeted pass — including
    ``min_recall=`` requests whose planned budget already predicts at or
    above the floor, so they batch with unconstrained requests),
    ``"exact"`` (all T·K buckets swept; ``probes`` is pinned to T·K), or
    ``"escalate"`` (the prediction fell below the floor: the escalation
    driver runs, and ``min_recall`` carries the floor so only requests
    with the same floor share the engine call).
    """

    backend: str
    probes: int
    k: int
    rescore: int | None
    tier: str = "approx"
    min_recall: float | None = None


def exec_shape(
    req: SearchRequest,
    *,
    default_backend: str,
    default_probes: int,
    plan_target: Callable[[float], int] | None = None,
    total_probes: int | None = None,
    predict_recall: Callable[[int], float | None] | None = None,
    index: ClusterPruneIndex | None = None,
) -> ExecShape:
    """Resolve one request to its :class:`ExecShape` grouping key.

    ``default_backend`` / ``default_probes`` fill in what the request leaves
    unspecified (a retriever passes its own configuration). A
    ``recall_target=`` request needs a planner to realise the budget —
    ``plan_target`` maps the target to a probe count (a retriever passes its
    calibrated/cached :meth:`Retriever._plan_target`); without one such a
    request cannot be shaped and raises, rather than silently guessing a
    budget the serving engine would then not use.

    ``"auto"`` (whether the request's or the default) resolves HERE to the
    concrete backend name, so auto requests share a group with
    default-backend requests instead of batching separately under the
    literal string (which would also bypass the retriever's
    ``engine_opts`` and cache a duplicate engine). The pick is by platform
    and by ``index``'s size (:func:`~repro.core.engine.pick_backend`).

    ``total_probes`` (= T·K; a retriever passes its index's) clamps
    explicit budgets to the "probe everything = exact search" ceiling and
    anchors the tier resolution: ``exact=True`` pins ``probes`` to it, and
    a ``min_recall=`` request consults ``predict_recall`` — prediction at
    or above the floor batches as plain ``"approx"``, below it the shape
    carries the ``"escalate"`` tier and the floor, and with no predictor
    at all (no calibrated ladder) only the exact tier can state the
    guarantee, so the request resolves there.
    """
    backend = req.backend or default_backend
    if backend == "auto":
        backend = default_backend
    if backend in (None, "auto"):
        from .engine import pick_backend

        backend = pick_backend(index)
    if req.exact:
        if total_probes is None:
            raise ValueError(
                "request carries exact=True but total_probes= (T*K) was not "
                "given; resolve shapes through Retriever.exec_shape (or pass "
                "total_probes=) so the exact tier pins the full sweep budget"
            )
        return ExecShape(
            backend, int(total_probes), req.k, req.rescore, "exact", None
        )
    if req.probes is not None:
        probes = int(req.probes)
    elif req.recall_target is not None:
        if plan_target is None:
            raise ValueError(
                "request carries recall_target= but no plan_target planner "
                "was given; resolve shapes through Retriever.exec_shape (or "
                "pass plan_target=) so planned budgets match serving"
            )
        probes = int(plan_target(req.recall_target))
    else:
        probes = int(default_probes)
    if total_probes is not None:
        probes = min(probes, int(total_probes))
    if req.min_recall is not None:
        predicted = (
            predict_recall(probes) if predict_recall is not None else None
        )
        if predicted is None:
            # no ladder to predict with: only the exact tier can promise
            # the floor, so that is where the request goes
            if total_probes is None:
                raise ValueError(
                    "request carries min_recall= but no predict_recall "
                    "predictor or total_probes= fallback was given; resolve "
                    "shapes through Retriever.exec_shape so the floor can "
                    "be guaranteed"
                )
            return ExecShape(
                backend, int(total_probes), req.k, req.rescore, "exact", None
            )
        if float(predicted) < float(req.min_recall):
            return ExecShape(
                backend, probes, req.k, req.rescore, "escalate",
                float(req.min_recall),
            )
    return ExecShape(backend, probes, req.k, req.rescore)


# ---------------------------------------------------------------- the request
@dataclasses.dataclass(frozen=True, eq=False)
class SearchRequest:
    """One dynamically-weighted similarity query (the paper's user contract).

    Exactly one of ``query`` / ``like`` must be given:

    ``query``
        Keyword-embedding form: the per-field query vectors, either already
        concatenated ``(D,)`` or a sequence of per-field blocks. Field blocks
        are unit-normalised on resolution (corpus cosine geometry).
    ``like``
        More-like-this form: the identifier of a full corpus document; the
        query vector is resolved from the index at search time, and the
        document excludes itself from its own answer unless ``exclude`` is
        set explicitly (``exclude=-1`` disables masking).

    ``weights`` are given *by field name* (``{"title": 0.6, "abstract":
    0.4}`` — unnamed fields get weight 0) or as a full per-field sequence;
    ``None`` means equal weights. Validation against the corpus
    :class:`FieldSpec` (unknown names, negative or all-zero weights) happens
    at resolution, where the spec is known.

    ``probes`` fixes the visited-cluster budget directly; ``recall_target``
    lets :func:`plan_probes` choose it; setting both is an error, setting
    neither uses the retriever's default. ``backend`` overrides the
    retriever's engine choice for this request only (``"auto"`` picks
    ``fused`` on TPU, ``sharded`` on any multi-device host — the latter
    scores shard-local quantised packs and merges one top-k collective).
    ``rescore`` (>= k)
    opts into the exact-rescore tail: the pruned search runs at that depth
    and the surviving candidates are re-scored against the fp32 corpus
    before the final top-k cut — bounding quantised-storage noise
    (``pack_dtype="bfloat16"``/``"int8"``) at the cost of one extra
    gather+matmul, honestly charged to ``n_scored`` (on the sharded
    backend the rescore itself is distributed over the row-sharded
    corpus).

    Two tiered modes turn predictions into guarantees. ``exact=True``
    sweeps ALL T·K buckets (the clustered exact pass) — the answer is the
    true top-k, so a probe budget or a recall constraint alongside it is
    an error. ``min_recall=r`` runs the planned approximate pass but
    ESCALATES whenever the calibrated ladder predicts recall below ``r``
    — re-running at the next calibrated rung, ultimately the exact tier —
    with every tier's candidates charged to the response's ``n_scored``
    and the answering tier stamped on the response. It composes with an
    explicit ``probes=`` or ``recall_target=`` starting budget.
    """

    query: jnp.ndarray | np.ndarray | Sequence | None = None
    like: int | None = None
    weights: Mapping[str, float] | Sequence[float] | None = None
    k: int = 10
    probes: int | None = None
    recall_target: float | None = None
    exclude: int | None = None
    backend: str | None = None
    rescore: int | None = None
    exact: bool = False
    min_recall: float | None = None

    def __post_init__(self):
        if (self.query is None) == (self.like is None):
            raise ValueError(
                "exactly one of query= (keyword embedding) or like= (doc id) "
                "must be given"
            )
        if self.like is not None and int(self.like) < 0:
            raise ValueError(f"like= must be a doc id >= 0, got {self.like}")
        if self.k < 1:
            raise ValueError(f"k must be >= 1, got {self.k}")
        if self.probes is not None and self.recall_target is not None:
            raise ValueError(
                "give either probes= or recall_target=, not both"
            )
        if self.probes is not None and self.probes < 1:
            raise ValueError(f"probes must be >= 1, got {self.probes}")
        if self.recall_target is not None and not (
            0.0 < self.recall_target <= 1.0
        ):
            raise ValueError(
                f"recall_target must be in (0, 1], got {self.recall_target}"
            )
        if self.rescore is not None and self.rescore < self.k:
            raise ValueError(
                f"rescore depth must be >= k ({self.k}), got {self.rescore}"
            )
        if self.exact:
            if self.probes is not None or self.recall_target is not None:
                raise ValueError(
                    "exact=True sweeps every cluster; a probes=/"
                    "recall_target= budget alongside it is contradictory"
                )
            if self.min_recall is not None:
                raise ValueError(
                    "exact=True already guarantees recall 1.0; give either "
                    "exact=True or min_recall=, not both"
                )
        if self.min_recall is not None and not (
            0.0 < self.min_recall <= 1.0
        ):
            raise ValueError(
                f"min_recall must be in (0, 1], got {self.min_recall}"
            )

    # ------------------------------------------------------------ resolution
    def resolve_weights(self, spec: FieldSpec) -> np.ndarray:
        """Per-field weight vector ``(s,)`` in spec order, validated."""
        if self.weights is None:
            w = np.full((spec.s,), 1.0 / spec.s, np.float32)
        elif isinstance(self.weights, Mapping):
            unknown = set(self.weights) - set(spec.names)
            if unknown:
                raise ValueError(
                    f"unknown field name(s) {sorted(unknown)}; "
                    f"corpus fields are {list(spec.names)}"
                )
            w = np.asarray(
                [float(self.weights.get(n, 0.0)) for n in spec.names],
                np.float32,
            )
        else:
            w = np.asarray(self.weights, np.float32)
            # validate_weights accepts batched (nq, s) rows by design; a
            # request carries exactly one weight vector, so pin the shape
            # here before the batch-tolerant checks.
            if w.shape != (spec.s,):
                raise ValueError(
                    f"weights must have one entry per field "
                    f"({spec.s}: {list(spec.names)}), got shape {w.shape}"
                )
        return validate_weights(w, spec)

    def resolve_query(self, index: ClusterPruneIndex) -> jnp.ndarray:
        """The unweighted ``(D,)`` query vector (per-field unit-normalised)."""
        spec = index.spec
        if self.like is not None:
            if int(self.like) >= index.n_docs:
                raise ValueError(
                    f"like={self.like} out of range for a corpus of "
                    f"{index.n_docs} documents"
                )
            removed = getattr(index, "removed", None)
            if removed is not None and bool(removed[int(self.like)]):
                raise ValueError(
                    f"like={self.like} refers to a removed document; "
                    "more-like-this cannot seed from a tombstoned doc"
                )
            return index.docs[int(self.like)]
        q = self.query
        if not isinstance(q, (jnp.ndarray, np.ndarray)):
            q = jnp.concatenate([jnp.asarray(f).reshape(-1) for f in q])
        q = jnp.asarray(q).reshape(-1)
        if q.shape[0] != spec.total_dim:
            raise ValueError(
                f"query has dim {q.shape[0]}, corpus concat dim is "
                f"{spec.total_dim} (fields {list(spec.names)} "
                f"dims {list(spec.dims)})"
            )
        if not bool(jnp.all(jnp.isfinite(q))):
            raise ValueError(
                "query vector contains non-finite values (NaN/Inf); every "
                "similarity against it would be garbage — fix the embedding "
                "before searching"
            )
        return normalize_fields(q, spec)

    def resolve_exclude(self) -> int:
        """Doc id to mask (-1 = none). MLT requests self-exclude by default."""
        if self.exclude is not None:
            return int(self.exclude)
        return int(self.like) if self.like is not None else -1


# --------------------------------------------------------------- the response
@dataclasses.dataclass(frozen=True)
class Hit:
    """One retrieved document with its score and per-field decomposition.

    ``field_scores[name]`` is the contribution of that field's block to the
    aggregate: ``score == sum(field_scores.values())`` exactly (float tol),
    because ``qw·p`` splits over ``spec.slices()`` by linearity.
    """

    doc_id: int
    score: float
    field_scores: dict[str, float]


@dataclasses.dataclass(frozen=True, eq=False)
class SearchResponse:
    """Ranked answer to one :class:`SearchRequest`, plus batch stats.

    ``hits`` contains only valid results (short answers stay short);
    ``doc_ids`` / ``scores`` are the raw fixed-``k`` engine arrays (-1 /
    -inf padded) for metrics code that wants rectangular batches.

    Latency is attributed **per request**, split into the two components a
    serving p99 is made of: ``queue_wait_s`` is how long THIS request
    waited before its batch was dispatched (0 on the synchronous path —
    there is no queue; the async tier stamps the measured wait), and
    ``compute_s`` is the wall time of the engine call that served this
    request's batch of ``batch_size`` requests (shared by the group: every
    rider waits for the whole fused call). ``latency_s`` is their sum —
    the request's own end-to-end latency, not the group's.

    ``n_scored`` is this request's own Fig-1 distance-computation count —
    for an escalated request it is the CUMULATIVE count over every tier
    that ran (the escalation really did score them all).
    ``predicted_recall`` is
    the planner's fitted CR/k estimate for the probe budget that served this
    request (from the index's calibrated ladder; the nominal target itself
    when the static fallback planned it; None when no prediction exists) —
    callers can audit the ``recall_target=`` promise against achieved
    recall. ``tier`` names the tier that ANSWERED: ``"approx"`` (budgeted
    pass, no floor pressure), ``"escalated"`` (a ``min_recall=`` floor
    forced at least one re-run at a higher rung — ``escalations`` counts
    them), or ``"exact"`` (the full T·K sweep answered, whether requested
    via ``exact=True`` or reached as the escalation ceiling; its
    ``predicted_recall`` is exactly 1.0 and ``probes`` is T·K).

    ``degraded`` marks an answer the serving tier walked DOWN the quality
    ladder under overload or replica faults (:mod:`repro.serving.health`);
    ``degradation`` records each applied downgrade as an audit label
    (e.g. ``"rescore:64->none"``, ``"probes:48->24"``), and
    ``predicted_recall``/``probes`` describe the budget that actually
    served — so a degraded answer is cheaper but never dishonest. The
    synchronous path never degrades (both fields keep their defaults).
    """

    hits: tuple[Hit, ...]
    doc_ids: np.ndarray      # (k,) int32, -1 padded
    scores: np.ndarray       # (k,) float32, -inf padded
    n_scored: int
    latency_s: float         # queue_wait_s + compute_s, per request
    backend: str
    probes: int
    batch_size: int
    predicted_recall: float | None = None
    queue_wait_s: float = 0.0
    compute_s: float = 0.0
    tier: str = "approx"
    escalations: int = 0
    degraded: bool = False
    degradation: tuple[str, ...] = ()

    def __len__(self) -> int:
        return len(self.hits)

    def __iter__(self):
        return iter(self.hits)

    @property
    def ids(self) -> list[int]:
        """Doc ids of the valid hits, best first."""
        return [h.doc_id for h in self.hits]


# ------------------------------------------------------------- decomposition
@functools.partial(jax.jit, static_argnames=("spec",))
def _decompose(docs, qw, ids, *, spec: FieldSpec):
    safe = jnp.where(ids >= 0, ids, 0)
    hitvecs = docs[safe]                                 # (nq, k, D)
    parts = [
        jnp.einsum("qkd,qd->qk", hitvecs[..., sl], qw[..., sl],
                   precision=jax.lax.Precision.HIGHEST)
        for sl in spec.slices()
    ]
    out = jnp.stack(parts, axis=-1)                      # (nq, k, s)
    return jnp.where((ids >= 0)[..., None], out, 0.0)


def decompose_scores(
    qw: jnp.ndarray, docs: jnp.ndarray, ids: jnp.ndarray, spec: FieldSpec
) -> jnp.ndarray:
    """Split ``qw·p`` over the field blocks: ``(nq, k, s)`` contributions.

    Linearity of the dot product over ``spec.slices()`` makes this exact:
    summing the last axis reproduces the aggregate engine score (invalid id
    slots decompose to 0). One gather + s small einsums — cheap next to the
    search itself.
    """
    return _decompose(docs, jnp.atleast_2d(qw), jnp.atleast_2d(ids), spec=spec)


@functools.partial(jax.jit, static_argnames=("spec",))
def _mlt_weighted_query(docs, likes, w_rows, *, spec: FieldSpec):
    """``Q'_w`` of a batch of more-like-this requests as one program: the
    seed documents ``docs[likes]`` weighted by ``w_rows`` ``(nq, s)``."""
    return weighted_query(docs[likes], w_rows, spec)


# ------------------------------------------------------------------ retriever
class Retriever:
    """Facade over index + engines: typed requests in, typed responses out.

    Owns one :class:`ClusterPruneIndex` and the (cached) engines over it.
    ``search`` accepts a single request or a heterogeneous batch; requests
    sharing an execution shape ``(backend, probes, k, rescore, tier,
    min_recall)`` are served by ONE engine call (the engine's batch
    dimension), others are grouped into as few calls as their shapes
    allow, and responses come back in request order.
    """

    # Cache bound: a FIFO-evicted OrderedDict. Responses are a few KB of
    # hits, so the cap keeps the cache at tens of MB worst case.
    _RESPONSE_CACHE_MAX = 2048

    def __init__(self, index: ClusterPruneIndex, *, backend: str = "auto",
                 default_probes: int = 12, calibrate: bool = False,
                 calibrate_opts: Mapping | None = None,
                 engine_opts: Mapping | None = None):
        from .engine import pick_backend

        self.index = index
        self.backend = (
            pick_backend(index) if backend in (None, "auto") else backend
        )
        self.default_probes = default_probes
        # Engine construction knobs for the DEFAULT backend (e.g.
        # ``{"query_tile": 16}`` for the fused backend's v2 tiling, or
        # ``{"qchunk": 4}`` for reference) — resolved through the
        # opts-keyed get_engine cache, so the variant engine is built and
        # traced once. Per-request backend= overrides use that backend's
        # defaults: the opts were chosen for self.backend and may not even
        # be valid kwargs elsewhere.
        self.engine_opts = dict(engine_opts or {})
        # ``calibrate=True``: an index without a fitted ladder gets one
        # lazily, on the first recall_target= request (paid once) — and a
        # ladder gone stale from corpus churn gets REFIT the same way;
        # False falls back to the static plan_probes ladder with a warning.
        self.calibrate = calibrate
        self.calibrate_opts = dict(calibrate_opts or {})
        # planning state, hoisted once: (T, K) never changes for a built
        # index, and recall_target -> (probes, predicted recall) lookups
        # repeat across requests, so both are cached here instead of being
        # re-derived from index tensors on every request.
        t, k_clusters = index.counts.shape
        self._tk = (int(t), int(k_clusters))
        self._plan_cache: dict[float, tuple[int, float]] = {}
        self._plan_ladder: object | None = index.ladder
        self._warned_static = False
        self._warned_stale = False
        # request memoisation (ROADMAP "batch caching"): whole
        # repeat-request responses, valid for exactly one index version.
        from collections import OrderedDict

        self._response_cache: "OrderedDict[tuple, SearchResponse]" = (
            OrderedDict()
        )
        self._cache_version = getattr(index, "version", 0)

    @classmethod
    def build(
        cls,
        docs,
        spec: FieldSpec,
        k_clusters: int,
        *,
        backend: str = "auto",
        default_probes: int = 12,
        calibrate: bool | Mapping = False,
        calibrate_opts: Mapping | None = None,
        engine_opts: Mapping | None = None,
        **build_kwargs,
    ) -> "Retriever":
        """Build the weight-free index and wrap it (one-stop constructor).

        ``build_kwargs`` pass through to
        :meth:`ClusterPruneIndex.build` — notably ``pack_dtype="bfloat16"``
        for half-precision bucket-major storage — and ``engine_opts`` to
        every engine resolution for the default backend (e.g.
        ``{"query_tile": 16}``).

        Pass ``calibrate=True`` (or a dict of
        :func:`~repro.core.calibrate.calibrate_index` options) to fit the
        per-index recall->probes ladder at build time; the retriever then
        serves honest ``recall_target=`` requests from the first one. The
        same flag also arms the retriever's RE-calibration policy: when
        corpus churn (``add``/``remove``) drives the ladder stale, the next
        ``recall_target=`` request refits it with the same options.
        ``calibrate_opts`` merge over (and win against) options given via a
        ``calibrate`` dict; passing ``calibrate_opts`` without opting in
        via ``calibrate`` is an error, not a silent no-op.
        """
        # Normalise the two knobs ONCE into (opted_in, opts); index.build
        # owns the bool-or-Mapping opt-in rule for direct callers, this
        # entry point only merges its own pair before delegating.
        opted_in = bool(calibrate) or isinstance(calibrate, Mapping)
        opts: dict = dict(calibrate) if isinstance(calibrate, Mapping) else {}
        if calibrate_opts:
            if not opted_in:
                raise ValueError(
                    "calibrate_opts= was given but calibrate= is off; pass "
                    "calibrate=True (or a dict of options) to opt in"
                )
            opts.update(calibrate_opts)
        index = ClusterPruneIndex.build(
            docs, spec, k_clusters,
            calibrate=(opts or True) if opted_in else False,
            **build_kwargs,
        )
        return cls(index, backend=backend, default_probes=default_probes,
                   calibrate=opted_in, calibrate_opts=opts,
                   engine_opts=engine_opts)

    @property
    def spec(self) -> FieldSpec:
        return self.index.spec

    # ------------------------------------------------------------- mutation
    def add(self, new_docs) -> np.ndarray:
        """Ingest documents into the served index (no rebuild); returns the
        new doc ids. Streams through
        :meth:`~repro.core.index.ClusterPruneIndex.add_documents` and
        flushes every retriever-level cache — the next request sees the
        mutated corpus."""
        ids = self.index.add_documents(new_docs)
        self._flush_request_caches()
        return ids

    def remove(self, doc_ids) -> int:
        """Tombstone documents out of the served index; returns how many
        were newly removed. The ids can never appear in a hit again."""
        n = self.index.remove_documents(doc_ids)
        self._flush_request_caches()
        return n

    # long-form aliases matching the index methods
    add_documents = add
    remove_documents = remove

    def _flush_request_caches(self) -> None:
        self._response_cache.clear()
        self._plan_cache.clear()
        self._cache_version = getattr(self.index, "version", 0)

    def _sync_version(self) -> None:
        """Catch mutations applied to the index directly (not through this
        facade): the index bumps ``version`` on every mutation, and stale
        cached responses must never survive one."""
        if getattr(self.index, "version", 0) != self._cache_version:
            self._flush_request_caches()
        if self.index.ladder is not self._plan_ladder:
            # ladder swapped outside _plan_target (direct calibrate_index):
            # planned budgets / predicted recall may differ.
            self._plan_cache.clear()
            self._response_cache.clear()
            self._plan_ladder = self.index.ladder

    # request cache keys -----------------------------------------------------
    @staticmethod
    def _weights_key(weights):
        """Hashable canonical form of a request's weights (None = default)."""
        if weights is None:
            return None
        if isinstance(weights, Mapping):
            return tuple(sorted((str(k), float(v)) for k, v in weights.items()))
        return tuple(float(v) for v in np.asarray(weights).reshape(-1))

    def _request_key(self, req: SearchRequest) -> tuple | None:
        """Full identity of a more-like-this request, or None when the
        request is not cacheable (raw query vectors are not memoised — the
        corpus-resident ``like=`` form is the serving hot path)."""
        if req.like is None:
            return None
        # key on the RESOLVED budget source: a default-probes request must
        # not survive a default_probes change as a stale cached answer
        probes = req.probes
        if probes is None and req.recall_target is None:
            probes = self.default_probes
        return (
            int(req.like),
            self._weights_key(req.weights),
            req.k,
            probes,
            req.recall_target,
            req.exclude,
            req.backend or self.backend,
            req.rescore,
            req.exact,
            req.min_recall,
        )

    @staticmethod
    def _cache_put(cache, cap, key, value) -> None:
        cache[key] = value
        while len(cache) > cap:
            cache.popitem(last=False)

    # ------------------------------------------------------------- planning
    def exec_shape(self, req: SearchRequest) -> ExecShape:
        """This request's :class:`ExecShape` under THIS retriever's config.

        The module-level :func:`exec_shape` contract, with the retriever
        supplying its default backend/probes, its calibrated (and cached)
        ``recall_target`` planner, the index's T·K probe ceiling and its
        ladder's recall predictor. The async serving tier keys its
        micro-batching queues off this, so a request lands in exactly the
        queue whose flush `_search_batch` would have grouped it into.
        """
        if (
            req.min_recall is not None
            and self.calibrate
            and (self.index.ladder is None
                 or getattr(self.index, "ladder_stale", False))
        ):
            # same lazy-fit/refit policy recall_target= requests get: a
            # min_recall floor deserves a measured predictor when the
            # retriever opted into calibration, not a blanket exact tier
            self._plan_target(req.min_recall)
        return exec_shape(
            req,
            default_backend=self.backend,
            default_probes=self.default_probes,
            plan_target=lambda t: self._plan_target(t)[0],
            total_probes=self._tk[0] * self._tk[1],
            predict_recall=self._predict_recall,
            index=self.index,
        )

    def _plan(self, req: SearchRequest) -> tuple[ExecShape, float | None]:
        """(execution shape, predicted recall) for one request."""
        shape = self.exec_shape(req)
        if shape.tier == "exact":
            predicted = 1.0
        elif req.recall_target is not None and req.probes is None:
            predicted = self._plan_target(req.recall_target)[1]
        else:
            predicted = self._predict_recall(shape.probes)
        return shape, predicted

    def _predict_recall(self, probes: int) -> float | None:
        """Fitted CR/k at an explicit budget — None without a ladder (the
        static ladder maps targets to budgets, not budgets to recall)."""
        ladder = self.index.ladder
        return (
            None if ladder is None
            else float(ladder.predicted_recall(probes))
        )

    def _plan_target(self, target: float) -> tuple[int, float]:
        """Map recall_target -> (probes, predicted recall), cached.

        Consults the index's calibrated :class:`ProbeLadder`; with
        ``calibrate=True`` a missing ladder is fitted lazily (once) on this
        first request — and a ladder the index reports STALE (corpus churn
        past the drift threshold since it was fit) is re-fitted the same
        way. Without ``calibrate=True`` a stale ladder still plans, with a
        one-time warning: measured-but-outdated beats the static fallback.
        A missing ladder falls back to the static :func:`plan_probes`
        ladder with a warning — the static rungs were fit on ONE synthetic
        corpus and weight setting, so the target is nominal there, not
        measured.
        """
        ladder = self.index.ladder
        stale = getattr(self.index, "ladder_stale", False)
        if (ladder is None or stale) and self.calibrate:
            from .calibrate import calibrate_index

            ladder = calibrate_index(self.index, **self.calibrate_opts)
        elif stale and not self._warned_stale:
            warnings.warn(
                "the index's calibrated probe ladder is stale (corpus churn "
                "since calibration exceeds the drift threshold); "
                "recall_target planning still uses it, but re-run "
                "repro.core.calibrate.calibrate_index(index) — or construct "
                "the Retriever with calibrate=True to refit automatically.",
                stacklevel=3,
            )
            self._warned_stale = True
        if ladder is not self._plan_ladder:       # fitted/replaced: re-plan
            self._plan_cache.clear()
            self._response_cache.clear()          # planned budgets changed
            self._plan_ladder = ladder
        cached = self._plan_cache.get(target)
        if cached is not None:
            return cached
        if ladder is not None:
            probes = ladder.plan(target)
            predicted = float(ladder.predicted_recall(probes))
        else:
            if not self._warned_static:
                warnings.warn(
                    "index has no calibrated probe ladder; recall_target "
                    "planning falls back to the static _RECALL_LADDER, "
                    "which was fit on one synthetic corpus and one weight "
                    "setting — the target is nominal, not measured. Build "
                    "with calibrate=True or run "
                    "repro.core.calibrate.calibrate_index(index).",
                    stacklevel=3,
                )
                self._warned_static = True
            t, k_clusters = self._tk
            probes = plan_probes(target, t, k_clusters)
            predicted = float(target)
        self._plan_cache[target] = (probes, predicted)
        return probes, predicted

    # -------------------------------------------------------------- serving
    def search(
        self, request: SearchRequest | Iterable[SearchRequest]
    ) -> SearchResponse | list[SearchResponse]:
        """Serve one request or a heterogeneous batch (responses in order)."""
        if isinstance(request, SearchRequest):
            return self._search_batch([request])[0]
        return self._search_batch(list(request))

    def _search_batch(self, reqs: list[SearchRequest]) -> list[SearchResponse]:
        from .engine import get_engine

        if not reqs:
            return []
        with span(tracing.SEARCH_BATCH, n=len(reqs)) as batch_span:
            with span(tracing.SEARCH_PREPARE):
                self._sync_version()
                index, spec = self.index, self.spec

                # Whole-response memoisation: a byte-identical repeat of a
                # cacheable (more-like-this) request is answered without
                # touching the engine. Cached responses keep their original
                # latency/batch stats — they describe the engine call that
                # produced the answer.
                keys = [self._request_key(r) for r in reqs]
                out: list[SearchResponse | None] = [
                    self._response_cache.get(key) if key is not None else None
                    for key in keys
                ]
                miss = [i for i, resp in enumerate(out) if resp is None]
                if not miss:
                    return out  # type: ignore[return-value]
                mreqs = [reqs[i] for i in miss]

                # Resolve every miss in one step, with no device work per
                # row: an all-MLT batch is ONE program (corpus gather + §4
                # reduction) over the whole batch; a batch with a raw vector
                # resolves each query, then makes ONE weighted_query call.
                if all(r.like is not None for r in mreqs):
                    likes = [int(r.like) for r in mreqs]
                    bad = [l for l in likes if l >= index.n_docs]
                    if bad:
                        raise ValueError(
                            f"like={bad[0]} out of range for a corpus of "
                            f"{index.n_docs} documents"
                        )
                    removed = getattr(index, "removed", None)
                    if removed is not None:
                        gone = [l for l in likes if bool(removed[l])]
                        if gone:
                            raise ValueError(
                                f"like={gone[0]} refers to a removed "
                                "document; more-like-this cannot seed "
                                "from a tombstoned doc"
                            )
                    w_rows = np.stack([r.resolve_weights(spec) for r in mreqs])
                    qw_all = _mlt_weighted_query(
                        index.docs, np.asarray(likes, np.int32), w_rows,
                        spec=spec,
                    )                                     # (n_miss, D)
                else:
                    q_all = jnp.stack([r.resolve_query(index) for r in mreqs])
                    w_rows = np.stack([r.resolve_weights(spec) for r in mreqs])
                    qw_all = weighted_query(q_all, jnp.asarray(w_rows), spec)
                excl_all = np.asarray(
                    [r.resolve_exclude() for r in mreqs], np.int32
                )
                plans = [self._plan(r) for r in mreqs]

                # Group by execution shape; each group is one engine call.
                groups: dict[ExecShape, list[int]] = {}
                for j, (shape, _) in enumerate(plans):
                    groups.setdefault(shape, []).append(j)
            batch_span.set_metadata(groups=len(groups))

            for shape, rows in groups.items():
                with span(tracing.SEARCH_PREPARE):
                    backend, probes, k, rescore = (
                        shape.backend, shape.probes, shape.k, shape.rescore,
                    )
                    opts = self.engine_opts if backend == self.backend else {}
                    engine = get_engine(index, backend, **opts)
                    if len(rows) == len(mreqs):   # the whole batch, in order
                        qw, excl = qw_all, excl_all
                    else:
                        qw, excl = qw_all[jnp.asarray(rows)], excl_all[rows]
                    excl = jnp.asarray(excl)
                t0 = time.perf_counter()
                with span(tracing.SEARCH_ENGINE):
                    tier, escalations, pred_served = "approx", 0, None
                    if shape.tier == "exact":
                        scores, ids, n_scored = engine.search_exact(
                            qw, k=k, exclude=excl, rescore=rescore
                        )
                        tier, pred_served = "exact", 1.0
                    elif shape.tier == "escalate":
                        scores, ids, n_scored, info = engine.search_escalating(
                            qw, probes=probes, k=k,
                            min_recall=shape.min_recall, exclude=excl,
                            rescore=rescore,
                        )
                        tier = info["tier"]
                        escalations = info["escalations"]
                        probes = info["probes"]
                        pred_served = info["predicted_recall"]
                    else:
                        scores, ids, n_scored = engine.search(
                            qw, probes=probes, k=k, exclude=excl,
                            rescore=rescore,
                        )
                with span(tracing.SEARCH_WAIT):
                    jax.block_until_ready(scores)
                with span(tracing.SEARCH_FETCH):
                    fields = decompose_scores(qw, index.docs, ids, spec)
                    scores_np = np.asarray(scores, np.float32)
                    ids_np = np.asarray(ids, np.int32)
                    n_np = np.asarray(n_scored, np.int32)
                    fields_np = np.asarray(fields, np.float32)
                # compute time covers everything the group's riders wait on:
                # the engine call AND the shared decompose/host transfer.
                dt = time.perf_counter() - t0
                with span(tracing.SEARCH_ASSEMBLE):
                    for jj, j in enumerate(rows):
                        hits = tuple(
                            Hit(
                                doc_id=int(ids_np[jj, c]),
                                score=float(scores_np[jj, c]),
                                field_scores={
                                    name: float(fields_np[jj, c, f])
                                    for f, name in enumerate(spec.names)
                                },
                            )
                            for c in range(k)
                            if ids_np[jj, c] >= 0
                        )
                        resp = SearchResponse(
                            hits=hits,
                            doc_ids=ids_np[jj],
                            scores=scores_np[jj],
                            n_scored=int(n_np[jj]),
                            latency_s=dt,
                            backend=engine.name,
                            probes=probes,
                            batch_size=len(rows),
                            predicted_recall=(
                                pred_served if pred_served is not None
                                else plans[j][1]
                            ),
                            queue_wait_s=0.0,
                            compute_s=dt,
                            tier=tier,
                            escalations=escalations,
                        )
                        i = miss[j]
                        out[i] = resp
                        if keys[i] is not None:
                            # the cached object is shared with every future
                            # repeat caller: freeze its array views so an
                            # in-place edit by one consumer cannot poison
                            # later cache hits
                            resp.doc_ids.flags.writeable = False
                            resp.scores.flags.writeable = False
                            self._cache_put(
                                self._response_cache,
                                self._RESPONSE_CACHE_MAX, keys[i], resp,
                            )
        return out  # type: ignore[return-value]
