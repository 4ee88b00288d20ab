"""Multi-clustering cluster-prune index — the paper's search structure.

Build: ``T`` (default 3) *independent* clusterings of the weight-free
concatenated corpus, produced by a registered clusterer
(:mod:`repro.core.cluster` — ``method="auto"`` picks the fused Pallas FPF
path on TPU, the pure-JAX FPF reference elsewhere). Search: embed the user
weights into the query (:func:`repro.core.weights.weighted_query`), probe
the ``b/T`` clusters with the most similar representatives in *each*
clustering, exhaustively score the union of their buckets, return the top-k.

This module owns the *data structure only*: the padded ``(T, K, B)`` bucket-id
tensor (sentinel = ``n``), the per-clustering assignment vectors, and — new
with the engine layer — the bucket-major ``(T, K, B, D)`` corpus tensor that
the fused Pallas backend consumes, materialised **once at build time** (or
lazily on first fused search when the build deferred it for memory). An index
may additionally carry a fitted :class:`~repro.core.calibrate.ProbeLadder`
(``ladder``, opt-in ``calibrate=`` at build or lazily on the first
``recall_target=`` request) mapping recall targets to probe budgets measured
on *this* index; it round-trips through :meth:`ClusterPruneIndex.save` /
:meth:`ClusterPruneIndex.load`.

The index is no longer frozen at build time: :meth:`add_documents` streams
new documents through the same :func:`~repro.core.cluster.assign_to_centers`
primitive the build tail uses and inserts them into the padded buckets
(growing ``B`` when a bucket overflows); :meth:`remove_documents` tombstones
documents out of every bucket. Mutations bump ``version`` (cache coherence
for retriever-level memoisation), accumulate into ``n_mutations`` (the
calibrated ladder is reported stale once drift crosses
:data:`LADDER_DRIFT_THRESHOLD`), and invalidate the bucket-major tensor and
cached engines — the bucket-major layout is re-packed *lazily* on the next
fused search, so a burst of adds pays the layout conversion once.

Search *execution* lives in :mod:`repro.core.engine`: three interchangeable
backends (``reference`` pure-JAX gather, ``fused`` Pallas ``bucket_score``,
``sharded`` ``shard_map``) share identical probe/dedup/exclude/cost
semantics. :meth:`ClusterPruneIndex.search` is a thin delegation kept for
backward compatibility — pass ``backend=`` to pick a path explicitly.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .. import tracing
from ..tracing import span
from .cluster import assign_to_centers_multi, get_clusterer
from .fields import FieldSpec, normalize_fields
from .weights import weighted_query

__all__ = [
    "ClusterPruneIndex", "CorruptIndexError", "pack_buckets",
    "pack_buckets_major", "validate_pack_dtype", "SUPPORTED_PACK_DTYPES",
    "LADDER_DRIFT_THRESHOLD",
]


class CorruptIndexError(Exception):
    """A saved index failed to load: truncated, mismatched or unreadable.

    Raised by :meth:`ClusterPruneIndex.load` with the failing artifact
    (file, or archive member) NAMED, instead of whatever opaque
    numpy/zipfile traceback the corruption would otherwise surface as.
    :meth:`ClusterPruneIndex.save` writes atomically (temp file + rename)
    precisely so a crash mid-save leaves the previous index intact rather
    than a file that raises this."""

# Storage precisions the bucket-major pack (and the fused scoring kernel)
# support. fp32 = corpus dtype; bf16 halves the packed bytes (plain cast);
# int8 quarters them (symmetric per-bucket quantisation, scales carried in
# ``bucket_scales``). Validated in ONE place (:func:`validate_pack_dtype`)
# so build / load / lazy re-pack all fail with the same clear error.
SUPPORTED_PACK_DTYPES = ("float32", "bfloat16", "int8")


def validate_pack_dtype(pack_dtype) -> str | None:
    """Canonicalise and validate a ``pack_dtype`` spec.

    Accepts None (keep the corpus dtype), a dtype-like, or a string; returns
    the canonical dtype name or None. Raises ``ValueError`` listing the
    supported precisions for anything else — the single choke point for
    build, ``load``, and every lazy re-pack (``ensure_bucket_major``).
    """
    if pack_dtype is None:
        return None
    try:
        name = jnp.dtype(pack_dtype).name
    except TypeError as e:
        raise ValueError(
            f"unsupported pack_dtype {pack_dtype!r}: not a dtype "
            f"(supported: {', '.join(SUPPORTED_PACK_DTYPES)})"
        ) from e
    if name not in SUPPORTED_PACK_DTYPES:
        raise ValueError(
            f"unsupported pack_dtype {name!r} "
            f"(supported: {', '.join(SUPPORTED_PACK_DTYPES)})"
        )
    return name

# Fraction of the corpus that may churn (adds + removes) before a calibrated
# ProbeLadder is reported stale: the recall-vs-probes curve was measured on
# the pre-mutation clustering, and past this drift the promise is no longer
# trustworthy (Retriever re-calibrates or warns — see api._plan_target).
LADDER_DRIFT_THRESHOLD = 0.1

# Auto-materialise the bucket-major tensor at build (TPU only, where the
# fused backend serves by default) when it costs less than this; otherwise
# defer to the first fused search (ensure_bucket_major). The bound lies far
# below the size at which pick_backend shards an index, so a build that
# resolves to ``sharded`` never holds the whole pack on one device.
_PACK_MAJOR_AUTO_BYTES = 256 * 2**20


def pack_buckets(
    assign: np.ndarray, k: int, n: int, bucket_pad: int | None = None
) -> tuple[np.ndarray, np.ndarray]:
    """Pack an assignment vector into a padded (K, B) bucket-id matrix.

    Padding uses the sentinel id ``n`` (one past the last valid doc). ``B`` is
    the max bucket size rounded up to a multiple of 8 (TPU sublane friendly).
    Entries with ``assign < 0`` (tombstoned documents) are skipped.
    """
    assign = np.asarray(assign)
    valid_idx = np.flatnonzero(assign >= 0)
    a = assign[valid_idx]
    counts = np.bincount(a, minlength=k).astype(np.int32)
    b = (int(counts.max()) if counts.size else 1) if bucket_pad is None \
        else bucket_pad
    b = max(8, -(-b // 8) * 8)
    ids = np.full((k, b), n, dtype=np.int32)
    order = valid_idx[np.argsort(a, kind="stable")]
    sorted_assign = assign[order]
    # position of each doc inside its bucket
    start = np.zeros(k + 1, dtype=np.int64)
    np.cumsum(counts, out=start[1:])
    pos = np.arange(len(order)) - start[sorted_assign]
    ids[sorted_assign, pos] = order
    return ids, counts


def pack_buckets_major(
    docs: jnp.ndarray, buckets: jnp.ndarray, n: int, dtype=None
) -> tuple[jnp.ndarray, jnp.ndarray | None]:
    """Bucket-major layout: (n, D) corpus + (T, K, B) ids -> (T, K, B, D).

    Sentinel slots (id == ``n``) point at row 0; consumers mask them via the
    id tensor, so the data tensor itself needs no sentinel handling. This is
    the one-time layout conversion that lets the fused backend read a probed
    bucket as a contiguous block instead of a row gather. Delegates to the
    kernel-side :func:`repro.kernels.bucket_score.ops.pack_bucket_major`
    after normalising this module's sentinel-``n`` padding to its ``-1``.

    ``dtype`` selects the storage precision of the packed tensor —
    ``"bfloat16"`` halves the HBM bytes and the scoring bandwidth,
    ``"int8"`` quarters them via symmetric per-bucket quantisation; the
    fused kernel accumulates fp32 regardless, and navigation keeps the fp32
    leaders. The doc-major corpus and every other consumer stay fp32.

    Returns ``(data (T, K, B, D), scales (T, K) fp32 | None)`` — scales are
    non-None only for the int8 pack.
    """
    from ..kernels.bucket_score.ops import pack_bucket_major

    dtype = validate_pack_dtype(dtype)
    data, _, scales = pack_bucket_major(
        docs, jnp.where(buckets < n, buckets, -1),
        dtype=None if dtype is None else jnp.dtype(dtype),
    )
    return data, scales


@dataclasses.dataclass
class ClusterPruneIndex:
    """The paper's index: T independent clusterings over a weight-free corpus."""

    spec: FieldSpec
    docs: jnp.ndarray       # (n, D) per-field unit-normalised corpus
    leaders: jnp.ndarray    # (T, K, D)
    buckets: jnp.ndarray    # (T, K, B) int32, sentinel = n
    counts: jnp.ndarray     # (T, K) int32 LIVE members per bucket
    method: str = "fpf"
    assign: np.ndarray | None = None        # (T, n) cluster of each doc (-1 = removed)
    bucket_data: jnp.ndarray | None = None  # (T, K, B, D) bucket-major corpus
    bucket_scales: jnp.ndarray | None = None  # (T, K) fp32 int8 dequant scales
    pack_dtype: str | None = None           # bucket-major storage dtype (None = docs')
    ladder: object | None = None            # fitted ProbeLadder (or None)
    removed: np.ndarray | None = None       # (n,) bool tombstones (or None)
    version: int = 0                        # bumped on every mutation
    n_mutations: int = 0                    # docs churned since last calibration

    # ------------------------------------------------------------------ build
    @classmethod
    def build(
        cls,
        docs: jnp.ndarray,
        spec: FieldSpec,
        k_clusters: int,
        *,
        n_clusterings: int = 3,
        method: str = "auto",
        key: jax.Array | None = None,
        pack_major: bool | None = None,
        pack_dtype=None,
        calibrate: bool | dict = False,
        **clusterer_kwargs,
    ) -> "ClusterPruneIndex":
        """Cluster T ways, pack buckets, and materialise the bucket-major
        tensor for the fused backend where that backend will actually serve.

        ``method`` names a registered clusterer
        (:func:`repro.core.cluster.available_clusterers`); the default
        ``"auto"`` resolves to ``fpf_fused`` (the Pallas kernel path) on TPU
        and the pure-JAX ``fpf`` reference elsewhere — the two produce
        identical clusterings at a fixed seed, so the stored ``method``
        records the resolved name only for provenance.
        ``clusterer_kwargs`` pass through to the clusterer's constructor
        (e.g. ``iters=`` for ``kmeans``).

        ``pack_major``: True forces the (T, K, B, D) tensor now, False defers
        it to the first fused search, None (default) materialises it only on
        TPU (the fused auto-pick platform) and within a modest memory budget
        — either way the layout conversion happens exactly once per index.

        ``pack_dtype``: storage dtype of the bucket-major tensor only
        (:data:`SUPPORTED_PACK_DTYPES`). ``"bfloat16"`` halves its HBM
        footprint and the bandwidth the fused scoring matmul must hide;
        ``"int8"`` quarters them via symmetric per-bucket quantisation
        (scales land in ``bucket_scales`` and persist with the index) —
        quadruple the corpus per pack budget. Either way the kernel
        accumulates fp32 (``preferred_element_type``) and navigation keeps
        the fp32 leaders, so probe sets and ``n_scored`` are bit-identical
        across pack dtypes. Persisted with the index, honoured by every
        (re-)pack including the lazy one after mutations. None keeps the
        corpus dtype (fp32).

        ``calibrate``: opt-in planner calibration at build — True fits the
        per-index recall->probes :class:`~repro.core.calibrate.ProbeLadder`
        with default sampling, a dict passes options through to
        :func:`~repro.core.calibrate.calibrate_index` (e.g. ``{"n_queries":
        32, "seed": 1}``). False (default) leaves ``ladder=None``; a
        ``Retriever`` built with ``calibrate=True`` will then fit it lazily
        on the first ``recall_target=`` request.
        """
        if key is None:
            key = jax.random.PRNGKey(0)
        n = docs.shape[0]
        clusterer = get_clusterer(method, **clusterer_kwargs)
        reps_l, ids_l, counts_l, assign_l = [], [], [], []
        for t, sub in enumerate(jax.random.split(key, n_clusterings)):
            with span(tracing.BUILD_CLUSTER, t=t):
                res = clusterer.cluster(docs, k_clusters, sub)
                reps_l.append(res.reps)
                assign = np.asarray(res.assign)
                assign_l.append(assign)
            with span(tracing.BUILD_BUCKETS):
                ids, counts = pack_buckets(assign, k_clusters, n)
                ids_l.append(ids)
                counts_l.append(counts)
        with span(tracing.BUILD_BUCKETS):
            b = max(ids.shape[1] for ids in ids_l)
            ids_l = [
                np.pad(ids, ((0, 0), (0, b - ids.shape[1])), constant_values=n)
                for ids in ids_l
            ]
            buckets = jnp.asarray(np.stack(ids_l))
        pack_dtype = validate_pack_dtype(pack_dtype)
        if pack_major is None:
            itemsize = (
                docs.dtype.itemsize if pack_dtype is None
                else jnp.dtype(pack_dtype).itemsize
            )
            pack_major = (
                jax.default_backend() == "tpu"
                and buckets.size * docs.shape[1] * itemsize
                <= _PACK_MAJOR_AUTO_BYTES
            )
        bucket_data, bucket_scales = None, None
        if pack_major:
            with span(tracing.INDEX_PACK):
                bucket_data, bucket_scales = pack_buckets_major(
                    docs, buckets, n, dtype=pack_dtype
                )
        index = cls(
            spec=spec,
            docs=docs,
            leaders=jnp.stack(reps_l),
            buckets=buckets,
            counts=jnp.asarray(np.stack(counts_l)),
            method=clusterer.name,
            assign=np.stack(assign_l).astype(np.int64),
            bucket_data=bucket_data,
            bucket_scales=bucket_scales,
            pack_dtype=pack_dtype,
        )
        from collections.abc import Mapping

        # any Mapping (even empty = "calibrate with defaults") is an opt-in
        if calibrate or isinstance(calibrate, Mapping):
            from .calibrate import calibrate_index

            calibrate_index(
                index,
                **(dict(calibrate) if isinstance(calibrate, Mapping) else {}),
            )
        return index

    # ------------------------------------------------------------- structure
    @property
    def n_docs(self) -> int:
        """Corpus rows (tombstoned documents included — ids are stable)."""
        return self.docs.shape[0]

    @property
    def n_live(self) -> int:
        """Documents actually reachable through the buckets."""
        gone = 0 if self.removed is None else int(self.removed.sum())
        return self.n_docs - gone

    @property
    def ladder_stale(self) -> bool:
        """True when the calibrated ladder predates too much corpus churn.

        The recall-vs-probes curve was measured on the clustering as it
        stood at calibration time; once adds + removes exceed
        :data:`LADDER_DRIFT_THRESHOLD` of the corpus, ``recall_target=``
        promises planned from it are no longer measured-on-this-index.
        ``calibrate_index`` resets the drift counter when it refits.
        """
        if self.ladder is None:
            return False
        return self.n_mutations > LADDER_DRIFT_THRESHOLD * max(1, self.n_live)

    def assignments(self) -> np.ndarray:
        """(T, n) cluster assignment per doc, -1 for removed docs (derived
        from buckets if the index predates the ``assign`` field)."""
        if self.assign is not None:
            return self.assign
        t, k_clusters, _ = self.buckets.shape
        bk = np.asarray(self.buckets)
        out = np.full((t, self.n_docs), -1, np.int64)
        for ti in range(t):
            for c in range(k_clusters):
                row = bk[ti, c]
                out[ti, row[row < self.n_docs]] = c
        return out

    # ---------------------------------------------------------- maintenance
    def _invalidate(self) -> None:
        """After a mutation: drop every derived/cached view and bump the
        version. The bucket-major tensor is re-packed LAZILY (next fused
        search), cached engines re-materialise on next ``get_engine`` —
        retriever-level caches key off ``version``."""
        self.bucket_data = None
        self.bucket_scales = None
        self.__dict__.pop("_bucket_major_flat", None)
        self.__dict__.pop("_local_bucket_major", None)
        self.__dict__.pop("_engines", None)
        self.version += 1

    def add_documents(
        self, new_docs: jnp.ndarray, *, chunk: int = 16384
    ) -> np.ndarray:
        """Ingest documents WITHOUT a rebuild; returns their new doc ids.

        The whole batch is assigned under all T clusterings by ONE fused
        device call (:func:`~repro.core.cluster.assign_to_centers_multi` —
        a single ``(chunk, T·K)`` matmul per chunk, same argmax semantics
        as the build tail's per-clustering
        :func:`~repro.core.cluster.assign_to_centers`), then inserted into
        free padded bucket slots by a single vectorised host-side scatter;
        ``B`` grows (to the next sublane multiple of 8) only when a bucket
        overflows. Leaders are NOT moved — that is the paper's serve-time
        contract (representative drift is what the :attr:`ladder_stale`
        threshold prices in).

        ``new_docs`` rows are per-field unit-normalised on ingestion (a
        no-op for vectors that already follow the corpus convention).
        """
        new_docs = jnp.atleast_2d(jnp.asarray(new_docs))
        if new_docs.shape[-1] != self.spec.total_dim:
            raise ValueError(
                f"new docs have dim {new_docs.shape[-1]}, corpus concat dim "
                f"is {self.spec.total_dim}"
            )
        m = int(new_docs.shape[0])
        if m == 0:
            return np.empty((0,), np.int64)
        new_docs = normalize_fields(new_docs, self.spec)
        n_old = self.n_docs
        n_new = n_old + m
        t, k_clusters, b = self.buckets.shape

        # ONE fused (m, T·K) assignment matmul over all T clusterings —
        # large ingests are a single device call, not a Python loop over T.
        new_assign = np.asarray(
            assign_to_centers_multi(new_docs, self.leaders, chunk=chunk)[0]
        ).astype(np.int64)                                # (T, m)

        all_assign = self.assignments()                   # (T, n_old), pre-add
        counts = np.asarray(self.counts).copy()
        add_counts = np.zeros_like(counts)
        np.add.at(
            add_counts,
            (np.repeat(np.arange(t), m), new_assign.reshape(-1)),
            1,
        )

        # Grow B only on overflow; invalid slots always hold the CURRENT
        # sentinel (== n_docs), so valid entries are exactly ``< n_old``.
        need = int((counts + add_counts).max())
        new_b = b if need <= b else max(8, -(-need // 8) * 8)
        bk = np.asarray(self.buckets)
        out = np.full((t, k_clusters, new_b), n_new, np.int32)
        live = bk < n_old
        out[:, :, :b][live] = bk[live]

        # Single host-side scatter into free slots: sort the (clustering,
        # cluster) keys once, rank each new doc within its bucket group,
        # and land rank j in the j-th free column of its row. Free slots
        # are not necessarily a suffix (removals punch holes), so the free
        # columns are ranked per row too (stable argsort: free-first,
        # ascending column).
        ids_new = np.arange(n_old, n_new, dtype=np.int64)
        rows = out.reshape(t * k_clusters, new_b)
        flat_c = (
            new_assign + np.arange(t)[:, None] * k_clusters
        ).reshape(-1)                                     # (T·m,) row keys
        order = np.argsort(flat_c, kind="stable")
        sorted_c = flat_c[order]
        starts = np.r_[0, np.flatnonzero(np.diff(sorted_c)) + 1]
        group_len = np.diff(np.r_[starts, sorted_c.size])
        rank = np.arange(sorted_c.size) - np.repeat(starts, group_len)
        free_cols = np.argsort(rows != n_new, axis=1, kind="stable")
        rows[sorted_c, free_cols[sorted_c, rank]] = np.tile(ids_new, t)[order]
        counts += add_counts

        self.docs = jnp.concatenate([self.docs, new_docs])
        self.buckets = jnp.asarray(out)
        self.counts = jnp.asarray(counts)
        self.assign = np.concatenate([all_assign, new_assign], axis=1)
        if self.removed is not None:
            self.removed = np.concatenate(
                [self.removed, np.zeros((m,), bool)]
            )
        self.n_mutations += m
        self._invalidate()
        return ids_new

    def remove_documents(self, doc_ids) -> int:
        """Tombstone documents out of every bucket; returns how many were
        newly removed (already-removed ids are ignored).

        Doc ids are STABLE handles: the corpus rows stay in place (so
        ``like=`` resolution and score decomposition keep working for the
        survivors) but the removed ids leave every bucket — no backend can
        ever score or return them. Their padded slots become free capacity
        for later :meth:`add_documents` calls.
        """
        ids = np.unique(np.asarray(doc_ids, np.int64).reshape(-1))
        if ids.size == 0:
            return 0
        n = self.n_docs
        if ids[0] < 0 or ids[-1] >= n:
            raise ValueError(
                f"doc ids must be in [0, {n}), got range "
                f"[{ids[0]}, {ids[-1]}]"
            )
        removed = (
            self.removed.copy() if self.removed is not None
            else np.zeros((n,), bool)
        )
        fresh = ids[~removed[ids]]
        if fresh.size == 0:
            return 0

        all_assign = self.assignments().copy()            # (T, n)
        t = all_assign.shape[0]
        bk = np.asarray(self.buckets).copy()
        bk[np.isin(bk, fresh)] = n                        # back to sentinel
        counts = np.asarray(self.counts).copy()
        for ti in range(t):
            a = all_assign[ti, fresh]
            a = a[a >= 0]
            np.subtract.at(counts[ti], a, 1)
        all_assign[:, fresh] = -1
        removed[fresh] = True

        self.buckets = jnp.asarray(bk)
        self.counts = jnp.asarray(counts)
        self.assign = all_assign
        self.removed = removed
        self.n_mutations += int(fresh.size)
        self._invalidate()
        return int(fresh.size)

    def ensure_bucket_major(
        self,
    ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray | None]:
        """Bucket-major view for the fused backend: ``((T, K, B, D) data,
        (T*K, B) ids with -1 padding, (T*K,) fp32 scales | None)``.
        Materialises the data tensor if the build deferred it — in
        ``pack_dtype`` storage precision when the index carries one (bf16
        halves the packed HBM bytes, int8 quarters them and fills the
        per-bucket dequantisation scales) — and caches the flattened ids and
        scales so the serving hot path pays no per-query layout work. The
        data keeps its ``(T, K)`` axes: flattening it here would copy the
        pack, and :func:`~repro.kernels.bucket_score.ops.bucket_score_tiled`
        flattens it inside its jit for free."""
        cached = getattr(self, "_bucket_major_flat", None)
        if cached is not None:
            return cached
        self.pack_dtype = validate_pack_dtype(self.pack_dtype)
        if self.bucket_data is None:
            with span(tracing.INDEX_PACK):
                self.bucket_data, self.bucket_scales = pack_buckets_major(
                    self.docs, self.buckets, self.n_docs,
                    dtype=self.pack_dtype,
                )
        t, k_clusters, b, _ = self.bucket_data.shape
        ids = jnp.where(self.buckets < self.n_docs, self.buckets, -1)
        self._bucket_major_flat = (
            self.bucket_data,
            ids.reshape(t * k_clusters, b).astype(jnp.int32),
            (
                None if self.bucket_scales is None
                else self.bucket_scales.reshape(t * k_clusters)
            ),
        )
        return self._bucket_major_flat

    def ensure_local_bucket_major(
        self, n_shards: int, *, sharding=None
    ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray | None, int]:
        """Shard-local bucket-major pack for the sharded-fused backend:
        ``((S, T*K, B_l, D) data, (S, T*K, B_l) LOCAL ids with -1 padding,
        (S, T*K) fp32 scales | None, n_local rows per shard)``.

        Each shard's slice of every bucket, in ``pack_dtype`` storage
        precision (int8 quantises per ``(shard, bucket)`` — each shard's
        absmax over its own slice). Cached per shard count and dropped by
        :meth:`_invalidate`, so mutations trigger a lazy repack on the next
        sharded-fused search — same coherence contract as
        :meth:`ensure_bucket_major`. Corpora whose size does not divide
        ``n_shards`` pad with sentinel rows no bucket references.
        ``sharding`` places the pack on a mesh as it is built, one slice per
        device (:func:`~repro.core.distributed.pack_local_bucket_major`).
        """
        from .distributed import pack_local_bucket_major

        n_shards = int(n_shards)
        cache = self.__dict__.setdefault("_local_bucket_major", {})
        hit = cache.get(n_shards)
        if hit is not None:
            return hit
        self.pack_dtype = validate_pack_dtype(self.pack_dtype)
        k_clusters = int(self.buckets.shape[1])
        with span(tracing.INDEX_PACK):
            cache[n_shards] = pack_local_bucket_major(
                self.docs, self.assignments(), k_clusters, n_shards,
                dtype=self.pack_dtype, sharding=sharding,
            )
        return cache[n_shards]

    # ------------------------------------------------------------ persistence
    def save(self, path) -> None:
        """Serialize the index — calibrated ladder and mutation state
        (tombstones, ladder-drift counter) included — to one ``.npz``. The
        bucket-major tensor is NOT stored (it is a pure layout transform,
        re-derived lazily on load in ``pack_dtype`` precision); the tiny
        per-bucket int8 ``bucket_scales`` ARE, as is the ladder, so a loaded
        index keeps its honest ``recall_target=`` planning without re-paying
        the calibration sweep — and keeps knowing when that ladder went
        stale.

        The write is CRASH-SAFE: bytes go to a temp file in the target
        directory first and only an atomic ``os.replace`` publishes them
        under the final name, so a crash (or full disk) mid-save leaves
        any previous save untouched instead of a truncated archive."""
        import json
        import os
        import tempfile

        # np.savez appends ".npz" to suffix-less paths; pin the FINAL name
        # first so the atomic rename publishes exactly what load expects.
        final = os.fspath(path)
        if not final.endswith(".npz"):
            final += ".npz"
        fd, tmp = tempfile.mkstemp(
            dir=os.path.dirname(final) or ".",
            prefix=os.path.basename(final) + ".tmp.",
        )
        try:
            with os.fdopen(fd, "wb") as f:
                self._write_npz(f, json)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, final)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    def _write_npz(self, f, json) -> None:
        np.savez_compressed(
            f,
            docs=np.asarray(self.docs),
            leaders=np.asarray(self.leaders),
            buckets=np.asarray(self.buckets),
            counts=np.asarray(self.counts),
            assign=(
                self.assign if self.assign is not None
                else np.zeros((0, 0), np.int64)
            ),
            method=np.str_(self.method),
            names=np.asarray(self.spec.names),
            dims=np.asarray(self.spec.dims, np.int64),
            ladder=np.str_(
                "" if self.ladder is None
                else json.dumps(self.ladder.to_dict())
            ),
            removed=(
                self.removed if self.removed is not None
                else np.zeros((0,), bool)
            ),
            n_mutations=np.int64(self.n_mutations),
            pack_dtype=np.str_(self.pack_dtype or ""),
            bucket_scales=(
                np.asarray(self.bucket_scales)
                if self.bucket_scales is not None
                else np.zeros((0, 0), np.float32)
            ),
        )

    @classmethod
    def load(cls, path) -> "ClusterPruneIndex":
        """Inverse of :meth:`save` (ladder + mutation state included).

        Raises :class:`CorruptIndexError` naming the failing artifact on a
        truncated, mismatched or unreadable file — a clear diagnosis at
        the one place that knows which file and which member broke,
        instead of an opaque numpy/zipfile traceback from deep inside the
        decompressor."""
        import json
        import os
        import zipfile

        from .calibrate import ProbeLadder
        from .fields import FieldSpec

        fname = os.fspath(path)
        try:
            z = np.load(path, allow_pickle=False)
        except FileNotFoundError:
            raise
        except (zipfile.BadZipFile, ValueError, OSError, EOFError) as e:
            raise CorruptIndexError(
                f"saved index {fname!r} is not a readable .npz archive "
                f"(truncated save or not an index file): {e}"
            ) from e

        def member(key, required=True, default=None):
            """One eagerly-decompressed member; truncation inside the
            archive surfaces HERE, so the error can name the member."""
            if key not in z.files:
                if required:
                    raise CorruptIndexError(
                        f"saved index {fname!r} is missing required "
                        f"member {key!r} (have {sorted(z.files)})"
                    )
                return default
            try:
                return z[key]
            except Exception as e:
                raise CorruptIndexError(
                    f"member {key!r} of saved index {fname!r} failed to "
                    f"decompress (truncated or corrupt archive): {e}"
                ) from e

        assign = member("assign")
        ladder_json = str(member("ladder"))
        removed = member("removed", required=False,
                         default=np.zeros(0, bool))
        scales = member("bucket_scales", required=False,
                        default=np.zeros((0, 0), np.float32))
        try:
            ladder = (
                ProbeLadder.from_dict(json.loads(ladder_json))
                if ladder_json else None
            )
        except (ValueError, KeyError, TypeError) as e:
            raise CorruptIndexError(
                f"member 'ladder' of saved index {fname!r} holds invalid "
                f"calibration JSON: {e}"
            ) from e
        docs = member("docs")
        names = member("names")
        dims = member("dims")
        if docs.ndim != 2:
            raise CorruptIndexError(
                f"member 'docs' of saved index {fname!r} has shape "
                f"{docs.shape}, expected a 2-D (n, D) corpus"
            )
        if int(np.sum(np.asarray(dims, np.int64))) != int(docs.shape[1]):
            raise CorruptIndexError(
                f"saved index {fname!r} is internally inconsistent: field "
                f"dims {list(int(d) for d in dims)} sum to "
                f"{int(np.sum(np.asarray(dims, np.int64)))} but 'docs' has "
                f"dim {int(docs.shape[1])} (mismatched members — partial "
                f"overwrite?)"
            )
        return cls(
            spec=FieldSpec(
                names=tuple(str(n) for n in names),
                dims=tuple(int(d) for d in dims),
            ),
            docs=jnp.asarray(docs),
            leaders=jnp.asarray(member("leaders")),
            buckets=jnp.asarray(member("buckets")),
            counts=jnp.asarray(member("counts")),
            method=str(member("method")),
            assign=assign if assign.size else None,
            ladder=ladder,
            removed=removed if removed.size else None,
            n_mutations=int(
                member("n_mutations", required=False, default=0)
            ),
            pack_dtype=validate_pack_dtype(
                str(member("pack_dtype", required=False, default="")) or None
            ),
            bucket_scales=jnp.asarray(scales) if scales.size else None,
        )

    # ----------------------------------------------------------------- search
    def search_weighted(
        self,
        q: jnp.ndarray,
        w: jnp.ndarray,
        *,
        probes: int,
        k: int,
        exclude: jnp.ndarray | None = None,
        backend: str = "reference",
    ):
        """Search with per-field query ``q (nq, D)`` and weights ``w (nq, s)``."""
        qw = weighted_query(q, w, self.spec)
        return self.search(qw, probes=probes, k=k, exclude=exclude,
                           backend=backend)

    def search(
        self,
        qw: jnp.ndarray,
        *,
        probes: int,
        k: int,
        exclude: jnp.ndarray | None = None,
        qchunk: int | None = None,
        nav_query: jnp.ndarray | None = None,
        backend: str = "reference",
    ):
        """Cluster-pruned top-k for pre-weighted queries ``qw (nq, D)``.

        **Deprecated** thin shim over :mod:`repro.core.engine`, kept for
        existing callers — new code should speak
        :class:`repro.core.api.SearchRequest` through a
        :class:`repro.core.api.Retriever` (typed responses, weight
        validation, per-field score decomposition) or use ``get_engine``
        directly for raw tuples.

        ``backend`` picks the execution path (``"reference"``, ``"fused"``,
        ``"sharded"`` or ``"auto"``). ``nav_query``: optional separate query
        for LEADER navigation (the CellDec baseline navigates with the
        region-squeezed composite while scoring exactly — [18] §5.4);
        defaults to ``qw``. ``qchunk`` (None = backend default) is honoured
        only by the ``reference`` backend; setting it with any other
        backend raises instead of being silently dropped.

        Returns ``(scores (nq,k), ids (nq,k), n_scored (nq,))`` where
        ``n_scored`` counts true distance computations (leaders + candidates)
        for the paper's Fig-1 cost accounting.
        """
        from .engine import get_engine, pick_backend

        name = pick_backend(self) if backend in (None, "auto") else backend
        if qchunk is not None and name != "reference":
            raise ValueError(
                f"qchunk={qchunk} is only honoured by the 'reference' "
                f"backend, but backend={name!r} would silently ignore it; "
                "drop qchunk or use backend='reference'"
            )
        opts = {"qchunk": qchunk} if qchunk is not None else {}
        return get_engine(self, name, **opts).search(
            qw, probes=probes, k=k, exclude=exclude, nav_query=nav_query
        )
