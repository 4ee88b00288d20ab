"""The plain reference the benchmark judges the served answers by.

It imports nothing of the program. It recomputes, from the corpus the
benchmark generated and each request's document and field weights:

- the weighted query of the paper's section 4 (each field block scaled by
  its weight, the whole normalised);
- navigation: the ``probes // T`` (spread evenly) leaders of each of the T
  clusterings most similar to the query;
- the pruned answer: the top ``k`` of every member of the probed buckets,
  the request's own document left out, each document once;
- the exact answer: the top ``k`` of the whole corpus, for recall;
- the per-field split of each returned document's score.

The clustering itself (leaders and bucket membership) is the index the
program built. It is the state under test, like the rows of a table, and
is checked before it is used (``check_index``): every leader must be a
corpus document bit for bit, every document must sit in exactly one bucket
of every clustering, as the bucket counts say, in the bucket of its most
similar leader (``assign_gap``), and the leaders must be spread as the
paper's farthest-point-first (FPF) preprocessing spreads them
(``leader_gap``, against this module's own FPF run from the seed).

Every product that ranks documents runs at ``Precision.HIGHEST``: float32,
as the configuration states. ``CONTROL`` is the step below it, three
bfloat16 passes (``Precision.HIGH`` on a TPU), written out so that it
means the same on every backend. The build assigns documents at one
bfloat16 pass; ``FP8``, operands rounded to float8 (e4m3), is the step
below that and the control of the assignment.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"
CONTROL = "bf16x3"
FP8 = "fp8"
BLOCK = 256
# Leader similarities nearer than this are ties for navigation: float32
# products of 4096 terms computed in another order differ by some 1e-7.
NAV_TIE = 1e-5
# Child stream of the run's seed for the reference's own FPF samples.
_FPF_STREAM = 3


def matmul(a, b, precision: str):
    """``a @ b`` in float32: at ``HIGHEST``, in three bfloat16 passes
    (``CONTROL``), or of operands rounded to float8 (``FP8``)."""
    if precision == HIGHEST:
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)

    # Round with ``reduce_precision``, which the compiler keeps, where a
    # round trip of float32 values through a narrower type may be folded
    # away; the products of bfloat16 (or float8) values are exact in float32
    # on every backend.
    def bf16(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def dot(x, y):
        return jnp.matmul(x, y, precision=jax.lax.Precision.DEFAULT)

    if precision == FP8:
        def fp8(x):
            return jax.lax.reduce_precision(x, exponent_bits=4,
                                            mantissa_bits=3)
        return dot(fp8(a), fp8(b))
    if precision != CONTROL:
        raise ValueError(f"unknown precision {precision!r}")

    def split(x):
        hi = bf16(x)
        return hi, bf16(x - hi)

    a_hi, a_lo = split(a)
    b_hi, b_lo = split(b)
    return dot(a_lo, b_hi) + dot(a_hi, b_lo) + dot(a_hi, b_hi)


def split_probes(probes: int, t: int) -> tuple[int, ...]:
    """A probe budget spread evenly over T clusterings, the first ones
    taking the remainder."""
    base, rem = divmod(int(probes), t)
    return tuple(base + (1 if i < rem else 0) for i in range(t))


def field_bounds(dims) -> tuple[tuple[int, int], ...]:
    edges = np.cumsum([0, *dims])
    return tuple((int(a), int(b)) for a, b in zip(edges[:-1], edges[1:]))


def _weighted(q, w, dims):
    expanded = jnp.repeat(w, jnp.asarray(dims), axis=-1,
                          total_repeat_length=int(sum(dims)))
    qw = q * expanded
    norm = jnp.linalg.norm(qw, axis=-1, keepdims=True)
    return qw / jnp.maximum(norm, 1e-12)


def _navigate(qw, leaders, probes_t, precision):
    """Per request, the probed buckets ``(m, P)`` (flat ids), and which of
    them lie clearly above the best bucket of their clustering left out:
    more than ``NAV_TIE`` above it, so that every sound navigation probes
    them whatever its rounding."""
    t, kk, _ = leaders.shape
    lsims = matmul(qw, leaders.reshape(t * kk, -1).T, precision)
    parts, sure = [], []
    for i, p in enumerate(probes_t):
        if not p:
            continue
        v, ix = jax.lax.top_k(lsims[:, i * kk:(i + 1) * kk], min(p + 1, kk))
        parts.append(ix[:, :p] + i * kk)
        sure.append(v[:, :p] > v[:, p:p + 1] + NAV_TIE if p < kk
                    else jnp.ones_like(v, bool))
    return jnp.concatenate(parts, axis=-1), jnp.concatenate(sure, axis=-1)


def _fields(docs, qw, ids, bounds, precision):
    vecs = docs[jnp.maximum(ids, 0)]                       # (m, k, D)
    out = []
    for a, b in bounds:
        prod = matmul(vecs[..., a:b], qw[:, a:b, None], precision)
        out.append(prod[..., 0])
    return jnp.stack(out, axis=-1)                          # (m, k, fields)


def _pruned_scores(docs, leaders, buckets, qw, exclude, probes_t, precision,
                   sure_only):
    """Every document's score, which of them the pruned search may return
    (with ``sure_only``, only the members of buckets every sound navigation
    probes), and the probed buckets."""
    n = docs.shape[0]
    t, kk, b = buckets.shape
    flat, sure = _navigate(qw, leaders, probes_t, precision)   # (m, P)
    members = buckets.reshape(t * kk, b)[flat]
    if sure_only:
        members = jnp.where(sure[..., None], members, n)
    members = members.reshape(qw.shape[0], -1)
    rows = jnp.arange(qw.shape[0])[:, None]
    mask = jnp.zeros((qw.shape[0], n + 1), bool).at[rows, members].set(True)
    scores = matmul(qw, docs.T, precision)                  # (m, n)
    keep = mask[:, :n] & (jnp.arange(n)[None, :] != exclude[:, None])
    return scores, keep, flat


@functools.partial(jax.jit, static_argnames=("probes_t", "k", "dims"))
def truth(docs, leaders, buckets, likes, weights, answer_ids, *,
          probes_t, k, dims):
    """The reference at ``HIGHEST`` for one block of requests, and its
    reading of an answer ``answer_ids (m, k)``: ``(pruned scores, pruned
    ids, exact ids, answer's scores, answer's field scores, probes)``. The
    pruned answer is over the buckets every sound navigation probes: a
    bucket whose leader ties (``NAV_TIE``) with the best left out may be
    probed or not."""
    qw = _weighted(docs[likes], weights, dims)
    scores, keep, flat = _pruned_scores(
        docs, leaders, buckets, qw, likes, probes_t, HIGHEST, True)
    ps, pi = jax.lax.top_k(jnp.where(keep, scores, -jnp.inf), k)
    not_self = jnp.arange(docs.shape[0])[None, :] != likes[:, None]
    _, ei = jax.lax.top_k(jnp.where(not_self, scores, -jnp.inf), k)
    valid = answer_ids >= 0
    rp = jnp.take_along_axis(scores, jnp.maximum(answer_ids, 0), axis=-1)
    rp = jnp.where(valid, rp, -jnp.inf)
    rf = _fields(docs, qw, answer_ids, field_bounds(dims), HIGHEST)
    return ps, jnp.where(jnp.isfinite(ps), pi, -1), ei, rp, rf, flat


@functools.partial(jax.jit, static_argnames=("probes_t", "k", "dims"))
def control_answer(docs, leaders, buckets, likes, weights, *,
                   probes_t, k, dims):
    """The reference in the control's precision, in the program's place:
    ``(scores, ids, field scores)`` of its pruned answer."""
    qw = _weighted(docs[likes], weights, dims)
    scores, keep, _ = _pruned_scores(
        docs, leaders, buckets, qw, likes, probes_t, CONTROL, False)
    s, i = jax.lax.top_k(jnp.where(keep, scores, -jnp.inf), k)
    i = jnp.where(jnp.isfinite(s), i, -1)
    return s, i, _fields(docs, qw, i, field_bounds(dims), CONTROL)


def answer_gaps(ids, scores, fields, excluded, ps, rp, rf) -> np.ndarray:
    """Per request, the widest gap by which an answer falls short of the
    reference: its document at rank ``r`` scoring below the reference's
    ``r``-th best (``ps``, over the buckets every sound navigation probes:
    probing more can only raise an answer), or its own score or field split differing from the
    reference's reading of that document. A duplicate, the request's own
    document, or a missing rank the reference fills is an infinite gap."""
    ids, scores, fields = np.asarray(ids), np.asarray(scores), np.asarray(fields)
    ps, rp, rf = np.asarray(ps), np.asarray(rp), np.asarray(rf)
    valid = ids >= 0
    with np.errstate(invalid="ignore"):
        gap = np.maximum(ps - rp, np.abs(scores - rp))
        gap = np.maximum(gap, np.max(np.abs(fields - rf), axis=-1))
    gap = np.where(valid, gap, np.where(np.isfinite(ps), np.inf, 0.0))
    out = np.max(gap, axis=-1)
    srt = np.sort(np.where(valid, ids, -1 - np.arange(ids.shape[1])), axis=-1)
    dup = np.any(srt[:, 1:] == srt[:, :-1], axis=-1)
    own = np.any(ids == np.asarray(excluded)[:, None], axis=-1)
    return np.where(dup | own, np.inf, out)


def recall(ids, exact_ids) -> np.ndarray:
    """CR/k per request: the share of the exact top-k the answer holds."""
    ids, exact_ids = np.asarray(ids), np.asarray(exact_ids)
    hit = (ids[:, :, None] == exact_ids[:, None, :]) & (ids[:, :, None] >= 0)
    return hit.any(-1).sum(-1) / exact_ids.shape[1]


@functools.partial(jax.jit, static_argnames=("t", "control"))
def _index_block(docs_blk, leaders_flat, assign_blk, *, t, control):
    """For a block of documents: the widest gap of the program's assignment
    (``assign_blk (T, r)``) and of the control's, and each leader's most
    similar document of the block."""
    sims = matmul(docs_blk, leaders_flat.T, HIGHEST)         # (r, T*K)
    per = sims.reshape(docs_blk.shape[0], t, -1)
    best = jnp.max(per, -1)

    def gap(assign):                                        # (r, T)
        mine = jnp.take_along_axis(per, assign[:, :, None], -1)[..., 0]
        return jnp.max(best - mine)

    cgap = jnp.float32(0.0)
    if control:
        low = matmul(docs_blk, leaders_flat.T, FP8).reshape(per.shape)
        cgap = gap(jnp.argmax(low, -1))
    return gap(assign_blk.T), cgap, jnp.max(sims, 0), jnp.argmax(sims, 0)


@jax.jit
def spread(leaders):
    """Per clustering, the mean over leaders of the similarity to the most
    similar other leader: low where FPF has spread them, high where they
    crowd (``leaders (T, K, D)``)."""
    s = jnp.einsum("tkd,tjd->tkj", leaders, leaders,
                   precision=jax.lax.Precision.HIGHEST)
    own = jnp.eye(leaders.shape[1], dtype=bool)
    return jnp.mean(jnp.max(jnp.where(own, -jnp.inf, s), -1), -1)


@functools.partial(jax.jit, static_argnames=("k",))
def _fpf_medoids(docs, sample, first, *, k):
    """One clustering the paper's way: FPF (each center the sample document
    least similar to every center before it) over the documents ``sample``,
    every document assigned to its most similar center, then each center
    replaced by its cluster's medoid, the member most similar to the
    cluster's normalised centroid."""
    xs = docs[sample]
    n = docs.shape[0]

    def body(i, carry):
        centers, near = carry
        sim = matmul(xs, xs[centers[i - 1]][:, None], HIGHEST)[:, 0]
        near = jnp.maximum(near, sim)
        return centers.at[i].set(jnp.argmin(near).astype(jnp.int32)), near

    centers = jnp.zeros((k,), jnp.int32).at[0].set(first)
    centers, _ = jax.lax.fori_loop(
        1, k, body, (centers, jnp.full((xs.shape[0],), -jnp.inf)))
    assign = jnp.argmax(matmul(docs, xs[centers].T, HIGHEST), -1)
    cent = jax.ops.segment_sum(docs, assign, k)
    cent = cent / jnp.maximum(
        jnp.linalg.norm(cent, axis=-1, keepdims=True), 1e-12)
    score = jnp.sum(docs * cent[assign], -1)
    best = jax.ops.segment_max(score, assign, k)
    cand = jnp.where(score >= best[assign], jnp.arange(n), n)
    med = jax.ops.segment_min(cand, assign, k)
    return jnp.where((med < n)[:, None], docs[jnp.minimum(med, n - 1)],
                     xs[centers])


def fpf_leaders(docs, k: int, t: int, seed: int):
    """``(T, K, D)`` leaders of the reference's own T clusterings, each over
    a sample of ``ceil(sqrt(K n))`` documents (the paper's size) drawn from
    the seed."""
    n = int(docs.shape[0])
    m = max(k, min(n, math.ceil(math.sqrt(k * n))))
    out = []
    for i in range(t):
        rng = np.random.default_rng([int(seed), _FPF_STREAM, i])
        sample = jnp.asarray(rng.choice(n, m, replace=False), jnp.int32)
        out.append(_fpf_medoids(docs, sample, int(rng.integers(m)), k=k))
    return jnp.stack(out)


def check_index(docs, docs_np, leaders, buckets, counts, seed: int, *,
                control: bool = False, rows: int = 8192) -> dict:
    """The program's index against the reference:

    - ``faults``: documents not in exactly one bucket of a clustering,
      buckets whose count is wrong, and leaders that are no corpus document
      bit for bit;
    - ``assign_gap``: the widest similarity by which a document's leader
      lies below its most similar leader of the same clustering
      (``control_assign_gap``: the same of the control's assignment, with
      ``control``);
    - ``leader_gap``: by how much, in the worst clustering, the program's
      leaders crowd more than the reference FPF's (``spread``)."""
    leaders, buckets = np.asarray(leaders), np.asarray(buckets)
    counts = np.asarray(counts)
    t, kk, _ = buckets.shape
    n = docs_np.shape[0]
    faults = 0
    assign = np.zeros((t, n), np.int32)
    for i in range(t):
        live = buckets[i] < n
        faults += int(np.sum(live.sum(-1) != counts[i]))
        seen = np.bincount(buckets[i][live], minlength=n)
        faults += int(np.sum(seen != 1))
        rows_k = np.broadcast_to(np.arange(kk)[:, None], buckets[i].shape)
        assign[i, buckets[i][live]] = rows_k[live]
    flat = jnp.asarray(leaders.reshape(t * kk, -1))
    best_s = np.full((t * kk,), -np.inf, np.float32)
    best_i = np.zeros((t * kk,), np.int64)
    gap = cgap = 0.0
    for a in range(0, n, rows):
        b = min(n, a + rows)
        g, cg, s, j = _index_block(docs[a:b], flat,
                                   jnp.asarray(assign[:, a:b]), t=t,
                                   control=control)
        gap, cgap = max(gap, float(g)), max(cgap, float(cg))
        s, j = np.asarray(s), np.asarray(j)
        better = s > best_s
        best_s = np.where(better, s, best_s)
        best_i = np.where(better, j + a, best_i)
    same = np.all(docs_np[best_i] == leaders.reshape(t * kk, -1), axis=-1)
    faults += int(np.sum(~same))
    mine = np.asarray(spread(jnp.asarray(leaders)))
    ref = np.asarray(spread(fpf_leaders(docs, kk, t, seed)))
    return {"faults": faults, "assign_gap": gap, "control_assign_gap": cgap,
            "leader_gap": float(np.max(mine - ref)),
            "spread": mine.tolist(), "reference_spread": ref.tolist()}
