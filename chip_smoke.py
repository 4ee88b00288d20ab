#!/usr/bin/env python3
"""Bring-up smoke test: the cluster-pruned search end to end on a TPU.

    python chip_smoke.py            # one chip, the paper's TS1 corpus
    python chip_smoke.py --chips 4  # four chips, TS2 (the pick shards it)

One chip builds the TS1 corpus (53,722 docs, D=4096 over three fields,
K=500, T=3, fp32, seeded) through ``Retriever.build`` with the defaults the
platform picks (``fpf_fused`` builds, ``fused`` serves), then:

(a) prints the resolved clusterer and backend and the build seconds;
(b) serves 64 more-like-this requests, each with its own Dirichlet field
    weights, at ``probes=12``, k=10, and checks ids and scores against the
    ``reference`` engine on the same index, printing CR/k and NAG against
    brute force;
(c) serves the same requests with ``exact=True`` and checks them against
    brute force id for id;
(d) serves them through the async ``SearchServer`` and checks them against
    the synchronous answers;
(e) prints the device kind, the compile seconds and the device bytes in use.

``--chips 4`` runs only the multi-chip path and what it is compared with:
TS2 (100,000 docs, K=1000; its fp32 pack does not fit one chip) built with
``backend="auto"``, which has to resolve to ``sharded``, then phases (a)-(e)
as above, with each device's share of the pack and its bytes in use
printed.

Any failed check raises, so the script exits non-zero. It also exits
non-zero, printing no result, where JAX finds no TPU. The last line of a
passing run is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# Score agreement between two fp32 scoring paths that sum in different
# orders (Pallas kernel, XLA einsum, brute force), all at full precision.
SCORE_RTOL, SCORE_ATOL = 1e-5, 1e-6


def corpus_config(name: str) -> dict:
    """The paper's test sets as ``benchmarks/common.py`` sizes them."""
    sys.path.insert(0, str(ROOT))
    from benchmarks.common import bench_sizes

    return bench_sizes(name)


class CompileClock:
    """Seconds spent in XLA backend compiles (persistent-cache reads
    included, so a warm cache shows as fewer seconds) and cache hits."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip smoke check failed: {what}")


def _compare(ids_a, s_a, ids_b, s_b):
    """``(rows whose ids differ, rows whose difference is not a near-tie
    reordering)``. Two fp32 paths that sum in different orders may swap
    two documents whose scores agree within the tolerance; anything else
    is a wrong answer."""
    import numpy as np

    ids_a, ids_b = np.asarray(ids_a), np.asarray(ids_b)
    s_a, s_b = np.asarray(s_a), np.asarray(s_b)
    differ = np.any(ids_a != ids_b, axis=-1)
    wrong = 0
    for r in np.flatnonzero(differ):
        kth = float(s_b[r, -1])
        tol = SCORE_ATOL + SCORE_RTOL * abs(kth)
        # a document only one side returned must score at the k-th score
        edge = all(
            abs(float(s[r][list(ids[r]).index(d)]) - kth) <= tol
            for ids, s, other in ((ids_a, s_a, ids_b), (ids_b, s_b, ids_a))
            for d in set(ids[r]) - set(other[r])
        )
        close = np.allclose(s_a[r], s_b[r], rtol=SCORE_RTOL, atol=SCORE_ATOL)
        wrong += int(not (close and edge))
    return int(differ.sum()), wrong


def run_phases(
    cfg: dict,
    *,
    method: str = "auto",
    backend: str = "auto",
    n_requests: int = 64,
    probes: int = 12,
    k: int = 10,
    seed: int = 0,
    log=print,
) -> dict:
    """Phases (a)-(e) over the corpus ``cfg`` (:func:`corpus_config`
    keys). ``method``/``backend`` go to ``Retriever.build`` unchanged —
    ``"auto"`` is the platform's own pick. Returns what was measured;
    raises on any failed check."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import (
        Retriever,
        brute_force_bottomk,
        brute_force_topk,
        competitive_recall,
        get_engine,
        normalized_aggregate_goodness,
        weighted_query,
    )
    from repro.data import CorpusConfig, make_corpus
    from repro.launch.serve import make_requests, serve_async

    clock = CompileClock()
    out: dict = {}

    # (a) build ---------------------------------------------------------
    corpus = CorpusConfig(
        n_docs=cfg["n_docs"], field_dims=tuple(cfg["field_dims"]),
        vocab_sizes=tuple(cfg["vocab_sizes"]), n_topics=cfg["n_topics"],
        topic_mix_alpha=cfg["topic_mix_alpha"],
        noise_terms=tuple(cfg["noise_terms"]), seed=seed,
    )
    docs_np, spec, _ = make_corpus(corpus)
    docs = jax.block_until_ready(jnp.asarray(docs_np))
    t0 = time.perf_counter()
    retriever = Retriever.build(
        docs, spec, cfg["k_clusters"], method=method, backend=backend,
        key=jax.random.PRNGKey(seed),
    )
    index = retriever.index
    jax.block_until_ready((index.leaders, index.buckets))
    out["build_s"] = time.perf_counter() - t0
    out["clusterer"], out["backend"] = index.method, retriever.backend
    t_cl, k_cl, b = (int(x) for x in index.buckets.shape)
    log(f"[a] build: clusterer={index.method} backend={retriever.backend} "
        f"n={index.n_docs} D={spec.total_dim} T={t_cl} K={k_cl} B={b} "
        f"in {out['build_s']:.2f} s")

    # (b) pruned search vs reference, quality vs brute force -------------
    rng = np.random.default_rng(seed)
    qids = rng.choice(index.n_docs, n_requests, replace=False)
    w = rng.dirichlet([1.0] * spec.s, size=n_requests).astype(np.float32)
    reqs = make_requests(qids, w, spec, probes=probes, k=k)
    t0 = time.perf_counter()
    resps = retriever.search(reqs)
    first_s = time.perf_counter() - t0
    ids = np.stack([r.doc_ids for r in resps])
    scores = np.stack([r.scores for r in resps])
    ref = retriever.search(
        make_requests(qids, w, spec, probes=probes, k=k, backend="reference")
    )
    ref_ids = np.stack([r.doc_ids for r in ref])
    ref_s = np.stack([r.scores for r in ref])
    differ, wrong = _compare(ids, scores, ref_ids, ref_s)
    _check(wrong == 0, f"{wrong} of {n_requests} answers differ from "
                       "the reference engine")
    qw = weighted_query(docs[qids], jnp.asarray(w), spec)
    excl = jnp.asarray(qids, jnp.int32)
    gt_s, gt_i = brute_force_topk(docs, qw, k, exclude=excl)
    far_s, _ = brute_force_bottomk(docs, qw, k, exclude=excl)
    cr = float(jnp.mean(competitive_recall(jnp.asarray(ids), gt_i))) / k
    nag = float(jnp.mean(normalized_aggregate_goodness(
        jnp.asarray(scores), gt_s, far_s
    )))
    _check(0.0 < cr <= 1.0 and np.isfinite(nag), f"CR/k={cr} NAG={nag}")
    out.update(ref_differ=differ, cr=cr, nag=nag, first_search_s=first_s)
    log(f"[b] {n_requests} MLT requests probes={probes} k={k} on "
        f"{resps[0].backend}: first call {first_s:.2f} s; vs reference: "
        f"{n_requests - differ}/{n_requests} id-identical, {wrong} wrong "
        f"({differ - wrong} near-tie reorderings); CR/k={cr:.4f} "
        f"NAG={nag:.4f}")

    # (c) exact tier vs brute force -------------------------------------
    ex = retriever.search(make_requests(qids, w, spec, k=k, exact=True))
    ex_ids = np.stack([r.doc_ids for r in ex])
    ex_s = np.stack([r.scores for r in ex])
    tiers = sorted({r.tier for r in ex})
    differ, wrong = _compare(ex_ids, ex_s, gt_i, gt_s)
    _check(tiers == ["exact"], f"exact requests answered by tiers {tiers}")
    _check(wrong == 0, f"{wrong} exact-tier answers differ from brute force")
    out["exact_mismatches"] = differ
    log(f"[c] exact tier on {ex[0].backend}: {differ} mismatches vs brute "
        f"force ({wrong} beyond near-ties)")

    # (d) async serving tier vs the synchronous answers -----------------
    retriever._flush_request_caches()       # answer from the engine
    t0 = time.perf_counter()
    async_resps, stats, _ = serve_async(retriever, reqs)
    a_ids = np.stack([r.doc_ids for r in async_resps])
    a_s = np.stack([r.scores for r in async_resps])
    same_ids = int(np.sum(np.all(a_ids == ids, axis=-1)))
    close = np.allclose(a_s, scores, rtol=SCORE_RTOL, atol=SCORE_ATOL)
    _check(same_ids == n_requests and close,
           f"async tier: {n_requests - same_ids} answers differ from "
           "the synchronous path")
    out["async_s"] = time.perf_counter() - t0
    log(f"[d] SearchServer: {same_ids}/{n_requests} id- and "
        f"score-identical to sync search ({stats})")

    # (e) device, compile time, memory ----------------------------------
    devs = jax.devices()
    in_use = [
        int((d.memory_stats() or {}).get("bytes_in_use", -1)) for d in devs
    ]
    out.update(compile_s=clock.seconds, cache_hits=clock.cache_hits,
               bytes_in_use=in_use)
    if retriever.backend == "sharded":
        n_sh = get_engine(index, "sharded").n_shards
        data = index.ensure_local_bucket_major(n_sh)[0]
        shares = sorted(
            (str(s.device), int(s.data.nbytes)) for s in data.addressable_shards
        )
        _check(
            len({d for d, _ in shares}) == n_sh
            and all(nb * n_sh == data.nbytes for _, nb in shares),
            f"pack shards {shares} are not one {n_sh}th each",
        )
        out["pack_share_bytes"] = shares[0][1]
        log(f"[e] sharded pack: {data.nbytes} bytes, {shares[0][1]} on "
            f"each of {n_sh} devices")
    log(f"[e] device={devs[0].device_kind} x{len(devs)} compile "
        f"{clock.seconds:.2f} s ({clock.cache_hits} persistent-cache hits) "
        f"bytes_in_use={in_use}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: TS1 on the platform defaults; 4: TS2 sharded")
    args = ap.parse_args(argv)
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is not beside this "
              f"script ({e})", file=sys.stderr)
        return 2
    enable_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} devices",
              file=sys.stderr)
        return 1
    if args.chips == 1:
        out = run_phases(corpus_config("ts1"))
        _check(out["clusterer"] == "fpf_fused" and out["backend"] == "fused",
               f"platform picked {out['clusterer']} + {out['backend']}, "
               "not fpf_fused + fused")
    else:
        out = run_phases(corpus_config("ts2"))
        _check(out["backend"] == "sharded",
               f"TS2 on {len(devs)} chips resolved to {out['backend']}, "
               "not sharded")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
