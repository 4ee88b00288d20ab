"""Retrieval serving driver — the paper's system end to end.

Builds the corpus and the FPF multi-clustering index behind a
:class:`repro.core.Retriever`, then serves batched more-like-this
:class:`repro.core.SearchRequest` objects with per-request dynamic field
weights (the paper's setting) and verifies quality online against exact
brute force:

    PYTHONPATH=src python -m repro.launch.serve --docs 20000 --queries 64 \
        --probes 12 --k 10 --backend fused

``--backend`` selects the execution path (``auto`` picks fused on a TPU or a
multi-device host, reference otherwise); ``--compare`` serves the
same request batch through every runnable backend on the same index and
prints a per-backend latency/recall table. ``--recall-target 0.9`` replaces
the fixed ``--probes`` budget with a recall target served by the per-index
calibrated planner (the index is calibrated right after build — sample
queries x weight draws, probe sweep, isotonic fit), and the report prints
the planner's predicted recall next to the achieved one, so the target is
honest, not nominal. ``--exact`` serves every request through the clustered
exact tier (all T·K buckets swept) and hard-checks the answers against
brute force id-for-id; ``--min-recall r`` arms the recall-floor escalation
— requests start at the ``--probes`` budget and re-run at higher calibrated
rungs (ultimately the exact tier) whenever predicted recall sits below the
floor, with the tier histogram and escalation count printed next to the
achieved recall. ``--mutate N`` exercises the index's incremental
maintenance mid-serve: N new documents are ingested through
``retriever.add`` (streamed into the padded buckets, NO rebuild), verified
retrievable, then removed again and verified gone — the serving loop never
restarts. ``--serve`` additionally drives the SAME requests through the
async micro-batching tier (:mod:`repro.serving`) as concurrent submits and
asserts the batched responses are id/score-identical to the synchronous
one-by-one path — the end-to-end proof that micro-batching changes latency,
never answers. The raw ``(scores, ids,
n_scored)`` tuple surface lives only inside :mod:`repro.core.engine` — this
driver speaks requests and responses exclusively. LM serving
(prefill/decode) lives in examples/serve_lm.py; this driver is the paper's
own serving loop. ``--profile-port N`` starts JAX's profiler server, from
which ``python -m jax.collect_profile N <ms> --log_dir <dir>`` (or
TensorBoard's profile plugin) records the program's layer spans
(:mod:`repro.tracing`) beside the device's trace.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    Retriever,
    SearchRequest,
    available_backends,
    brute_force_bottomk,
    brute_force_topk,
    competitive_recall,
    normalized_aggregate_goodness,
    weighted_query,
)
from repro.data import CorpusConfig, make_corpus

__all__ = ["build_index", "build_retriever", "make_requests",
           "serve_requests", "serve_async", "main"]


def build_index(n_docs: int = 20_000, *, k_clusters: int | None = None,
                n_clusterings: int = 3, seed: int = 0, pack_dtype=None):
    from repro.core import ClusterPruneIndex

    docs_np, spec, _ = make_corpus(CorpusConfig(n_docs=n_docs, seed=seed))
    docs = jnp.asarray(docs_np)
    if k_clusters is None:
        k_clusters = max(16, int(np.sqrt(n_docs)))
    index = ClusterPruneIndex.build(
        docs, spec, k_clusters, n_clusterings=n_clusterings,
        key=jax.random.PRNGKey(seed), pack_dtype=pack_dtype,
    )
    return index, docs, spec


def build_retriever(n_docs: int = 20_000, *, backend: str = "auto",
                    k_clusters: int | None = None, n_clusterings: int = 3,
                    seed: int = 0, pack_dtype=None, calibrate: bool = False,
                    calibrate_opts=None):
    """Corpus + index + facade in one call -> (retriever, docs, spec).

    ``calibrate=True`` arms lazy planner calibration: the first
    ``recall_target=`` request fits the per-index probe ladder
    (``calibrate_opts`` passes sampling options through). ``pack_dtype``
    sets the bucket-major storage precision the fused backend scores from
    (bf16 halves, int8 quarters the packed bytes).
    """
    index, docs, spec = build_index(
        n_docs, k_clusters=k_clusters, n_clusterings=n_clusterings,
        seed=seed, pack_dtype=pack_dtype,
    )
    retriever = Retriever(index, backend=backend, calibrate=calibrate,
                          calibrate_opts=calibrate_opts)
    return retriever, docs, spec


def make_requests(qids, weights, spec, *, probes: int | None = None,
                  k: int = 10, recall_target: float | None = None,
                  backend: str | None = None, exact: bool = False,
                  min_recall: float | None = None) -> list[SearchRequest]:
    """Per-user more-like-this requests with field-name weights.

    One request per query document id; each carries its own dynamic weight
    dict (the paper's per-query user weights). MLT requests self-exclude
    automatically. Give either an explicit ``probes`` budget or a
    ``recall_target`` the retriever's calibrated planner maps to one —
    or ``exact=True`` for the full-sweep exact tier (any budget args are
    ignored: the tier pins its own). ``min_recall`` arms the recall-floor
    escalation on every request.
    """
    weights = np.asarray(weights, np.float32)
    if exact:
        probes = recall_target = min_recall = None
    return [
        SearchRequest(
            like=int(qid),
            weights=dict(zip(spec.names, map(float, w))),
            probes=probes, k=k, recall_target=recall_target, backend=backend,
            exact=exact, min_recall=min_recall,
        )
        for qid, w in zip(np.asarray(qids), weights)
    ]


def serve_requests(retriever: Retriever, requests):
    """Serve a batch through the facade -> list[SearchResponse]."""
    return retriever.search(requests)


def serve_async(retriever: Retriever, requests, *, window_s: float = 0.002,
                replicas: int = 1, deadline_s: float | None = None,
                chaos: str | None = None, seed: int = 0):
    """Drive requests through the async micro-batching tier.

    Every request is submitted concurrently (the serving tier's intended
    traffic shape — the micro-batch window coalesces them into engine-sized
    batches). Returns ``(responses, stats_line, health)`` with responses in
    request order; each response carries the per-request ``queue_wait_s`` /
    ``compute_s`` latency split stamped by the server, and ``health`` is
    the final per-replica health snapshot (breaker state, EWMA latency,
    success/failure counts).

    ``chaos`` names a fault profile from
    :data:`repro.serving.FAULT_PROFILES` to inject into the replica pool;
    under chaos an individual response slot may hold a typed serving
    exception (:class:`~repro.serving.ServingError`) instead of a
    response — a typed failure is an acceptable chaos outcome, a hang or
    a silent wrong answer is not.
    """
    import asyncio

    from repro.serving import FaultPolicy, ResilienceConfig, SearchServer

    policy = FaultPolicy.named(chaos, seed=seed) if chaos else None
    cfg = ResilienceConfig(seed=seed) if chaos else None
    # Fault handling is per dispatch: one giant coalesced batch gives the
    # breaker/retry machinery a single roll of the dice, so under chaos cap
    # the batch size to spread work across replicas.
    max_batch = 8 if chaos else None  # None -> default_max_batch

    async def _run():
        async with SearchServer(retriever, window_s=window_s,
                                replicas=replicas, max_batch=max_batch,
                                resilience=cfg,
                                fault_policy=policy) as server:
            resps = await asyncio.gather(
                *(server.submit(r, deadline_s=deadline_s)
                  for r in requests),
                return_exceptions=bool(chaos),
            )
            line = server.stats.format_line()
            health = server.pool.health_snapshot()
        return list(resps), line, health

    return asyncio.run(_run())


def main():
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--docs", type=int, default=20_000)
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--probes", type=int, default=12)
    ap.add_argument("--recall-target", type=float, default=None,
                    help="plan probes from a recall target via the per-index "
                         "calibrated ladder (overrides --probes; the index "
                         "is calibrated after build)")
    ap.add_argument("--exact", action="store_true",
                    help="serve every request through the exact tier (all "
                         "T*K buckets swept); the report hard-checks the "
                         "answers against brute force id-for-id")
    ap.add_argument("--min-recall", type=float, default=None,
                    help="recall floor: requests run at the --probes budget "
                         "but ESCALATE through the calibrated ladder rungs "
                         "(ultimately the exact tier) whenever predicted "
                         "recall falls below the floor; the index is "
                         "calibrated after build")
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default="auto",
                    choices=("auto",) + available_backends(),
                    help="search engine backend (auto = platform pick)")
    ap.add_argument("--compare", action="store_true",
                    help="serve the same requests through every runnable "
                         "backend and report per-backend latency")
    ap.add_argument("--serve", action="store_true",
                    help="also drive the requests through the async "
                         "micro-batching serving tier (repro.serving) as "
                         "concurrent submits and verify id/score parity "
                         "against the synchronous one-by-one path")
    ap.add_argument("--window-ms", type=float, default=2.0,
                    help="--serve micro-batch window")
    ap.add_argument("--replicas", type=int, default=1,
                    help="--serve parallel dispatch slots")
    ap.add_argument("--chaos", default=None, metavar="PROFILE",
                    help="inject a named fault profile (repro.serving."
                         "FAULT_PROFILES, e.g. hang_flap) into the --serve "
                         "replica pool and print the per-replica health "
                         "report; implies --serve, and sizes the pool to "
                         "at least 4 replicas so every profile index is "
                         "populated")
    ap.add_argument("--mutate", type=int, default=0, metavar="N",
                    help="after serving, add N new documents through "
                         "retriever.add (incremental bucket maintenance, no "
                         "rebuild), verify they are retrievable, then remove "
                         "them and verify they are gone")
    ap.add_argument("--profile-port", type=int, default=None, metavar="N",
                    help="start JAX's profiler server on port N, so that a "
                         "profiler client can capture the program's spans "
                         "(repro.tracing) and the device trace from the "
                         "live process")
    args = ap.parse_args()
    if args.profile_port is not None:
        jax.profiler.start_server(args.profile_port)
    if args.exact and (args.recall_target is not None
                       or args.min_recall is not None):
        ap.error("--exact already guarantees recall 1.0; it cannot combine "
                 "with --recall-target or --min-recall")
    if args.chaos is not None:
        from repro.serving import FAULT_PROFILES

        if args.chaos not in FAULT_PROFILES:
            ap.error(f"--chaos {args.chaos!r}: unknown profile; known: "
                     f"{', '.join(sorted(FAULT_PROFILES))}")
        args.serve = True
        args.replicas = max(args.replicas, 4)

    t0 = time.time()
    retriever, docs, spec = build_retriever(
        args.docs, backend=args.backend, seed=args.seed,
    )
    index = retriever.index
    print(f"[serve] index built in {time.time() - t0:.1f}s "
          f"(K={index.leaders.shape[1]}, T={index.leaders.shape[0]})")

    if args.recall_target is not None or args.min_recall is not None:
        from repro.core import calibrate_index

        t0 = time.time()
        # seed+1: the serving queries below are drawn with args.seed, so the
        # printed achieved-vs-predicted recall is measured on HELD-OUT
        # queries/weights, not the calibration set itself.
        ladder = calibrate_index(index, seed=args.seed + 1)
        rungs = ", ".join(
            f"{p}->{r:.2f}" for p, r in zip(ladder.probes, ladder.recall)
        )
        print(f"[serve] planner calibrated in {time.time() - t0:.1f}s "
              f"(probes->recall: {rungs})")

    rng = np.random.default_rng(args.seed)
    qids = rng.choice(args.docs, args.queries, replace=False)
    # per-request dynamic weights (the paper's setting)
    w = rng.dirichlet([1.0] * spec.s, size=args.queries).astype(np.float32)

    # Exact ground truth: identical across backends, computed once from the
    # same §4 reduction the retriever applies internally.
    qw = weighted_query(docs[qids], jnp.asarray(w), spec)
    exclude = jnp.asarray(qids, jnp.int32)
    gt_s, gt_i = brute_force_topk(docs, qw, args.k, exclude=exclude)
    far_s, _ = brute_force_bottomk(docs, qw, args.k, exclude=exclude)

    backends = (
        list(available_backends()) if args.compare else [retriever.backend]
    )
    report = []
    sample = None
    for name in backends:
        if args.exact:
            requests = make_requests(
                qids, w, spec, k=args.k, backend=name, exact=True,
            )
        elif args.recall_target is not None:
            requests = make_requests(
                qids, w, spec, recall_target=args.recall_target, k=args.k,
                backend=name, min_recall=args.min_recall,
            )
        else:
            requests = make_requests(
                qids, w, spec, probes=args.probes, k=args.k, backend=name,
                min_recall=args.min_recall,
            )
        responses = serve_requests(retriever, requests)
        dt = responses[0].latency_s           # whole-batch engine wall time
        served = responses[0].backend
        if sample is None:
            sample = responses[0]
        ids = np.stack([r.doc_ids for r in responses])
        scores = np.stack([r.scores for r in responses])
        n_scored = np.asarray([r.n_scored for r in responses], np.float32)
        cr = float(jnp.mean(competitive_recall(jnp.asarray(ids), gt_i)))
        nag = float(jnp.mean(normalized_aggregate_goodness(
            jnp.asarray(scores), gt_s, far_s
        )))
        frac = float(np.mean(n_scored)) / args.docs
        report.append((served, dt, cr, nag, frac))
        print(f"[serve] backend={served}: {args.queries} requests in "
              f"{dt * 1e3:.1f} ms ({dt / args.queries * 1e3:.2f} ms/request)")
        planner = ""
        if args.recall_target is not None:
            planner = (f" [target {args.recall_target:.2f}, planner "
                       f"predicted {responses[0].predicted_recall:.2f} "
                       f"@ {responses[0].probes} probes]")
        print(f"[serve] backend={served}: recall@{args.k} = "
              f"{cr:.2f}/{args.k}, NAG = {nag:.4f}, "
              f"scored {frac:.1%} of corpus{planner}")
        if args.exact or args.min_recall is not None:
            tiers: dict[str, int] = {}
            for resp in responses:
                tiers[resp.tier] = tiers.get(resp.tier, 0) + 1
            esc = sum(resp.escalations for resp in responses)
            print(f"[serve] backend={served}: tiers {tiers}, "
                  f"{esc} escalations")
        if args.exact:
            # exact tier contract: id-for-id identical to brute force
            wrong = int(np.sum(np.any(ids != np.asarray(gt_i), axis=-1)))
            print(f"[serve] backend={served}: exact-tier parity vs brute "
                  f"force: {wrong} mismatches "
                  f"({'OK' if wrong == 0 else 'FAIL'})")
            if wrong:
                raise SystemExit(
                    f"[serve] exact tier returned {wrong} answers "
                    f"differing from brute force"
                )
        if args.min_recall is not None:
            achieved = cr / args.k
            ok = achieved >= args.min_recall - 0.05   # held-out queries
            print(f"[serve] backend={served}: recall floor "
                  f"{args.min_recall:.2f}: achieved {achieved:.2f} "
                  f"({'OK' if ok else 'FAIL'})")
            if not ok:
                raise SystemExit(
                    f"[serve] min-recall floor {args.min_recall} missed: "
                    f"achieved {achieved:.2f} on held-out queries"
                )

    if sample is not None and sample.hits:
        best = sample.hits[0]
        parts = ", ".join(
            f"{n}={v:.3f}" for n, v in best.field_scores.items()
        )
        print(f"[serve] sample hit for doc {int(qids[0])}: "
              f"doc {best.doc_id} score {best.score:.3f} ({parts})")

    if args.serve:
        # Async tier end to end: the same query set, submitted concurrently
        # through the micro-batching front against the retriever's own
        # backend (the compare loop may have left ``requests`` pinned to
        # another). Flush the facade caches first — the sync pass above
        # already answered these queries, and a cache hit would let the
        # async path skip the engine entirely.
        requests = make_requests(
            qids, w, spec, k=args.k,
            probes=(None if args.recall_target is not None or args.exact
                    else args.probes),
            recall_target=args.recall_target,
            exact=args.exact, min_recall=args.min_recall,
        )
        retriever._flush_request_caches()
        if args.chaos:
            from repro.serving import FaultPolicy

            print(f"[serve] chaos: injecting "
                  f"{FaultPolicy.named(args.chaos, seed=args.seed).describe()} "
                  f"across {args.replicas} replicas")
        t0 = time.time()
        async_resps, stats_line, health = serve_async(
            retriever, requests, window_s=args.window_ms / 1e3,
            replicas=args.replicas, chaos=args.chaos, seed=args.seed,
        )
        dt = time.time() - t0
        retriever._flush_request_caches()
        one_by_one = [retriever.search(r) for r in requests]
        # Under chaos a slot may hold a typed failure or a degraded=True
        # answer — both are honest outcomes; a non-degraded response that
        # differs from the synchronous path is the only lie.
        ok_resps = [r for r in async_resps if not isinstance(r, Exception)]
        failed = len(async_resps) - len(ok_resps)
        degraded = sum(1 for r in ok_resps if r.degraded)
        mismatches = sum(
            1 for a, b in zip(async_resps, one_by_one)
            if not isinstance(a, Exception) and not a.degraded
            and (list(a.doc_ids) != list(b.doc_ids)
                 or not np.allclose(a.scores, b.scores,
                                    rtol=1e-5, atol=1e-6))
        )
        waits = np.asarray([r.queue_wait_s for r in ok_resps]) * 1e3
        comps = np.asarray([r.compute_s for r in ok_resps]) * 1e3
        print(f"[serve] async tier: {len(requests)} concurrent submits in "
              f"{dt * 1e3:.1f} ms (mean batch "
              f"{np.mean([r.batch_size for r in ok_resps]):.1f}, wait "
              f"p50 {np.percentile(waits, 50):.1f} ms, compute p50 "
              f"{np.percentile(comps, 50):.1f} ms)")
        print(f"[serve] async stats: {stats_line}")
        if args.chaos:
            print(f"[serve] chaos outcome: {len(ok_resps)} answered "
                  f"({degraded} degraded), {failed} failed typed")
            for h in health:
                print(f"[serve] replica {h['idx']}: {h['state']:>9} "
                      f"ewma={h['ewma_ms']} ms, "
                      f"{h['successes']}/{h['dispatches']} ok, "
                      f"{h['timeouts']} timeouts, trips "
                      f"{h['trips']}/{h['recoveries']} recovered")
        print(f"[serve] async parity vs one-by-one: {mismatches} "
              f"mismatches ({'OK' if mismatches == 0 else 'FAIL'})")
        if mismatches:
            raise SystemExit(
                f"[serve] async serving tier returned {mismatches} "
                f"responses differing from the synchronous path"
            )

    if len(report) > 1:
        print("\n[serve] per-backend latency (same index, same requests)")
        print("backend,ms_per_request,recall,nag,corpus_scanned")
        for name, dt, cr, nag, frac in report:
            print(f"{name},{dt / args.queries * 1e3:.3f},{cr:.2f},"
                  f"{nag:.4f},{frac:.3f}")

    if args.mutate > 0:
        # Incremental maintenance round-trip: ingest exact copies of the
        # first N query documents — a copy is its original's true nearest
        # neighbour, so "the copy is hit #1 for like=original" is a sharp
        # end-to-end check that adds really land in the probed buckets.
        n_mut = min(args.mutate, args.queries)
        src = qids[:n_mut]
        t0 = time.time()
        new_ids = retriever.add(docs[src])
        dt_add = time.time() - t0
        reqs = make_requests(src, w[:n_mut], spec, probes=args.probes,
                             k=args.k)
        responses = serve_requests(retriever, reqs)
        found = sum(
            1 for r, nid in zip(responses, new_ids)
            if r.hits and r.hits[0].doc_id == int(nid)
        )
        print(f"[serve] mutate: added {n_mut} docs in {dt_add * 1e3:.1f} ms "
              f"(no rebuild, index now {retriever.index.n_live} live docs); "
              f"{found}/{n_mut} copies came back as hit #1")
        t0 = time.time()
        retriever.remove(new_ids)
        dt_rm = time.time() - t0
        responses = serve_requests(retriever, reqs)
        removed_set = set(map(int, new_ids))
        leaked = sum(
            1 for r in responses
            if any(h.doc_id in removed_set for h in r.hits)
        )
        print(f"[serve] mutate: removed them again in {dt_rm * 1e3:.1f} ms; "
              f"{leaked} leaked back into any top-k "
              f"({'OK' if leaked == 0 else 'FAIL'})")
        if found < n_mut or leaked:
            raise SystemExit(
                f"[serve] mutate round-trip failed: {found}/{n_mut} adds "
                f"retrieved, {leaked} removals leaked"
            )


if __name__ == "__main__":
    main()
