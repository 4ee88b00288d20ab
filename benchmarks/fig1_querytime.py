"""Paper Fig 1: average query time vs number of visited clusters.

The paper's claim: their scheme answers queries ~2x faster than CellDec at
equal visited-cluster budgets (fewer, sparser distance computations); we
additionally report the distance-computation count (hardware-independent
cost, the paper's own accounting) next to wall time.

Our system is measured through the typed retrieval API (``SearchRequest`` ->
``Retriever`` -> ``SearchResponse``), so the numbers include the full
serving surface users actually hit — weight resolution, probe planning,
decomposition — not just the kernel. The CellDec baseline predates the
engine seam and keeps its own direct path.

Since the engine refactor, every probe budget is also timed across all
registered search backends on the SAME built index (reference / fused) by
tagging requests with ``backend=``, so the layout/mechanism
cost is measured apples-to-apples. Note: off-TPU the fused backend runs the
Pallas kernel in interpret mode — its wall time there is a correctness
check, not a speed claim.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import (
    CellDecIndex, ClusterPruneIndex, Retriever, SearchRequest,
    available_backends, calibrate_index,
)
from repro.data import CorpusConfig, make_corpus

from .common import bench_sizes, std_parser, timed

K_NN = 10
FIG1_WEIGHTS = (0.6, 0.2, 0.2)


def _mlt_requests(qids, spec, *, probes, backend=None):
    wd = dict(zip(spec.names, FIG1_WEIGHTS))
    return [
        SearchRequest(like=int(q), weights=wd, probes=probes, k=K_NN,
                      backend=backend)
        for q in np.asarray(qids)
    ]


def run(scale: str = "quick", seed: int = 0, probe_grid=(3, 6, 9, 12, 18),
        backends=None, calibrate: bool = False):
    sz = bench_sizes(scale)
    docs_np, spec, _ = make_corpus(CorpusConfig(
        n_docs=sz["n_docs"], field_dims=sz["field_dims"],
        vocab_sizes=sz["vocab_sizes"], n_topics=sz["n_topics"],
        topic_mix_alpha=sz["topic_mix_alpha"],
        noise_terms=sz["noise_terms"], seed=seed,
    ))
    docs = jnp.asarray(docs_np)
    kc = sz["k_clusters"]
    key = jax.random.PRNGKey(seed)

    ours = ClusterPruneIndex.build(docs, spec, kc, n_clusterings=3,
                                   method="fpf", key=key)
    retriever = Retriever(ours, backend="reference")
    if calibrate:
        # Annotate each timed probe budget with the calibrated planner's
        # fitted recall, so the time-vs-quality tradeoff reads off one table.
        ladder = calibrate_index(ours, seed=seed)
        print("# planner (calibrated): " + ", ".join(
            f"probes {p} -> recall {ladder.predicted_recall(p):.2f}"
            for p in probe_grid))
    celldec = CellDecIndex.build(docs, spec, kc, method="kmeans", iters=10,
                                 key=key)

    rng = np.random.default_rng(seed)
    nq = min(64, sz["n_queries"])
    qids = jnp.asarray(rng.choice(sz["n_docs"], nq, replace=False), jnp.int32)
    queries = docs[qids]
    wv = jnp.tile(jnp.asarray(FIG1_WEIGHTS, jnp.float32)[None], (nq, 1))

    print(f"\n# Fig 1 — query time vs visited clusters (n={sz['n_docs']}, "
          f"{nq} queries)")
    print("probes,algo,ms_per_query,distance_computations_per_query")
    out = {}
    for probes in probe_grid:
        reqs = _mlt_requests(qids, spec, probes=probes)
        t_our, responses = timed(lambda r=reqs: retriever.search(r))
        dc_our = float(np.mean([resp.n_scored for resp in responses]))
        t_cd, (s2, i2, ns2) = timed(
            lambda p=probes: celldec.search_weighted(
                queries, wv, probes=p, k=K_NN, exclude=qids)
        )
        dc_cd = float(jnp.mean(jnp.asarray(ns2, jnp.float32)))
        print(f"{probes},our,{t_our / nq * 1e3:.3f},{dc_our:.0f}")
        print(f"{probes},celldec,{t_cd / nq * 1e3:.3f},{dc_cd:.0f}")
        out[probes] = (t_our / nq, dc_our, t_cd / nq, dc_cd)

    # -- backend sweep: same index, same requests, every mechanism ----------
    if backends is None:
        backends = available_backends()
    mid = probe_grid[len(probe_grid) // 2]
    print(f"\n# backends — same index, probes={mid} "
          f"(platform={jax.default_backend()}; fused is interpret-mode "
          f"off-TPU)")
    print("backend,ms_per_query,distance_computations_per_query,ids_match_ref")
    ref_resp = retriever.search(_mlt_requests(qids, spec, probes=mid))
    ids_ref = np.stack([r.doc_ids for r in ref_resp])
    for name in backends:
        reqs = _mlt_requests(qids, spec, probes=mid, backend=name)
        t_b, responses = timed(lambda r=reqs: retriever.search(r))
        ids_b = np.stack([r.doc_ids for r in responses])
        dc = float(np.mean([r.n_scored for r in responses]))
        match = bool(np.array_equal(ids_b, ids_ref))
        print(f"{name},{t_b / nq * 1e3:.3f},{dc:.0f},{match}")
        out[f"backend:{name}"] = (t_b / nq, dc)
    return out


if __name__ == "__main__":
    parser = std_parser(__doc__)
    parser.add_argument(
        "--calibrate", action="store_true",
        help="fit the per-index probe ladder and annotate each probe "
             "budget with its predicted recall")
    args = parser.parse_args()
    run(args.scale, args.seed, calibrate=args.calibrate)
