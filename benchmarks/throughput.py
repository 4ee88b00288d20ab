"""Serving throughput: QPS vs batch size, per search backend and pack dtype.

The paper reports per-query latency (Fig 1); a serving system's headline is
*throughput* — how many queries per second one host sustains when requests
arrive in batches. This is exactly the axis the query-tiled ``bucket_score``
v2 kernel targets: a batch shares one probe-dedup schedule per query tile,
so popular buckets are read from HBM once per tile instead of once per
query, and each block read feeds a ``(QT, D)×(D, B)`` MXU matmul instead of
a matvec. Quantised packs (bf16 halves, int8 quarters the packed bytes)
shrink the per-bucket DMA and buy a larger query tile out of the same VMEM
budget, so their rows should dominate fp32 at large batch. Off-TPU the
fused backend runs the Pallas kernel in interpret mode — its numbers there
are a correctness smoke, not a speed claim (the reference backend is the
honest CPU row).

Every emitted entry is fully labelled (backend, batch, pack_dtype,
query_tile, rescore, **platform**) so BENCH_query.json rows stay comparable
across runs without guessing which configuration produced them — the
platform tag (``jax.default_backend()``) keeps interpret-CPU rows from
being compared against TPU rows by accident. Besides the mean-derived QPS,
each entry reports **p50/p99 per-query latency** over the timing repeats
(at small repeat counts the p99 is effectively the max — it exists to
catch retrace/GC spikes a mean would launder, not to claim tail
statistics).

Measured at the engine seam (one ``engine.search`` call per batch — the
same call ``Retriever._search_batch`` issues per execution-shape group), so
the numbers isolate the scoring mechanism from response assembly.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ClusterPruneIndex, available_backends, get_engine
from repro.data import CorpusConfig, make_corpus
from repro.kernels import pick_query_tile

from .common import bench_sizes, std_parser, timed_all

K_NN = 10
PROBES = 12
BATCH_SIZES = (1, 8, 64)
REPEATS = 5


def _pad_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _pack_stats(engine, qw, probes: int, k: int):
    """Per-query packed HBM bytes the fused path reads, plus the serving
    tile. The byte count fixes the SCHEDULE to the fp32-sized tile so rows
    differ only in storage itemsize — the controlled comparison that makes
    an int8 pack read exactly ¼ the bytes of fp32 (the engine's own tile
    can only be larger for a reduced-precision pack, i.e. fewer duplicate
    block reads, so real traffic is at or below the reported number).
    Every shard reads ITS (B_l, D) slice of each scheduled bucket, hence
    the ``n_shards`` factor."""
    from repro.kernels.bucket_score.ops import (
        build_probe_schedule_device, schedule_block_reads, schedule_length,
    )

    data, _, _, _ = engine._ensure_placed()
    n_shards, n_buckets, b_l, d = (int(x) for x in data.shape)
    nq = int(qw.shape[0])
    flat = engine._flat_probes(qw, engine._probes_t(probes))
    qt_serve = engine.tile_for(k)
    if engine.query_tile is None:
        qt_serve = min(qt_serve, _pad_to(nq, 8))
    qt_sched = min(
        pick_query_tile(d, b_l, k_pad=_pad_to(k, 8), pack_itemsize=4),
        _pad_to(nq, 8),
    )
    s_len = schedule_length(qt_sched, int(flat.shape[1]), n_buckets)
    _, member = build_probe_schedule_device(
        flat, query_tile=qt_sched, s_len=s_len
    )
    reads = schedule_block_reads(member)
    per_q = n_shards * reads * b_l * d * data.dtype.itemsize / nq
    return round(per_q, 1), qt_serve


def run(scale: str = "quick", seed: int = 0, batch_sizes=BATCH_SIZES,
        backends=None, pack_dtypes=(None, "bfloat16", "int8"),
        rescore=None):
    """Returns a list of labelled throughput entries. The fused backend is
    measured once per pack dtype (re-packing the SAME index, so clustering
    is held fixed); its rows carry ``n_shards`` and
    ``packed_bytes_per_query`` (the shard-local block bytes the probe-dedup
    schedule reads — bf16 exactly ½, int8 exactly ¼ of the fp32 row,
    asserted). Reference scores fp32 docs, one row."""
    sz = bench_sizes(scale)
    docs_np, spec, _ = make_corpus(CorpusConfig(
        n_docs=sz["n_docs"], field_dims=sz["field_dims"],
        vocab_sizes=sz["vocab_sizes"], n_topics=sz["n_topics"],
        topic_mix_alpha=sz["topic_mix_alpha"],
        noise_terms=sz["noise_terms"], seed=seed,
    ))
    docs = jnp.asarray(docs_np)
    index = ClusterPruneIndex.build(
        docs, spec, sz["k_clusters"], n_clusterings=3, method="fpf",
        key=jax.random.PRNGKey(seed),
    )
    # One query draw PER BATCH SIZE, shared by every backend × pack-dtype
    # row — rows at the same batch are measured on identical queries (and
    # probe sets), which is what lets the packed-bytes ratio check below
    # hold the schedule fixed across pack dtypes.
    rng = np.random.default_rng(seed)
    qids_by_bs = {
        bs: rng.choice(sz["n_docs"], bs, replace=False)
        for bs in batch_sizes
    }
    if backends is None:
        backends = available_backends()

    platform = jax.default_backend()
    print(f"\n# Throughput — QPS vs batch size (n={sz['n_docs']}, "
          f"probes={PROBES}, k={K_NN}, rescore={rescore}, "
          f"platform={platform}; fused is interpret-mode off-TPU)")
    print("backend,pack_dtype,query_tile,batch,qps,"
          "p50_ms_per_query,p99_ms_per_query")
    entries = []
    for name in backends:
        # the tiled backend sweeps the pack dtypes; reference scores fp32
        # docs and gets one row
        tiled = get_engine(index, name).tile_for(K_NN) is not None
        for pd in pack_dtypes if tiled else (None,):
            idx = (index if pd is None
                   else dataclasses.replace(index, pack_dtype=pd))
            engine = get_engine(idx, name)
            label = pd or "float32"
            # Off-TPU the tiled kernel interprets (a correctness smoke, not
            # a speed claim) — two repeats bound the wall cost of the
            # sweep without changing what the entries verify.
            reps = 2 if tiled and platform != "tpu" else REPEATS
            for bs in batch_sizes:
                qids = qids_by_bs[bs]
                qw = docs[jnp.asarray(qids)]
                ex = jnp.asarray(qids, jnp.int32)
                ts, _ = timed_all(
                    lambda e=engine, q=qw, x=ex: e.search(
                        q, probes=PROBES, k=K_NN, exclude=x,
                        rescore=rescore,
                    ),
                    repeats=reps,
                )
                per_query_ms = np.asarray(ts, np.float64) / bs * 1e3
                t = float(np.median(ts))
                entry = {
                    "backend": name, "batch": bs,
                    "qps": round(bs / t, 2),
                    "ms_per_query": round(t / bs * 1e3, 3),
                    "p50_ms_per_query": round(
                        float(np.percentile(per_query_ms, 50)), 3),
                    "p99_ms_per_query": round(
                        float(np.percentile(per_query_ms, 99)), 3),
                    "pack_dtype": label, "query_tile": None,
                    "rescore": rescore, "platform": platform,
                }
                if tiled:
                    per_q, qt = _pack_stats(engine, qw, PROBES, K_NN)
                    entry["query_tile"] = qt
                    entry["n_shards"] = engine.n_shards
                    entry["packed_bytes_per_query"] = per_q
                entries.append(entry)
                print(f"{name},{label},{entry['query_tile']},{bs},"
                      f"{entry['qps']:.1f},"
                      f"{entry['p50_ms_per_query']:.3f},"
                      f"{entry['p99_ms_per_query']:.3f}")
    _check_pack_ratio(entries)
    return entries


def _check_pack_ratio(entries):
    """Regression gate: at the same batch, an int8 pack must read exactly
    ¼ (and bf16 exactly ½) the packed bytes of fp32 — the schedule is held
    fixed, so only the storage itemsize may differ."""
    by = {
        (e["batch"], e["pack_dtype"]): e["packed_bytes_per_query"]
        for e in entries if "packed_bytes_per_query" in e
    }
    checked = 0
    for (bs, pd), v in by.items():
        base = by.get((bs, "float32"))
        if base is None or pd == "float32":
            continue
        want = {"bfloat16": 2.0, "int8": 4.0}[pd]
        assert abs(base / v - want) < 1e-6, (
            f"{pd} packed bytes/query {v} is not 1/{want:.0f} of "
            f"fp32 ({base}) at batch {bs}"
        )
        checked += 1
    if checked:
        print(f"# pack-dtype byte ratios verified "
              f"({checked} entries: bf16=1/2, int8=1/4 of fp32)")


if __name__ == "__main__":
    parser = std_parser(__doc__)
    parser.add_argument(
        "--pack-dtype", default=None,
        choices=[None, "float32", "bfloat16", "int8"],
        help="restrict the fused backend to ONE bucket-major storage dtype "
             "(default sweeps float32, bfloat16 and int8; bf16 halves and "
             "int8 quarters the packed HBM bytes)")
    parser.add_argument(
        "--rescore", type=int, default=None,
        help="exact-rescore tail depth (>= k) applied to every search — "
             "prices the fp32 gather+matmul re-rank into the QPS numbers")
    parser.add_argument(
        "--backend", default=None,
        choices=[None, "reference", "fused"],
        help="measure ONE backend (default sweeps all registered ones); "
             "combine with XLA_FLAGS=--xla_force_host_platform_device_count"
             "=N to run the fused path over an N-device CPU mesh")
    parser.add_argument(
        "--batches", default=None,
        help="comma-separated batch sizes (default 1,8,64) — smokes trim "
             "this to keep interpret-mode sweeps bounded")
    args = parser.parse_args()
    dts = (
        (None, "bfloat16", "int8") if args.pack_dtype is None
        else (None,) if args.pack_dtype == "float32"
        else (args.pack_dtype,)
    )
    run(args.scale, args.seed,
        batch_sizes=(
            BATCH_SIZES if args.batches is None
            else tuple(int(b) for b in args.batches.split(","))
        ),
        backends=None if args.backend is None else (args.backend,),
        pack_dtypes=dts, rescore=args.rescore)
