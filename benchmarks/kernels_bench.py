"""Kernel benches: Pallas (interpret on CPU; compiled on TPU) vs jnp oracle.

On this CPU container interpret-mode wall time is NOT indicative of TPU
performance — the meaningful outputs are (a) allclose vs the oracle at every
shape, (b) the VMEM working-set accounting per BlockSpec (printed), which is
the quantity that determines TPU block residency.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels import (
    bucket_score, bucket_score_ref, bucket_score_tiled, build_probe_schedule,
    embed_bag, embed_bag_ref, fpf_iter, fpf_iter_ref, pick_query_tile,
    quantize_bucket_major, schedule_block_reads, topk_score, topk_score_ref,
)

from .common import timed


def _vmem_mb(*arrs):
    return sum(a.size * a.dtype.itemsize for a in arrs) / 2**20


def run():
    key = jax.random.PRNGKey(0)
    print("\n# Kernels — oracle agreement + VMEM working set")
    print("kernel,shape,allclose,vmem_block_MB,oracle_ms")

    # topk_score: serving-scale brute scoring
    q = jax.random.normal(key, (64, 1024))
    d = jax.random.normal(key, (16384, 1024))
    s, i = topk_score(q, d, k=10, block_q=64, block_n=512)
    rs_, ri = topk_score_ref(q, d, 10)
    t_ref, _ = timed(lambda: topk_score_ref(q, d, 10))
    ok = np.allclose(np.asarray(s), np.asarray(rs_), atol=1e-4)
    vmem = _vmem_mb(q[:64], d[:512]) + 64 * (10 + 512) * 4 / 2**20
    print(f"topk_score,(64x16384x1024 k=10),{ok},{vmem:.1f},{t_ref*1e3:.1f}")

    # bucket_score: cluster-prune inner loop
    K, B, D, P = 64, 128, 1024, 6
    bd = jax.random.normal(key, (K, B, D))
    bi = jnp.arange(K * B, dtype=jnp.int32).reshape(K, B)
    qs = jax.random.normal(key, (8, D))
    probes = jax.random.randint(key, (8, P), 0, K)
    s, i = bucket_score(qs, bd, bi, probes, k=10)
    rs_, ri = bucket_score_ref(qs, bd, bi, probes, 10)
    t_ref, _ = timed(lambda: bucket_score_ref(qs, bd, bi, probes, 10))
    ok = np.allclose(np.asarray(s), np.asarray(rs_), atol=1e-4)
    vmem = _vmem_mb(bd[0], qs[:1]) + (10 + B) * 2 * 4 / 2**20
    print(f"bucket_score,({K}x{B}x{D} P={P}),{ok},{vmem:.1f},{t_ref*1e3:.1f}")

    # bucket_score_tiled (v2): query-tiled scoring over a dedup'd schedule.
    # The extra columns are the throughput mechanism itself: HBM block
    # reads collapse from nq*P (v1) to the schedule length, and every read
    # feeds a (QT, D)x(D, B) MXU matmul instead of a matvec.
    qt = pick_query_tile(D, B, k_pad=16)
    sched, member = build_probe_schedule(np.asarray(probes), qt)
    s2, i2 = bucket_score_tiled(
        qs, bd, bi, jnp.asarray(sched), jnp.asarray(member), k=10
    )
    ok = np.allclose(np.asarray(s2), np.asarray(rs_), atol=1e-4)
    n_live = schedule_block_reads(member)
    vmem = (qt * D + B * D + qt * B + 2 * qt * 16) * 4 / 2**20
    print(f"bucket_score_tiled,({K}x{B}x{D} P={P} QT={qt}),{ok},{vmem:.1f},"
          f"{t_ref*1e3:.1f}")

    # quantised packs: the SAME schedule, shrinking per-bucket DMA bytes —
    # bf16 halves, int8 (per-bucket scales) quarters them. Agreement is vs
    # the fp32 oracle, so the printed tolerance IS the quantisation noise.
    bd8, sc8 = quantize_bucket_major(bd)
    s8, i8 = bucket_score_tiled(
        qs, bd8, bi, jnp.asarray(sched), jnp.asarray(member), k=10,
        scales=sc8,
    )
    # oracle here is the DEQUANTISED reference (same int8 values) — the
    # remaining slack is the kernel's bf16 query cast, ~0.4% of the ~32
    # score magnitude on this unnormalised corpus
    rs8, _ = bucket_score_ref(qs, bd8, bi, probes, 10, scales=sc8)
    f2, f8 = np.isfinite(np.asarray(rs8)), np.isfinite(np.asarray(s8))
    ok8 = bool(
        np.array_equal(f2, f8)
        and np.allclose(np.asarray(s8)[f8], np.asarray(rs8)[f2], atol=0.25)
    )
    quant_rms = float(np.sqrt(np.mean(
        (np.asarray(rs8)[f2] - np.asarray(rs_)[f2]) ** 2)))
    vmem8 = (qt * D + B * D // 4 + qt * B + 2 * qt * 16) * 4 / 2**20
    print(f"bucket_score_tiled[int8],({K}x{B}x{D} P={P} "
          f"QT={pick_query_tile(D, B, k_pad=16, pack_itemsize=1)}),{ok8},"
          f"{vmem8:.1f},{t_ref*1e3:.1f}")

    # the throughput mechanism in two numbers: HBM block reads collapse
    # from nq*P (v1) to the dedup'd schedule length, and the packed bytes
    # each query pays for those reads shrink with the storage dtype.
    nq = qs.shape[0]
    print(f"# int8 quantisation RMS vs fp32 top-k scores: {quant_rms:.3f} "
          f"(score magnitude ~{float(np.abs(np.asarray(rs_)[f2]).mean()):.0f})")
    print(f"# tiled schedule: {nq * P} per-query probes -> "
          f"{n_live} deduplicated block reads")
    for label, itemsize in (("float32", 4), ("bfloat16", 2), ("int8", 1)):
        per_q = n_live * B * D * itemsize / nq
        print(f"#   packed bytes/query [{label}]: {per_q / 2**20:.2f} MiB"
              f" ({n_live} blocks x {B}x{D}x{itemsize}B / {nq} queries)")

    # fpf_iter: preprocessing round
    x = jax.random.normal(key, (16384, 512))
    x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
    ms = jnp.full((16384,), -jnp.inf)
    nm, idx, val = fpf_iter(x, x[0], ms, block_m=1024)
    rm, ridx, _ = fpf_iter_ref(x, x[0], ms)
    t_ref, _ = timed(lambda: fpf_iter_ref(x, x[0], ms))
    ok = np.allclose(np.asarray(nm), np.asarray(rm), atol=1e-5) and int(idx) == int(ridx)
    vmem = _vmem_mb(x[:1024]) + 1024 * 2 * 4 / 2**20
    print(f"fpf_iter,(16384x512),{ok},{vmem:.1f},{t_ref*1e3:.1f}")

    # embed_bag: recsys lookup
    tbl = jax.random.normal(key, (100_000, 128))
    idxs = jax.random.randint(key, (256, 16), -1, 100_000)
    o = embed_bag(tbl, idxs, combiner="sum")
    r = embed_bag_ref(tbl, idxs, combiner="sum")
    t_ref, _ = timed(lambda: embed_bag_ref(tbl, idxs, combiner="sum"))
    ok = np.allclose(np.asarray(o), np.asarray(r), atol=1e-4)
    vmem = (128 * 4 * 2) / 2**20
    print(f"embed_bag,(100000x128 B=256 L=16),{ok},{vmem:.3f},{t_ref*1e3:.1f}")

    run_engines()


def run_engines():
    """Engine-layer bench: all search backends on ONE built index.

    Unlike the per-kernel rows above this times the full serving hot path
    (probe -> gather -> score -> merge) through the SearchEngine seam, so a
    backend's layout cost (doc-major gather vs shard-local bucket-major
    block reads) shows up end to end. Off-TPU the fused backend is
    interpret-mode Pallas — agreement is the signal there, not wall time.
    """
    from repro.core import (
        ClusterPruneIndex, FieldSpec, available_backends, get_engine,
        normalize_fields,
    )

    key = jax.random.PRNGKey(1)
    spec = FieldSpec(names=("a", "b", "c"), dims=(64, 64, 128))
    docs = normalize_fields(jax.random.normal(key, (4096, 256)), spec)
    idx = ClusterPruneIndex.build(docs, spec, 64, n_clusterings=3)
    qw = docs[:16]
    ex = jnp.arange(16, dtype=jnp.int32)

    print(f"\n# Engine backends — one index (n=4096, K=64, T=3), 16 queries,"
          f" probes=9 (platform={jax.default_backend()})")
    print("backend,ms_per_query,matches_reference,n_scored_mean")
    ref = get_engine(idx, "reference").search(qw, probes=9, k=10, exclude=ex)
    for name in available_backends():
        eng = get_engine(idx, name)
        t, (s, i, ns) = timed(
            lambda e=eng: e.search(qw, probes=9, k=10, exclude=ex)
        )
        match = bool(
            np.array_equal(np.asarray(i), np.asarray(ref[1]))
            and np.allclose(np.asarray(s), np.asarray(ref[0]), atol=1e-4)
            and np.array_equal(np.asarray(ns), np.asarray(ref[2]))
        )
        print(f"{name},{t / 16 * 1e3:.3f},{match},{float(jnp.mean(ns)):.0f}")


if __name__ == "__main__":
    run()
