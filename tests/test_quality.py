"""Quality-regression tier: pinned CR/NAG floors for every backend.

Backend parity (tests/test_engine.py) proves the three mechanisms return the
SAME answer — it cannot catch a change that makes them all identically
worse (probe-split semantics, dedup, bucket packing, clusterer drift, a
"faster" kernel that scores fewer candidates). This tier pins the paper's
§6 output-quality metrics themselves: on a seeded corpus hard enough to sit
in the paper's mid-recall regime, mean competitive recall and NAG at fixed
probe budgets must stay above floors measured on the current
implementation. A kernel/engine PR that silently degrades output quality
fails HERE instead of only shifting benchmark numbers.

Floors are the measured values minus a small float-tolerance margin — the
pipeline is deterministic (seeded corpus, seeded clustering, seeded
queries), so any drop beyond the margin is a real semantic change.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ClusterPruneIndex,
    brute_force_bottomk,
    brute_force_topk,
    competitive_recall,
    get_engine,
    normalized_aggregate_goodness,
    weighted_query,
)

BACKENDS = ("reference", "fused", "sharded")
K_NN = 10

# (probes, CR floor in [0, 10], NAG floor in [0, 1]) — measured values at
# the seeds below were CR {6: 5.88, 12: 7.31, 24: 8.66} (worst weight set)
# and NAG {6: 0.922, 12: 0.950, 24: 0.974}; floors leave ~0.3 CR / ~0.02
# NAG of margin for float reordering, none for semantic regressions.
QUALITY_FLOORS = (
    (6, 5.5, 0.90),
    (12, 7.0, 0.93),
    (24, 8.3, 0.955),
)

# equal, title-heavy, abstract-heavy — spanning the weight simplex the way
# the paper's Table-2 sets do.
WEIGHT_SETS = (
    (1 / 3, 1 / 3, 1 / 3),
    (0.6, 0.2, 0.2),
    (0.15, 0.15, 0.7),
)


@pytest.fixture(scope="module")
def quality_setup():
    from repro.data import CorpusConfig, make_corpus

    docs_np, spec, _ = make_corpus(CorpusConfig(
        n_docs=1500, field_dims=(64, 64, 128),
        vocab_sizes=(800, 1200, 3000), n_topics=200, topic_mix_alpha=1.0,
        noise_terms=(4, 2, 24), seed=3,
    ))
    docs = jnp.asarray(docs_np)
    index = ClusterPruneIndex.build(
        docs, spec, 40, n_clusterings=3, method="fpf",
        key=jax.random.PRNGKey(0),
    )
    rng = np.random.default_rng(11)
    qids = jnp.asarray(rng.choice(1500, 32, replace=False), jnp.int32)
    # ground truth per weight set, computed once
    cells = []
    for w in WEIGHT_SETS:
        qw = weighted_query(
            docs[qids], jnp.tile(jnp.asarray(w, jnp.float32)[None], (32, 1)),
            spec,
        )
        gt_s, gt_i = brute_force_topk(docs, qw, K_NN, exclude=qids)
        far_s, _ = brute_force_bottomk(docs, qw, K_NN, exclude=qids)
        cells.append((qw, gt_s, gt_i, far_s))
    return index, qids, cells


@pytest.mark.slow
@pytest.mark.parametrize("backend", BACKENDS)
def test_quality_floors(case_engine, quality_setup, backend):
    """Mean CR and NAG at fixed probe budgets stay above the pinned floors
    on every backend, for every weight set."""
    index, qids, cells = quality_setup
    engine = case_engine(index, backend)
    for probes, cr_floor, nag_floor in QUALITY_FLOORS:
        for wi, (qw, gt_s, gt_i, far_s) in enumerate(cells):
            s, ids, _ = engine.search(qw, probes=probes, k=K_NN, exclude=qids)
            cr = float(jnp.mean(competitive_recall(ids, gt_i)))
            nag = float(jnp.mean(
                normalized_aggregate_goodness(s, gt_s, far_s)))
            assert cr >= cr_floor, (
                f"{backend}, probes={probes}, weight set {wi}: "
                f"CR {cr:.3f} fell below the {cr_floor} floor — an engine/"
                f"kernel change degraded output quality")
            assert nag >= nag_floor, (
                f"{backend}, probes={probes}, weight set {wi}: "
                f"NAG {nag:.4f} fell below the {nag_floor} floor")


@pytest.mark.slow
def test_quality_floors_bf16_pack(quality_setup):
    """Half-precision bucket-major storage must stay above the SAME CR/NAG
    floors as fp32: bf16 quantises stored vectors (~1e-2 score noise) but
    may not change which documents the fused backend retrieves enough to
    dent output quality. Probing is untouched (fp32 leaders)."""
    import dataclasses

    index, qids, cells = quality_setup
    bf16 = dataclasses.replace(index, pack_dtype="bfloat16")
    data, _, _, _ = bf16.ensure_local_bucket_major(1)
    assert data.dtype == jnp.bfloat16
    engine = get_engine(bf16, "fused")
    for probes, cr_floor, nag_floor in QUALITY_FLOORS:
        for wi, (qw, gt_s, gt_i, far_s) in enumerate(cells):
            s, ids, _ = engine.search(qw, probes=probes, k=K_NN, exclude=qids)
            cr = float(jnp.mean(competitive_recall(ids, gt_i)))
            nag = float(jnp.mean(
                normalized_aggregate_goodness(s, gt_s, far_s)))
            assert cr >= cr_floor, (
                f"bf16 fused, probes={probes}, weight set {wi}: "
                f"CR {cr:.3f} fell below the {cr_floor} floor")
            assert nag >= nag_floor, (
                f"bf16 fused, probes={probes}, weight set {wi}: "
                f"NAG {nag:.4f} fell below the {nag_floor} floor")


@pytest.mark.slow
def test_quality_floors_int8_pack_with_rescore(quality_setup):
    """Quarter-precision bucket-major storage behind the exact-rescore tail
    must stay above the SAME CR/NAG floors as fp32 and bf16 on the fused
    backend: int8 quantisation perturbs which candidates surface (bounded
    by the per-bucket scale), and the fp32 rescore of the top 3k fixes the
    ordering — measured on this corpus the combination sits at fp32 quality
    (CR min 5.88/7.31/8.66 at probes 6/12/24). Probing is untouched (fp32
    leaders)."""
    import dataclasses

    index, qids, cells = quality_setup
    i8 = dataclasses.replace(index, pack_dtype="int8")
    data, _, scales, _ = i8.ensure_local_bucket_major(1)
    assert data.dtype == jnp.int8 and scales is not None
    engine = get_engine(i8, "fused")
    for probes, cr_floor, nag_floor in QUALITY_FLOORS:
        for wi, (qw, gt_s, gt_i, far_s) in enumerate(cells):
            s, ids, _ = engine.search(
                qw, probes=probes, k=K_NN, exclude=qids, rescore=3 * K_NN
            )
            cr = float(jnp.mean(competitive_recall(ids, gt_i)))
            nag = float(jnp.mean(
                normalized_aggregate_goodness(s, gt_s, far_s)))
            assert cr >= cr_floor, (
                f"int8+rescore fused, probes={probes}, weight set {wi}: "
                f"CR {cr:.3f} fell below the {cr_floor} floor")
            assert nag >= nag_floor, (
                f"int8+rescore fused, probes={probes}, weight set {wi}: "
                f"NAG {nag:.4f} fell below the {nag_floor} floor")


@pytest.mark.slow
@pytest.mark.parametrize("backend", BACKENDS)
def test_quality_floors_with_rescore(case_engine, quality_setup, backend):
    """The exact-rescore tail can only re-rank candidates the pruned search
    already surfaced — on an fp32 pack it must keep every backend above the
    same pinned floors (it is an identity there), so a backend whose
    rescore plumbing dropped candidates fails HERE."""
    index, qids, cells = quality_setup
    engine = case_engine(index, backend)
    for probes, cr_floor, nag_floor in QUALITY_FLOORS:
        qw, gt_s, gt_i, far_s = cells[0]
        s, ids, _ = engine.search(
            qw, probes=probes, k=K_NN, exclude=qids, rescore=2 * K_NN
        )
        cr = float(jnp.mean(competitive_recall(ids, gt_i)))
        nag = float(jnp.mean(normalized_aggregate_goodness(s, gt_s, far_s)))
        assert cr >= cr_floor, (
            f"{backend}+rescore, probes={probes}: CR {cr:.3f} fell below "
            f"the {cr_floor} floor")
        assert nag >= nag_floor, (
            f"{backend}+rescore, probes={probes}: NAG {nag:.4f} fell below "
            f"the {nag_floor} floor")


@pytest.mark.slow
def test_pack_dtype_topk_overlap_floors(quality_setup):
    """Storage precision may only perturb the retrieved set marginally:
    mean top-k overlap of the fused backend against its own fp32 pack must
    stay above pinned floors for bf16 and int8 (measured 0.997+ / 0.988+ on
    this corpus; floors leave noise margin only)."""
    import dataclasses

    OVERLAP_FLOORS = {"bfloat16": 0.97, "int8": 0.95}
    index, qids, cells = quality_setup
    f32 = get_engine(index, "fused")
    for pack, floor in OVERLAP_FLOORS.items():
        twin = dataclasses.replace(index, pack_dtype=pack)
        eng = get_engine(twin, "fused")
        for probes, _, _ in QUALITY_FLOORS:
            for wi, (qw, _, _, _) in enumerate(cells):
                _, i_ref, _ = f32.search(
                    qw, probes=probes, k=K_NN, exclude=qids
                )
                _, i_out, _ = eng.search(
                    qw, probes=probes, k=K_NN, exclude=qids
                )
                overlap = float(np.mean([
                    len(set(a.tolist()) & set(b.tolist())) / K_NN
                    for a, b in zip(np.asarray(i_ref), np.asarray(i_out))
                ]))
                assert overlap >= floor, (
                    f"{pack} fused, probes={probes}, weight set {wi}: "
                    f"top-{K_NN} overlap {overlap:.3f} fell below {floor}")


@pytest.mark.slow
def test_quality_improves_with_probes(quality_setup):
    """Sanity on the floors' premise: the recall-vs-probes curve the planner
    calibrates against is increasing on this corpus."""
    index, qids, cells = quality_setup
    engine = get_engine(index, "reference")
    qw, _, gt_i, _ = cells[0]
    crs = []
    for probes, _, _ in QUALITY_FLOORS:
        _, ids, _ = engine.search(qw, probes=probes, k=K_NN, exclude=qids)
        crs.append(float(jnp.mean(competitive_recall(ids, gt_i))))
    assert crs == sorted(crs), crs
