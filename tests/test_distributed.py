"""Distributed-search + sharding tests on 8 forced host devices.

Runs in a SUBPROCESS so the 8-device XLA flag never leaks into other tests
(jax locks the device count at first init).
"""

import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import (ClusterPruneIndex, FieldSpec, brute_force_topk,
                        competitive_recall, normalize_fields, weighted_query)
from repro.core.distributed import (build_local_buckets, distributed_brute_topk,
                                    distributed_index_search, shard_docs)
from repro.launch.mesh import make_host_mesh

spec = FieldSpec(names=("a", "b"), dims=(32, 32))
n = 1024
docs = normalize_fields(jax.random.normal(jax.random.PRNGKey(0), (n, 64)), spec)
mesh = make_host_mesh((2, 2, 2), ("pod", "data", "model"))
axes = ("pod", "data", "model")
docs_sh = shard_docs(docs, mesh, axes)
w = jnp.tile(jnp.asarray([[0.7, 0.3]]), (4, 1))
qw = weighted_query(docs[10:14], w, spec)

# exact distributed top-k == single-device brute force
s, i = distributed_brute_topk(mesh, docs_sh, qw, k=10, shard_axes=axes)
gt_s, gt_i = brute_force_topk(docs, qw, 10)
assert np.array_equal(np.asarray(i), np.asarray(gt_i)), "brute mismatch"

# index-based distributed search == single-device index search
idx = ClusterPruneIndex.build(docs, spec, 16, n_clusterings=3, method="fpf")
assign = np.full((3, n), -1)
for t in range(3):
    bk = np.asarray(idx.buckets[t])
    for c in range(bk.shape[0]):
        for d in bk[c]:
            if d < n:
                assign[t, d] = c
bl = build_local_buckets(assign, n, 8, 16)
s2, i2 = distributed_index_search(mesh, docs_sh, idx.leaders,
                                  jnp.asarray(bl), qw, probes_t=(2, 2, 2),
                                  k=10, shard_axes=axes)
s1, i1, _ = idx.search(qw, probes=6, k=10)
# distributed and single-device agree up to float tie-breaks at the k-th
# score: require >= 9/10 overlap per query and matched top scores
for a, b, sa, sb in zip(np.asarray(i2), np.asarray(i1),
                        np.asarray(s2), np.asarray(s1)):
    overlap = len(set(a.tolist()) & set(b.tolist()))
    assert overlap >= 9, f"index search overlap {overlap}: {a} vs {b}"
    assert abs(float(sa[0]) - float(sb[0])) < 1e-3

# sharding rules produce valid lowerings for a tiny LM on the host mesh
from repro.configs import get_arch
from repro.models import transformer as tf
from repro.runtime.sharding import lm_param_rules, lm_use_rules
from jax.sharding import NamedSharding
cfg = get_arch("qwen3-8b").make_smoke_config()
rules = lm_param_rules(cfg, mesh)
use = lm_use_rules(cfg, mesh)
specs = tf.param_specs(cfg)
toks = jax.ShapeDtypeStruct((8, 32), jnp.int32)
def step(p, t):
    return tf.loss_fn(p, t, t, cfg, use)[0]
shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), rules,
                         is_leaf=lambda x: isinstance(x, P))
with jax.set_mesh(mesh):
    c = jax.jit(step, in_shardings=(shardings, NamedSharding(mesh, P(("pod", "data"), None)))).lower(specs, toks).compile()
assert c.cost_analysis() is not None
print("DISTRIBUTED_OK")
"""


@pytest.mark.slow
def test_distributed_search_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT], env=env, capture_output=True,
        text=True, timeout=600,
    )
    assert "DISTRIBUTED_OK" in out.stdout, out.stdout + out.stderr


# Sharded-FUSED parity: the ShardedEngine runs the query-tiled Pallas v2
# kernel (interpret mode on CPU) shard-locally over device-local
# bucket-major packs. The odd corpus size (1019 on 8 shards) exercises the
# sentinel-row padding; pack dtypes, ragged batches, exclude, rescore and
# the exact tier all check against the single-device reference engine.
_FUSED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.core import (ClusterPruneIndex, FieldSpec, brute_force_topk,
                        normalize_fields, weighted_query)
from repro.core.engine import get_engine, pick_backend

assert jax.device_count() == 8
assert pick_backend() == ("fused", 8)   # multi-device auto-pick, any n_docs

spec = FieldSpec(names=("a", "b"), dims=(32, 32))
n = 1019                             # deliberately NOT divisible by 8
docs = normalize_fields(jax.random.normal(jax.random.PRNGKey(0), (n, 64)), spec)
idx = ClusterPruneIndex.build(docs, spec, 16, n_clusterings=3, method="fpf")
w = jnp.tile(jnp.asarray([[0.7, 0.3]]), (5, 1))
qw = weighted_query(docs[10:15], w, spec)
ref = get_engine(idx, "reference")
sh = get_engine(idx, "fused", interpret=True)
assert sh.n_shards == 8

def ids_scores_n(a, b, tag, atol=1e-5):
    assert np.array_equal(np.asarray(a[1]), np.asarray(b[1])), tag + " ids"
    assert np.allclose(np.asarray(a[0]), np.asarray(b[0]), atol=atol), \
        tag + " scores"
    assert np.array_equal(np.asarray(a[2]), np.asarray(b[2])), tag + " n"

# fp32: exact id/score/n_scored parity, plain + exclude + rescore
ids_scores_n(ref.search(qw, probes=6, k=10),
             sh.search(qw, probes=6, k=10), "fp32")
ex = jnp.asarray([10, 11, 12, 13, 14], jnp.int32)
ids_scores_n(ref.search(qw, probes=6, k=10, exclude=ex),
             sh.search(qw, probes=6, k=10, exclude=ex), "exclude")
ids_scores_n(ref.search(qw, probes=6, k=5, rescore=20),
             sh.search(qw, probes=6, k=5, rescore=20), "rescore")

# ragged batch shapes, incl. a single 1-D query (squeezed result shape)
for m in (1, 3, 7):
    qb = weighted_query(docs[20:20 + m], jnp.tile(w[:1], (m, 1)), spec)
    ids_scores_n(ref.search(qb, probes=6, k=10),
                 sh.search(qb, probes=6, k=10), f"batch{m}")
q1 = weighted_query(docs[42], jnp.asarray([0.5, 0.5]), spec)
r1, s1 = ref.search(q1, probes=6, k=10), sh.search(q1, probes=6, k=10)
assert s1[0].shape == (10,) and np.array_equal(np.asarray(r1[1]),
                                               np.asarray(s1[1]))

# exact tier (fp32 pack) == brute force on shards
es, ei, en = sh.search_exact(qw, k=10)
gs, gi = brute_force_topk(docs, qw, 10)
assert np.array_equal(np.asarray(ei), np.asarray(gi)), "exact tier ids"
rs, ri, rn = ref.search_exact(qw, k=10)
assert np.array_equal(np.asarray(en), np.asarray(rn)), "exact tier n"

# quantised packs: fp32 leaders keep navigation & n_scored bit-identical;
# storage noise stays within the usual floors and the rescore tail (and
# with it the exact tier) recovers exact fp32 ids/scores.
r = ref.search(qw, probes=6, k=10)
for dt, floor in (("bfloat16", 0.9), ("int8", 0.9)):
    q = dataclasses.replace(idx, pack_dtype=dt)
    shq = get_engine(q, "fused", interpret=True)
    sq = shq.search(qw, probes=6, k=10)
    assert np.array_equal(np.asarray(sq[2]), np.asarray(r[2])), dt + " n"
    ov = np.mean([len(set(a.tolist()) & set(b.tolist())) / 10
                  for a, b in zip(np.asarray(sq[1]), np.asarray(r[1]))])
    assert ov >= floor, f"{dt} overlap {ov}"
    ids_scores_n(ref.search(qw, probes=6, k=5, rescore=20),
                 shq.search(qw, probes=6, k=5, rescore=20), dt + " rescore")
    eq = shq.search_exact(qw, k=10)
    assert np.array_equal(np.asarray(eq[1]), np.asarray(gi)), dt + " exact"

# mutations repack lazily on the SAME engine object (version-keyed)
new = normalize_fields(jax.random.normal(jax.random.PRNGKey(7), (3, 64)), spec)
idx.add_documents(new)
ids_scores_n(get_engine(idx, "reference").search(qw, probes=6, k=10),
             sh.search(qw, probes=6, k=10), "post-add")
idx.remove_documents([0, 1, 2])
ids_scores_n(get_engine(idx, "reference").search(qw, probes=6, k=10),
             sh.search(qw, probes=6, k=10), "post-remove")
print("SHARDED_FUSED_OK")
"""


@pytest.mark.slow
def test_sharded_fused_parity_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", _FUSED_SCRIPT], env=env, capture_output=True,
        text=True, timeout=600,
    )
    assert "SHARDED_FUSED_OK" in out.stdout, out.stdout + out.stderr
