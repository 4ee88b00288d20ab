"""``SearchServer`` over the fused engine that ``"auto"`` picks over four
(virtual CPU) devices: full batches, answered as the ``reference`` backend
answers, id for id and score for score, with each request's own document
left out and ``k`` hits, where the requests' own documents and their
answers lie on other shards than each other; the answer comes back on the
corpus's device."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

SCRIPT = r"""
import asyncio, json
import jax, jax.numpy as jnp, numpy as np
from repro.core import (FieldSpec, Retriever, SearchRequest, get_engine,
                        normalize_fields)
from repro.core.distributed import shard_rows
from repro.serving import SearchServer

MAX_BATCH, N, K = int(__import__("sys").argv[1]), 801, 10
assert jax.device_count() == 4
spec = FieldSpec(names=("title", "authors", "abstract"), dims=(32, 32, 64))
docs = normalize_fields(
    jax.random.normal(jax.random.PRNGKey(3), (N, spec.total_dim)), spec)
r = Retriever.build(docs, spec, 12, key=jax.random.PRNGKey(4))
rng = np.random.default_rng(5)
likes = rng.choice(N, 2 * MAX_BATCH, replace=False)
w = rng.dirichlet([1.0] * 3, size=len(likes))
reqs = [SearchRequest(like=int(i), weights=dict(zip(spec.names, map(float, x))),
                      k=K, probes=6) for i, x in zip(likes, w)]

async def serve():
    async with SearchServer(r, window_s=0.05, max_batch=MAX_BATCH) as server:
        return await asyncio.gather(*(server.submit(q) for q in reqs))

got = asyncio.run(serve())
engine = get_engine(r.index, r.backend)
_, top, _ = engine.search(docs[:4], probes=6, k=K)
ref = Retriever(r.index, backend="reference").search(reqs)
n_local = shard_rows(N, 4)
own = [q.like // n_local for q in reqs]
print(json.dumps({
    "backend": r.backend,
    "shards": engine.n_shards,
    "served_by": sorted({a.backend for a in got}),
    "batches": sorted({a.batch_size for a in got}),
    "max_batch": MAX_BATCH,
    "ids_equal": all(list(a.doc_ids) == list(b.doc_ids)
                     for a, b in zip(got, ref)),
    "score_gap": max(float(np.max(np.abs(a.scores - b.scores)))
                     for a, b in zip(got, ref)),
    "own_left_out": all(q.like not in a.ids for q, a in zip(reqs, got)),
    "k_hits": all(len(a.hits) == K for a in got),
    "own_shards": sorted(set(own)),
    "hits_off_own_shard": sum(int(h // n_local != s)
                              for a, s in zip(got, own) for h in a.ids),
    "answer_devices": len(top.devices()),
    "global_pack": sorted(vars(r.index)["_local_bucket_major"]) != [4]
                   or len(engine._ensure_placed()[0].devices()) != 4,
}))
"""


@pytest.mark.parametrize("max_batch", [64, 128])
def test_served_sharded_search_equals_reference(max_batch):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": str(ROOT / "src")}
    out = subprocess.run([sys.executable, "-c", SCRIPT, str(max_batch)],
                         capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["backend"] == "fused" and res["served_by"] == ["fused"]
    assert res["shards"] == 4
    assert res["batches"] == [max_batch]
    assert res["ids_equal"] and res["score_gap"] <= 1e-5, res
    assert res["own_left_out"] and res["k_hits"]
    assert res["own_shards"] == [0, 1, 2, 3]
    assert res["hits_off_own_shard"] > 0
    assert not res["global_pack"]
    # the answer comes back on the corpus's device, not replicated over
    # the mesh, so what reads it next never copies the corpus to all four
    assert res["answer_devices"] == 1
