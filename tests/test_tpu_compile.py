"""Ahead-of-time compiles of the main path's kernels for a described TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests catch what interpret mode cannot —
a block shape off the (8, 128) tiling, a primitive Mosaic does not lower, a
kernel asking for more VMEM than its limit — at the real widths (D=4096,
the paper's TS1/TS2 bucket sizes) without a chip. Nothing runs: they say
nothing about results or times.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and under several test
workers only the worker given this file must try.
"""

from __future__ import annotations

import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.distributed import distributed_bucket_score
from repro.kernels.bucket_score.ops import (
    bucket_score_tiled,
    build_probe_schedule_device,
    pick_query_tile,
    schedule_length,
)
from repro.kernels.common import pad_to
from repro.kernels.fpf_iter.ops import fpf_iter

D = 4096
T = 3
K_TOP = 10
PROBES = 12
# (clusters per clustering, max bucket size): the paper's TS1 and TS2
# corpora at the default K (the B the builds of benchmarks/common.py give)
CORPORA = {"ts1": (500, 312), "ts2": (1000, 568)}
PACK_DTYPES = (jnp.float32, jnp.bfloat16, jnp.int8)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler / topology support here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # an AOT compile is written to the persistent cache but cannot be read
    # back without a chip; keep the cache off for this module
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("corpus", sorted(CORPORA))
@pytest.mark.parametrize("dtype", PACK_DTYPES, ids=lambda d: jnp.dtype(d).name)
def test_bucket_score_tiled_compiles(one_chip, corpus, dtype):
    k_clusters, b = CORPORA[corpus]
    # TS2's fp32 pack (27.9 GB) needs four chips; one chip compiles one
    # clustering of it — the kernel's blocks depend on B and D only
    t = 1 if (corpus, dtype) == ("ts2", jnp.float32) else T
    k_pad = pad_to(K_TOP, 8)
    qt = pick_query_tile(
        D, b, k_pad=k_pad, pack_itemsize=jnp.dtype(dtype).itemsize
    )
    nq = 2 * qt                                        # two query tiles
    s_len = schedule_length(qt, PROBES, t * k_clusters)
    S = functools.partial(_shape, one_chip)
    args = (
        S((nq, D), jnp.float32),
        S((t, k_clusters, b, D), dtype),
        S((t * k_clusters, b), jnp.int32),
        S((2, s_len), jnp.int32),
        S((2, s_len, qt), jnp.int32),
    )
    scales = (
        S((t * k_clusters,), jnp.float32) if dtype == jnp.int8 else None
    )
    compiled = jax.jit(
        lambda *a, exclude, scales: bucket_score_tiled(
            *a, k=K_TOP, exclude=exclude, scales=scales, interpret=False
        )
    ).lower(*args, exclude=S((nq,), jnp.int32), scales=scales).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fpf_iter_compiles(one_chip):
    m = 53_722                                         # TS1 corpus rows
    S = functools.partial(_shape, one_chip)
    compiled = jax.jit(
        lambda x, c, ms: fpf_iter(x, c, ms, interpret=False)
    ).lower(
        S((m, D), jnp.float32), S((D,), jnp.float32), S((m,), jnp.float32)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_probe_schedule_device_compiles(one_chip):
    k_clusters, b = CORPORA["ts1"]
    qt = pick_query_tile(D, b, k_pad=pad_to(K_TOP, 8))
    s_len = schedule_length(qt, PROBES, T * k_clusters)
    compiled = jax.jit(
        functools.partial(
            build_probe_schedule_device, query_tile=qt, s_len=s_len
        )
    ).lower(_shape(one_chip, (64, PROBES), jnp.int32)).compile()
    assert compiled.memory_analysis() is not None


def test_sharded_bucket_score_compiles(one_chip, topo):
    """The TS2 deployment's hot path: a 4-way row-sharded fp32 pack, one
    kernel per shard, one all-gather + merge of the per-shard top-k, at the
    served batch of 128 (one full query tile)."""
    devices = np.asarray(topo.devices[:4])
    mesh = Mesh(devices, ("data",))
    n_shards = devices.size
    k_clusters, b = CORPORA["ts2"]
    b_l = pad_to(-(-b // n_shards), 8)                 # per-shard bucket rows
    n_local = 100_000 // n_shards
    nq = 128
    qt = min(pick_query_tile(D, b_l, k_pad=pad_to(K_TOP, 8)), nq)
    n_tiles = -(-nq // qt)
    s_len = schedule_length(qt, PROBES, T * k_clusters)
    sh = lambda *spec: NamedSharding(mesh, P(*spec))
    args = (
        jax.ShapeDtypeStruct(
            (n_shards, T * k_clusters, b_l, D), jnp.float32,
            sharding=sh("data", None, None, None),
        ),
        jax.ShapeDtypeStruct(
            (n_shards, T * k_clusters, b_l), jnp.int32,
            sharding=sh("data", None, None),
        ),
        jax.ShapeDtypeStruct((nq, D), jnp.float32, sharding=sh(None, None)),
        jax.ShapeDtypeStruct(
            (n_tiles, s_len), jnp.int32, sharding=sh(None, None)
        ),
        jax.ShapeDtypeStruct(
            (n_tiles, s_len, qt), jnp.int32, sharding=sh(None, None, None)
        ),
    )
    compiled = jax.jit(
        lambda data, ids, qw, sched, member: distributed_bucket_score(
            mesh, data, ids, None, qw, sched, member, k=K_TOP,
            n_local=n_local, interpret=False,
        )
    ).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-gather" in text
    # each device holds a quarter of the pack, never the whole of it
    pack_bytes = n_shards * T * k_clusters * b_l * D * 4
    assert compiled.memory_analysis().argument_size_in_bytes < pack_bytes / 2


@pytest.mark.parametrize("corpus,n_docs,n_shards",
                         [("ts1", 53_722, 1), ("ts2", 100_000, 4)])
def test_fused_search_compiles(one_chip, topo, corpus, n_docs, n_shards):
    """The fused engine's whole search as the one program it launches:
    navigation on the replicated fp32 leaders, the probe-dedup schedule,
    each shard's kernel, the all-gather and merge, and ``n_scored``. TS1's
    fp32 pack on one chip, TS2's row-sharded over four, at the served batch
    of 128 with self-exclusion."""
    from repro.core.distributed import _bucket_scorer
    from repro.core.engine import _fused_search_fn

    mesh = Mesh(np.asarray(topo.devices[:n_shards]), ("data",))
    k_clusters, b = CORPORA[corpus]
    b_l = pad_to(-(-b // n_shards), 8)
    n_buckets = T * k_clusters
    nq = 128
    qt = min(pick_query_tile(D, b_l, k_pad=pad_to(K_TOP, 8)), nq)
    s_len = schedule_length(qt, PROBES, n_buckets)
    scorer = _bucket_scorer(
        mesh, ("data",), (n_shards, n_buckets, b_l, D), s_len, k=K_TOP,
        n_local=-(-n_docs // n_shards), interpret=False,
    )
    program = _fused_search_fn(scorer, (4, 4, 4), K_TOP, qt, s_len)
    S = lambda shape, dtype, *spec: jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh, P(*spec)))
    compiled = program.lower(
        S((n_shards, n_buckets, b_l, D), jnp.float32, "data"),
        S((n_shards, n_buckets, b_l), jnp.int32, "data"),
        S((n_shards, n_buckets), jnp.float32, "data"),
        S((T, k_clusters, D), jnp.float32),
        S((n_buckets,), jnp.int32),
        S((nq, D), jnp.float32),
        None,
        S((nq,), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert ("all-gather" in text) == (n_shards > 1)
    pack_bytes = n_shards * n_buckets * b_l * D * 4
    assert compiled.memory_analysis().argument_size_in_bytes < (
        pack_bytes / 2 if n_shards > 1 else 16 * 2**30)
