"""Shared fixtures. NOTE: no XLA_FLAGS here — tests see the REAL device
count (1 CPU); only launch/dryrun.py forces 512 host devices."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FieldSpec, normalize_fields


@pytest.fixture(scope="session")
def small_corpus():
    """Structured 3-field corpus, 1500 docs (session-cached)."""
    from repro.data import CorpusConfig, make_corpus

    docs, spec, topics = make_corpus(
        CorpusConfig(n_docs=1500, field_dims=(64, 64, 128),
                     vocab_sizes=(800, 1200, 3000), n_topics=16, seed=3)
    )
    return jnp.asarray(docs), spec, topics


@pytest.fixture(scope="session")
def random_corpus():
    spec = FieldSpec(names=("a", "b", "c"), dims=(32, 32, 64))
    x = jax.random.normal(jax.random.PRNGKey(0), (1200, spec.total_dim))
    return normalize_fields(x, spec), spec


# The one kernel-path engine runs every parity test twice: over the mesh
# the pick derives (case "fused") and over an explicit ``mesh=`` of all of
# the host's devices (case "sharded", the ``mesh=``/``shard_axes=`` path).
def _host_mesh():
    return jax.make_mesh((jax.device_count(),), ("data",))


@pytest.fixture(scope="session")
def case_engine():
    """``case_engine(index, case, **opts)``: the engine a parity case runs.
    ``"sharded"`` is the fused engine over an explicit ``mesh=`` of all of
    the host's devices; the other cases are backends by name."""
    from repro.core import get_engine

    def engine(index, case, **opts):
        if case == "sharded":
            return get_engine(index, "fused", mesh=_host_mesh(),
                              shard_axes=("data",), **opts)
        return get_engine(index, case, **opts)

    return engine


@pytest.fixture(scope="session")
def case_retriever():
    """``case_retriever(retriever, case)`` -> ``(retriever, request
    backend)``: case ``"sharded"`` is a retriever over the same index whose
    fused engine runs over an explicit ``mesh=`` of all of the host's
    devices; the other cases keep the retriever and name the backend."""
    from repro.core import Retriever

    def served(retriever, case):
        if case != "sharded":
            return retriever, case
        return Retriever(retriever.index, backend="fused",
                         engine_opts={"mesh": _host_mesh()}), "fused"

    return served
