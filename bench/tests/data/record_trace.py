"""Record the small traces the reduction tests read.

    python bench/tests/data/record_trace.py

On a host with four TPU chips it writes ``sharded4.xplane.pb`` (the
``sharded`` backend), on one chip ``fused1.xplane.pb`` (``fused``): a small
three-field index, two batches of 64 searches inside a ``bench.window``
span, the profiler's Python tracer off as the harness has it.
"""

import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[2]), str(HERE.parents[2] / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench.corpus import make_corpus  # noqa: E402
from repro.core import FieldSpec, Retriever, SearchRequest  # noqa: E402

corpus = {"n_docs": 4000, "field_names": ["title", "authors", "abstract"],
          "field_dims": [128, 128, 256], "vocab_sizes": [800, 1200, 3000],
          "terms_per_field": [8, 3, 80], "n_topics": 16,
          "salient_per_topic": 60, "topic_mix_alpha": 1.0,
          "noise_terms": [4, 2, 24]}
docs = jax.device_put(make_corpus(corpus, 1))
spec = FieldSpec(names=tuple(corpus["field_names"]),
                 dims=tuple(corpus["field_dims"]))
four = jax.device_count() == 4
r = Retriever.build(docs, spec, 20, backend="sharded" if four else "fused",
                    key=jax.random.PRNGKey(1))
rng = np.random.default_rng(1)


def batch():
    w = rng.dirichlet([1.0] * 3, size=64)
    return [SearchRequest(like=int(i), weights=dict(zip(spec.names, map(float, x))),
                          k=10, probes=12)
            for i, x in zip(rng.integers(0, 4000, 64), w)]


r.search(batch())
d = tempfile.mkdtemp()
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
jax.profiler.start_trace(d, profiler_options=opts)
with jax.profiler.TraceAnnotation("bench.window"):
    for _ in range(2):
        r.search(batch())
jax.profiler.stop_trace()
found = list(Path(d).rglob("*.xplane.pb"))
shutil.copy(found[0], HERE / ("sharded4.xplane.pb" if four else "fused1.xplane.pb"))
print("recorded", found[0].stat().st_size, "bytes on", jax.devices()[0].device_kind)
