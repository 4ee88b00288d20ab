"""Record the small chip trace the span readers' tests read.

    python bench/tests/data/record_spans.py

On one TPU chip it writes ``fused1_spans.xplane.pb``: ``record_trace.py``'s
small three-field corpus in one clustering of 16 leaders, built inside a
``bench.build`` span (the build and its first answer, the span ``build_s``
times), then two dispatches of 8 requests through a ``SearchServer``
inside a ``bench.window`` span, the profiler's Python tracer off as the
harness has it. (Sizes keep the file under 2 MB.) The program's own spans
(``repro.tracing``) land in it beside the device's planes. The same index
is built once untraced first, so that the traced build compiles nothing,
as in a run with a warm compilation cache.
"""

import asyncio
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
from repro.serving import SearchServer  # noqa: E402

corpus = {"n_docs": 4000, "field_names": ["title", "authors", "abstract"],
          "field_dims": [128, 128, 256], "vocab_sizes": [800, 1200, 3000],
          "terms_per_field": [8, 3, 80], "n_topics": 16,
          "salient_per_topic": 60, "topic_mix_alpha": 1.0,
          "noise_terms": [4, 2, 24]}
docs = jax.device_put(make_corpus(corpus, 1))
spec = FieldSpec(names=tuple(corpus["field_names"]),
                 dims=tuple(corpus["field_dims"]))
rng = np.random.default_rng(1)


def batch(n):
    w = rng.dirichlet([1.0] * 3, size=n)
    return [SearchRequest(like=int(i), weights=dict(zip(spec.names, map(float, x))),
                          k=10, probes=12)
            for i, x in zip(rng.integers(0, 4000, n), w)]


def build():
    r = Retriever.build(docs, spec, 16, n_clusterings=1, backend="fused",
                        key=jax.random.PRNGKey(1))
    r.search(batch(1))
    return r


async def serve(r):
    async with SearchServer(r, window_s=0.002, max_batch=8) as server:
        for _ in range(2):
            await asyncio.gather(*(server.submit(q) for q in batch(8)))


build().search(batch(8))          # compiles every program traced below
d = tempfile.mkdtemp()
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
jax.profiler.start_trace(d, profiler_options=opts)
with jax.profiler.TraceAnnotation("bench.build"):
    r = build()
with jax.profiler.TraceAnnotation("bench.window"):
    asyncio.run(serve(r))
jax.profiler.stop_trace()
found = list(Path(d).rglob("*.xplane.pb"))
shutil.copy(found[0], HERE / "fused1_spans.xplane.pb")
print("recorded", found[0].stat().st_size, "bytes on", jax.devices()[0].device_kind)
