"""Named host spans at the layer boundaries of the search path and the build.

A span is a :class:`jax.profiler.TraceAnnotation`: it is written into the
profiler's own trace, on the clock of the device planes, so each gap in
which the device idles falls inside the span the host was in. With no
profiler running a span costs a microsecond or two, so spans are always on:
there is no switch. They are per dispatch, per engine group or per
clustering, never per request or per hit, and each wraps statements that
run anyway: none of them waits on the device or copies from it.

The names below are the contract with whoever reads a trace (the
benchmark's per-layer readers match them letter for letter). Capture them
from a live server with ``python -m repro.launch.serve --profile-port N``
and a profiler client, or around any code with ``jax.profiler.trace``.

Every backend compile (a persistent-cache read included) also leaves a
marker span, :data:`COMPILE` with its ``seconds``, inside whichever span
compiled.
"""

from __future__ import annotations

import jax

# serving tier (serving/server.py)
SERVE_FLUSH = "repro.serve.flush"        # ready queues -> dispatch tasks
SERVE_CALL = "repro.serve.call"          # executor thread: one replica call
SERVE_RESPOND = "repro.serve.respond"    # stamp and resolve the tickets

# request planning, engine call, response assembly (core/api.py)
SEARCH_BATCH = "repro.search.batch"
SEARCH_PREPARE = "repro.search.prepare"
SEARCH_ENGINE = "repro.search.engine"    # host dispatch of the engine call
SEARCH_WAIT = "repro.search.wait"        # block on the scores
SEARCH_FETCH = "repro.search.fetch"      # decompose + device-to-host copies
SEARCH_ASSEMBLE = "repro.search.assemble"

# engine internals (core/engine.py)
ENGINE_PICK = "repro.engine.pick"        # marker: where "auto" resolves
ENGINE_NAVIGATE = "repro.engine.navigate"
ENGINE_SCHEDULE = "repro.engine.schedule"
ENGINE_SCORE = "repro.engine.score"
ENGINE_RESCORE = "repro.engine.rescore"

# build (core/index.py, core/cluster.py)
BUILD_CLUSTER = "repro.build.cluster"
BUILD_FPF = "repro.build.fpf"
BUILD_ASSIGN = "repro.build.assign"
BUILD_BUCKETS = "repro.build.buckets"
INDEX_PACK = "repro.index.pack"

COMPILE = "repro.compile"

# ``span(name, **args)``: a context manager; ``args`` become the event's stats.
span = jax.profiler.TraceAnnotation

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def _mark_compile(event: str, seconds: float, **_) -> None:
    if event == _BACKEND_COMPILE:
        with span(COMPILE, seconds=seconds):
            pass


jax.monitoring.register_event_duration_secs_listener(_mark_compile)
