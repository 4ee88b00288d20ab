"""Compile accounting from JAX's monitoring events.

A copy of ``chip_smoke.py``'s ``CompileClock`` with a count beside the
seconds, so that the harness can say how many programs were compiled (or
read from the persistent cache) inside a span that should have none.
"""

from __future__ import annotations

_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    """Seconds spent in XLA backend compiles (persistent-cache reads
    included, so a warm cache shows as fewer seconds), their count, and
    the persistent-cache hits among them."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0

        def on_duration(event, secs, **_):
            if event == _COMPILE:
                self.seconds += secs
                self.count += 1

        def on_event(event, **_):
            if event == _CACHE_HIT:
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def reading(self) -> tuple[float, int, int]:
        return self.seconds, self.count, self.cache_hits
