"""Compile seconds and persistent-cache hits from JAX's own monitoring
events (the same listener ``chip_smoke.py`` uses)."""
from __future__ import annotations


class CompileClock:
    """Counts backend compiles (cache retrievals included), their seconds,
    and persistent compile-cache hits since construction."""

    def __init__(self):
        import jax
        self.seconds, self.hits, self.count = 0.0, 0, 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.count += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
