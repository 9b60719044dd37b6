"""XLA backend compiles, counted from JAX's monitoring events.

A copy of the bring-up smoke's meter.  A compile served from the
persistent cache still fires the duration event (at its read time) and
also counts as a cache hit.
"""

from __future__ import annotations

from typing import Tuple


class CompileMeter:
    def __init__(self):
        import jax

        self.seconds, self.count, self.cache_hits = 0.0, 0, 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.count += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> Tuple[float, int, int]:
        return self.seconds, self.count, self.cache_hits
