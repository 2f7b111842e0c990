"""Backend compile seconds and persistent-cache hits, from JAX's monitoring
events (a cache hit reports its retrieval time as the compile duration)."""
from __future__ import annotations

import jax

_COMPILE = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"


class CompileMeter:
    def __init__(self):
        self.seconds, self.compiles, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == _COMPILE:
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == _HIT:
            self.hits += 1

    def mark(self):
        return self.seconds, self.compiles, self.hits
