"""Compiles and persistent-cache reads, from JAX's monitoring events.

``/jax/core/compile/backend_compile_duration`` is recorded around every
fetch of an executable, whether XLA compiles it or it comes from the
persistent cache; ``/jax/compilation_cache/cache_hits`` marks the
latter. So compiles = backend events − cache hits.
"""
from __future__ import annotations

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileStats:
    def __init__(self):
        import jax
        self.fetches = 0
        self.hits = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == CACHE_HIT:
            self.hits += 1

    def _duration(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.fetches += 1

    @property
    def compiles(self) -> int:
        return self.fetches - self.hits
