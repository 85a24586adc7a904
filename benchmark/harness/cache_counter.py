"""Traffic of JAX's persistent compilation cache, from JAX's own monitoring
events (copied from ``chip_smoke.py:CacheCounter``). Every request for a
program that is not in this process yet is one event: a hit is read from the
directory, a miss is compiled."""

from __future__ import annotations

import jax.monitoring


class CacheCounter:
    def __init__(self):
        self.hits = self.misses = self.backend_compiles = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compiles += 1

    @property
    def programs_obtained(self) -> int:
        """Programs this process had to get, read from the cache or compiled
        (a miss is followed by a compile, so misses are not added): inside a
        measured window the count must not move."""
        return self.hits + self.backend_compiles
