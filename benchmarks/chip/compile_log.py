"""Counts XLA compiles through JAX's monitoring events.

A persistent-cache hit is counted as a compile too, since loading a
cached program still passes through the backend-compile span; the hits
are counted apart, and the programs that missed the cache are named.
"""

from __future__ import annotations


class CompileLog:
    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.missed: list[str] = []
        self._hits_seen = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, fun_name="?", **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += secs
            if self.cache_hits == self._hits_seen:
                self.missed.append(fun_name)
            self._hits_seen = self.cache_hits

    def _on_event(self, event, **_):
        if event == self.HIT:
            self.cache_hits += 1

    def snapshot(self) -> tuple[int, float, int]:
        return self.count, self.seconds, self.cache_hits
