"""XLA compiles and persistent-cache hits, from JAX's own monitoring events."""
from __future__ import annotations

import jax

__all__ = ["CompileLog"]


class CompileLog:
    """Counts programs loaded (each fires JAX's backend-compile event,
    whether the persistent cache served it or XLA compiled it), their
    seconds, and persistent-cache hits.  ``mark()`` snapshots the counts so
    a stretch of the run (set-up, the window) reads as a difference;
    programs compiled = loaded - hits."""

    def __init__(self):
        self.loaded = 0
        self.load_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.loaded += 1
            self.load_s += duration

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self) -> tuple[int, float, int]:
        return self.loaded, self.load_s, self.cache_hits
