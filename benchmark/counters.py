"""Counts the benchmark reads off JAX itself, not off the program."""

from __future__ import annotations

from typing import Dict


class CompileCounter:
    """JAX's own monitoring events: executables built in this process, and
    how many requests the persistent cache answered."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        from jax import monitoring

        self.compiles = self.requests = self.hits = 0
        self.seconds = 0.0
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event, **_kw) -> None:
        if event == self.REQUEST:
            self.requests += 1
        elif event == self.HIT:
            self.hits += 1

    def _on_duration(self, event, secs, **_kw) -> None:
        if event == self.BUILD:
            self.compiles += 1
            self.seconds += secs

    def snapshot(self):
        return (self.compiles, self.requests, self.hits, self.seconds)

    def since(self, snap=(0, 0, 0, 0.0)) -> Dict[str, float]:
        """Counts since ``snap``; with none given, since the process began."""
        c, r, h, s = (a - b for a, b in zip(self.snapshot(), snap))
        return {"compiles": c, "cache_hits": h, "cache_misses": r - h, "compile_seconds": round(s, 3)}
