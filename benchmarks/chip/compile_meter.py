"""Seconds JAX spends compiling, and compiles counted in a window.

Copied from ``CompileMeter`` in ``chip_smoke.py`` at the repo root, with a
count of backend compiles added: the measured window must compile nothing.
"""
from __future__ import annotations

import jax


class CompileMeter:
    """Seconds JAX spends tracing, lowering and compiling (a persistent
    cache hit counts its retrieval), backend compiles, and the cache's
    hits and misses."""

    DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration",
                 "/jax/core/compile/backend_compile_duration")
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in self.DURATIONS:
            self.seconds += secs
        if event == self.BACKEND:
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}
