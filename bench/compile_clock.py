"""Seconds JAX spends compiling, read from ``jax.monitoring`` events."""

from __future__ import annotations

import threading

import jax

EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration",
          "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Tracing, lowering and compiling (or loading from the persistent
    cache) summed over all threads, plus the persistent cache's hits and
    misses and the number of backend compiles."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def snapshot(self) -> dict:
        with self._lock:
            return {"seconds": self.seconds, "compiles": self.compiles,
                    "cache_hits": self.cache_hits,
                    "cache_misses": self.cache_misses}

    def _duration(self, event, duration, **_):
        if event in EVENTS:
            with self._lock:
                self.seconds += duration
                if event == EVENTS[-1]:
                    self.compiles += 1

    def _event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1
