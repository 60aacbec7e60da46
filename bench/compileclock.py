"""Counts JAX's compile events, so a run can show what compiled where.

JAX reports each backend compile, and each read of a program back from the
persistent cache, as one `backend_compile_duration` event; tracing and
lowering have events of their own. The clock sums their seconds and counts
them, with the cache's hits, from the moment it is made.
"""
from __future__ import annotations

import threading

BACKEND = "/jax/core/compile/backend_compile_duration"
EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration", BACKEND)
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.secs = 0.0
        self.compiles = 0      # backend compiles and persistent-cache reads
        self.hits = 0          # persistent-cache reads
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in EVENTS:
            with self._lock:
                self.secs += secs
                self.compiles += event == BACKEND

    def _event(self, event, **_):
        if event == CACHE_HIT:
            with self._lock:
                self.hits += 1

    def snapshot(self) -> dict:
        with self._lock:
            return dict(secs=self.secs, compiles=self.compiles,
                        hits=self.hits)

    def since(self, snap: dict) -> dict:
        now = self.snapshot()
        return {k: now[k] - snap[k] for k in now}
