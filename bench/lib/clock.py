"""Seconds JAX spends tracing, lowering and compiling.

A copy of ``chip_smoke.CompileClock``: it reads JAX's own monitoring
events, so the benchmark needs no span inside the program.
"""
from __future__ import annotations

import jax


class CompileClock:
    """Sums the duration events of tracing to a jaxpr, lowering to MLIR and
    the backend compile (or the fetch of a compiled program from the
    persistent cache), from the moment it is made."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in self.EVENTS:
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
