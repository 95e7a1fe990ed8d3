"""Compile time and count from ``jax.monitoring`` (as ``chip_smoke.py``'s
``CompileClock`` reads them)."""
from __future__ import annotations

EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration",
          "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Sums JAX's compile-phase durations (trace, lower, backend compile)
    and counts the programs traced and compiled while it is registered."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.traces = 0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in EVENTS:
            self.seconds += duration
        if event == EVENTS[0]:
            self.traces += 1
        elif event == EVENTS[2]:
            self.compiles += 1
