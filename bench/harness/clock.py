"""Compile clock, copied from ``chip_smoke.py``: seconds and events of
JAX's own tracing, lowering and compiling.

A compile inside the measured window shows up here as a lowering or a
backend-compile event (``compiles``).  Tracing alone is counted apart
(``traces``): the program's eager ``vmap`` calls re-trace their
operations on every call without compiling anything.
"""
from __future__ import annotations


class CompileClock:
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration
            if event == self.EVENTS[0]:
                self.traces += 1
            else:
                self.compiles += 1
