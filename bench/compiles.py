"""Counts JAX's traces and backend compiles, so a run can show that
nothing compiled inside its measured window (a program served from the
persistent cache still counts its trace)."""
from __future__ import annotations

EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/backend_compile_duration")


class CompileCounter:
    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in EVENTS:
            self.count += 1
