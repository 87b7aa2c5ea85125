"""Counts JAX's compilations while it is open, from ``jax.monitoring`` events.

A copy of ``CompileClock`` in the repository's ``chip_smoke.py``, with a count
of backend compilations beside the seconds.
"""
from __future__ import annotations

COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, and how many programs
    it compiled (cache hits included), while it is open."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0

    def _on_event(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.seconds += duration
        if event == BACKEND_COMPILE:
            self.compiles += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_event)
