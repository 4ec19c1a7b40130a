"""What JAX traced, lowered and compiled, and when: a listener on
``jax.monitoring``. A persistent-cache hit still traces and lowers, so a
program that appears inside the window cannot hide behind the cache."""

import time

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    """``events``: (name, seconds, time.perf_counter() when it ended)."""

    def __init__(self):
        import jax

        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, *args, **kwargs):
        if event in (TRACE, LOWER, COMPILE):
            self.events.append((event, float(duration), time.perf_counter()))

    def count_since(self, mark: int) -> int:
        return len(self.events) - mark

    def seconds(self, *names, until=None) -> float:
        return sum(d for n, d, t in self.events
                   if n in names and (until is None or t <= until))
