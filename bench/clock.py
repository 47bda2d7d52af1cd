"""Host-side clocks: compile time from JAX's own events, and named spans."""

from __future__ import annotations

import contextlib
import time

COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or loading from
    the persistent cache), summed; how many programs it compiled or loaded,
    and how many of those the persistent cache held."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_count)

    def _on_count(self, event: str, **_) -> None:
        if event == CACHE_HIT:
            self.cache_hits += 1

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.seconds += duration
        if event == BACKEND_COMPILE:
            self.compiles += 1


class Spans:
    """Host spans by name, on the host clock and in the profiler's trace."""

    def __init__(self):
        self.seconds: dict[str, float] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.seconds[name] = self.seconds.get(name, 0.0) + time.perf_counter() - t0
