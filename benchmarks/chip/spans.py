"""Host spans and compile counts, recorded by the benchmark around the calls
it makes into the program.

A span is ``(name, start, end)`` on ``time.perf_counter``.  With
tracing on, each span also opens a ``jax.profiler.TraceAnnotation`` named
``bench:<name>`` so the trace reduction can put host activity on the
device's clock.
"""

from __future__ import annotations

import contextlib
import time


class Spans:
    def __init__(self, traced: bool = False):
        self.traced = traced
        self.records: list = []

    @contextlib.contextmanager
    def span(self, name: str):
        annotation = None
        if self.traced:
            import jax
            annotation = jax.profiler.TraceAnnotation(f"bench:{name}")
            annotation.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if annotation is not None:
                annotation.__exit__(None, None, None)
            self.records.append((name, t0, t1))

    def durations(self, name: str, since: float = float("-inf"),
                  until: float = float("inf")) -> list:
        return [t1 - t0 for n, t0, t1 in self.records
                if n == name and t0 >= since and t1 <= until]


class CompileCounter:
    """Backend compiles that JAX reports, less those served by the
    persistent cache: ``jax.monitoring`` records the backend-compile
    duration around every lookup, and a cache hit besides."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.compiles = 0
        self.hits = 0
        self.names: list = []

    def _on_duration(self, event, duration, **kwargs):
        if event == self._COMPILE:
            self.compiles += 1
            self.names.append(kwargs.get("fun_name", ""))

    def _on_event(self, event, **kwargs):
        if event == self._HIT:
            self.hits += 1

    def __enter__(self):
        from jax._src import monitoring
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self._on_duration)
        monitoring.unregister_event_listener(self._on_event)

    def snapshot(self) -> tuple:
        return self.compiles, self.hits

    def since(self, snap: tuple) -> int:
        """Compiles not served by the cache since ``snap``."""
        return (self.compiles - snap[0]) - (self.hits - snap[1])
