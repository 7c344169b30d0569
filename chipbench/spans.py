"""Host spans the harness records around its calls into the program, and
JAX's compile events.

Spans are kept in memory as (name, start, end) on ``time.perf_counter``.
While the profiler runs, each span is also a ``TraceAnnotation``, so the
trace holds it on the same clock as the device's operations and idle gaps
can be put down to what the host was doing.
"""
from __future__ import annotations

import contextlib
import time

import jax

# trace, lowering and compile: what JAX reports for every compilation
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Sums JAX's trace, lowering and compile seconds and counts backend
    compilations, with the time each one ended."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles: list[float] = []

    def _listen(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.seconds += duration
        if event == BACKEND_COMPILE:
            self.compiles.append(time.perf_counter())

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._listen)
        return False

    def count_between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.compiles)


class Spans:
    """Named host intervals; ``annotate`` also writes them to the trace."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.items: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        ann = (jax.profiler.TraceAnnotation(name) if self.annotate
               else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ann:
            try:
                yield
            finally:
                self.items.append((name, t0, time.perf_counter()))

    def wrap(self, name: str, fn):
        """``fn`` with every call recorded as span ``name``."""
        def wrapped(*a, **kw):
            with self.span(name):
                return fn(*a, **kw)
        return wrapped

    def total(self, name: str, t0: float, t1: float) -> float:
        """Seconds of span ``name`` that lie inside [t0, t1]."""
        return sum(max(0.0, min(e, t1) - max(s, t0))
                   for n, s, e in self.items if n == name)
