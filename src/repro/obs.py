"""Spans, counters and compile events the program records about itself.

A span is one named interval of host time on ``time.perf_counter``, kept
as a ``Span``: its id, name, start and end, the id of the span it ran
inside (``None`` at the root) and the request id where it belongs to one
request.  Spans may carry counters, and the tracer keeps a running total
of every counter.  Each span is also a ``jax.profiler.TraceAnnotation``,
so while a profile runs its trace holds the span on the host's line, on
the same clock as the device's operations.

Recording is always on and bounded: a ``Tracer`` keeps its newest
``capacity`` spans and compilations in rings.  The process has one default
tracer (``tracer()``); ``repro.serve.engine.SlotServer`` records there
unless it is given its own.  A tracer is meant for one thread.

Compilations come from ``jax.monitoring``: each backend compilation is kept
as a ``Compile`` (the program's name, when it ended, its seconds), and
``compile_seconds`` sums tracing, lowering and compiling.
"""
from __future__ import annotations

import collections
import itertools
import time
from typing import NamedTuple, Optional

import jax

# tracing, lowering and backend compilation: what JAX reports for every
# program it compiles
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class Span(NamedTuple):
    id: int
    name: str
    start: float
    end: float
    parent: Optional[int]
    rid: Optional[int]
    counts: Optional[dict]

    @property
    def seconds(self) -> float:
        return self.end - self.start


class Compile(NamedTuple):
    name: str           # the compiled program, as JAX names it
    end: float          # perf_counter when the compilation ended
    seconds: float


class _Open:
    """A span while it runs; ``count`` adds to its counters."""
    __slots__ = ("tracer", "name", "rid", "id", "parent", "start", "counts",
                 "ann")

    def __init__(self, tracer: Tracer, name: str, rid: Optional[int]):
        self.tracer, self.name, self.rid = tracer, name, rid
        self.counts = None

    def count(self, **counts: int) -> None:
        if self.counts is None:
            self.counts = {}
        for k, v in counts.items():
            self.counts[k] = self.counts.get(k, 0) + v

    def __enter__(self) -> _Open:
        tr = self.tracer
        self.id = next(tr._ids)
        self.parent = tr._stack[-1] if tr._stack else None
        tr._stack.append(self.id)
        self.ann = jax.profiler.TraceAnnotation(self.name)
        self.ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        end = time.perf_counter()
        self.ann.__exit__(*exc)
        tr = self.tracer
        tr._stack.pop()
        tr.spans.append(Span(self.id, self.name, self.start, end,
                             self.parent, self.rid, self.counts))
        if self.counts:
            for k, v in self.counts.items():
                tr.totals[k] += v
        return False


class Tracer:
    """Spans and compilations in bounded rings, counters' totals."""

    def __init__(self, capacity: int = 1 << 16):
        self.spans: collections.deque[Span] = collections.deque(
            maxlen=capacity)
        self.compiles: collections.deque[Compile] = collections.deque(
            maxlen=capacity)
        self.totals: collections.Counter = collections.Counter()
        self.compile_seconds = 0.0
        self._ids = itertools.count()
        self._stack: list[int] = []
        self._listening = True
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def span(self, name: str, rid: Optional[int] = None) -> _Open:
        """``with tracer.span(name) as s:`` records the block as one span,
        inside whichever span is open; ``s.count(k=n)`` adds to its
        counters."""
        return _Open(self, name, rid)

    def _listen(self, event: str, duration: float, fun_name: str = "?",
                **_) -> None:
        if event in COMPILE_EVENTS:
            self.compile_seconds += duration
        if event == BACKEND_COMPILE:
            self.compiles.append(Compile(fun_name, time.perf_counter(),
                                         duration))

    def close(self) -> None:
        """Stop listening for compilations."""
        if self._listening:
            jax.monitoring.unregister_event_duration_listener(self._listen)
            self._listening = False

    def compiles_between(self, t0: float, t1: float) -> int:
        """Backend compilations that ended in [t0, t1]."""
        return sum(t0 <= c.end <= t1 for c in self.compiles)


_DEFAULT: Optional[Tracer] = None


def tracer() -> Tracer:
    """The process's default tracer, made on first use."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = Tracer()
    return _DEFAULT

