"""Host spans that the benchmark records around the program's layers.

In a traced run the benchmark wraps the ``collect`` and ``execute_many``
methods of the Session instance that the Server holds, and its own call of
``Server.drain``. Each span is kept in memory on the host clock and also
emitted as a ``jax.profiler.TraceAnnotation`` named ``bench.<name>``, so
that it lands in the device trace on the trace's clock.
"""
from __future__ import annotations

import contextlib
import time
from typing import List, NamedTuple

import jax


class Span(NamedTuple):
    name: str
    start: float
    end: float


class Spans:
    def __init__(self):
        self.records: List[Span] = []

    @contextlib.contextmanager
    def span(self, name: str):
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.records.append(Span(name, t0, time.perf_counter()))

    def wrap(self, obj, method: str, name: str) -> None:
        """Shadow ``obj.method`` on the instance with a spanned call."""
        inner = getattr(obj, method)

        def spanned(*args, **kwargs):
            with self.span(name):
                return inner(*args, **kwargs)

        setattr(obj, method, spanned)

    def total(self, name: str) -> float:
        """Seconds of all ``name`` spans."""
        return sum(s.end - s.start for s in self.records if s.name == name)

    def count(self, name: str) -> int:
        return sum(1 for s in self.records if s.name == name)
