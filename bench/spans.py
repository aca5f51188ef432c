"""Host spans recorded by the benchmark around its calls into the program.

A span is (name, start, end) on ``time.perf_counter``. With ``annotate``
each span is also written into the profiler's trace as a
``TraceAnnotation`` of the same name, so the trace reduction can say
what the host was doing in each idle gap of the device.
"""
from __future__ import annotations

import contextlib
import time


class Spans:
    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.records: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        if self.annotate:
            import jax
            ctx = jax.profiler.TraceAnnotation(name)
        else:
            ctx = contextlib.nullcontext()
        t0 = time.perf_counter()
        with ctx:
            yield
        self.records.append((name, t0, time.perf_counter()))

    def clear(self) -> None:
        self.records.clear()

    def total(self, name: str) -> float:
        return sum(e - s for n, s, e in self.records if n == name)

    def count(self, name: str) -> int:
        return sum(1 for n, _, _ in self.records if n == name)
