"""The benchmark's own spans, around its calls into the program.

Kept in memory on the host's clock; with ``traced=True`` each span is also
written into the profiler's trace (``jax.profiler.TraceAnnotation``), which
puts it on the device events' clock so that an idle gap on the device can
be named after what the host was doing in it.
"""

from __future__ import annotations

import contextlib
import time

PREFIX = "bench:"


class Spans:
    def __init__(self):
        self.traced = False
        self.records: dict[str, list[tuple[float, float]]] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        ctx = contextlib.nullcontext()
        if self.traced:
            import jax

            ctx = jax.profiler.TraceAnnotation(PREFIX + name)
        t0 = time.perf_counter()
        try:
            with ctx:
                yield
        finally:
            self.records.setdefault(name, []).append((t0, time.perf_counter()))

    def durations(self, name: str) -> list[float]:
        return [b - a for a, b in self.records.get(name, [])]

    def total(self, name: str) -> float:
        return sum(self.durations(name))
