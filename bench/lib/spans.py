"""The benchmark's own spans: host-clock intervals around its calls into
the program, each also written into the profiler's trace (when one is
being taken) as `bench.<name>`, so that the reduction can lay them over
the device's timeline."""

from __future__ import annotations

import time
from contextlib import contextmanager

PREFIX = "bench."


class Spans:
    def __init__(self):
        self.records = []  # (name, op index, start, end) on perf_counter

    @contextmanager
    def span(self, name: str, op: int = -1):
        import jax.profiler

        with jax.profiler.TraceAnnotation(PREFIX + name, op=op):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.records.append((name, op, t0, time.perf_counter()))

    def durations(self, name: str, lo: float = 0.0):
        """Seconds of each `name` span that started at or after `lo`."""
        return [e - s for n, _, s, e in self.records
                if n == name and s >= lo]
