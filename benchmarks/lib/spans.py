"""Host spans the benchmark records around its calls into each layer.

A span is recorded twice on purpose: on the host clock (totals, for the
`host_span_total` reader) and as a `jax.profiler.TraceAnnotation` named
`bench/<name>`, which lands in the profiler's own trace on the same clock
as the device events, so that an idle gap can be named by the span that
covers it.  Spans stay in memory; nothing is written during a window.
"""

import contextlib
import time

PREFIX = "bench/"


class Spans:
    def __init__(self):
        self.total_s = {}
        self.values = {}

    def mark(self):
        self.total_s, self.values = {}, {}

    @contextlib.contextmanager
    def span(self, name):
        import jax.profiler

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(PREFIX + name):
            try:
                yield
            finally:
                dt = time.perf_counter() - t0
                self.total_s[name] = self.total_s.get(name, 0.0) + dt

    def value(self, name, v):
        """A number the program handed back (a pause it measured itself)."""
        self.values.setdefault(name, []).append(float(v))
