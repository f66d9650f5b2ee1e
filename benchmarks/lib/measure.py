"""Spans, counters and values of one run, kept in memory.

A span is a host-clock interval recorded by the benchmark's own files around
a call into a layer; each also enters the profiler's trace as a
``TraceAnnotation`` named ``bench:<name>``, so that idle gaps of the device
can be named by what the host was doing.
"""
from __future__ import annotations

import contextlib
import time

ANNOTATION_PREFIX = "bench:"


class Measurements:
    def __init__(self):
        self.spans: dict = {}  # name -> [(start, end), ...] perf_counter s
        self.values: dict = {}  # name -> number (set-up readings)
        self.counters: dict = {}  # name -> window delta (set by the harness)
        self.window = (0.0, 0.0)
        self.trace = None  # lib.trace.TraceSummary of a traced run
        self._recording = True

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name):
            try:
                yield
            finally:
                if self._recording:
                    self.spans.setdefault(name, []).append(
                        (t0, time.perf_counter())
                    )

    def clear_window(self) -> None:
        """Forget what the warm-up recorded: the window starts clean."""
        self.spans.clear()

    def span_seconds(self, name: str) -> float:
        return sum(e - s for s, e in self.spans.get(name, ()))


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100), linear interpolation between order
    statistics, over ALL the values given."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))
