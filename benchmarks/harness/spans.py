"""The harness's own spans around the calls into the program.

Each span is written twice: into the profiler's trace through
``jax.profiler.TraceAnnotation`` (so it sits on the device trace's clock and
idle gaps can be attributed to it) and into memory with the host clock (so
untraced runs can still report counts and durations). Spans inside the
program are the ``tracing`` issue that follows.
"""

from __future__ import annotations

import contextlib
import threading
import time

PREFIX = "bench."   # what the spans are called in the profiler's trace
WINDOW = "window"   # the span around the whole measured window


class Spans:
    def __init__(self):
        self._lock = threading.Lock()
        self.records: list[tuple[str, float, float]] = []  # name, start, end

    @contextlib.contextmanager
    def span(self, name: str):
        from jax.profiler import TraceAnnotation

        t0 = time.perf_counter()
        with TraceAnnotation(PREFIX + name):
            try:
                yield
            finally:
                t1 = time.perf_counter()
                with self._lock:
                    self.records.append((name, t0, t1))
