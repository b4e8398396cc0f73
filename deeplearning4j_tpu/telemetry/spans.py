"""Host-side spans: the program's one span primitive.

A :class:`Span` measures one named host-side region (a dispatch, its
prepare/launch/fetch phases, a compile, ``net.init()``). Names used by the
program start with ``dl4j.`` (the list is in ``docs/observability.md``).
Every span is recorded three ways, each on one clock:

- ``jax.profiler.TraceAnnotation(name)``: whenever a profiler capture is
  running, the span lies in the SAME xplane timeline as the XLA device
  slices it encloses, so device-idle time can be attributed to it.
- an in-memory Chrome trace event in the :class:`SpanRecorder` ring
  (``ph: "X"``, microseconds; ``ts`` and ``dur`` both from
  ``time.perf_counter``) carrying ``parent`` — the name of the span that
  encloses it on the same thread — and ``dispatch`` — the identifier of the
  root span it descends from (one number per ``fit_on_device`` call, or per
  epoch of ``fit``, shared by all its children). Both come from a
  thread-local stack.
- seconds and count by name in the default registry's
  ``dl4jtpu_span_seconds{name=...}`` histogram, which is where runs without
  a profiler (set-up is never inside a traced window) read them, and where
  ``/metrics`` scrapes them.

One identifier a unit of work: every span opened inside ``with
identified(window=3):`` on that thread carries ``window=3`` in its event's
args and on its ``TraceAnnotation`` (so the xplane event has it as a stat),
whoever opens it. ``fit`` names a staged window and a batch that way, and the
spans of ``fit_on_device`` under it are the window's without knowing of it.

Closing a span never syncs the device: it records wall-clock enqueue time.
Under async dispatch a span around an un-synced jit call measures dispatch,
not execution — the sync point (the host fetch) has its own span.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import List, Optional

from .registry import get_registry


class SpanRecorder:
    """Bounded collector of completed span events (Chrome trace dicts)."""

    def __init__(self, max_events: int = 100_000):
        self.max_events = int(max_events)
        self.dropped = 0
        self._lock = threading.Lock()
        self._events: List[dict] = []

    def add(self, event: dict) -> None:
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped += 1
                return
            self._events.append(event)

    @property
    def events(self) -> List[dict]:
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self.dropped = 0

    def chrome_trace(self) -> dict:
        """Trace-event-format document (load in Perfetto / chrome://tracing)."""
        return {
            "traceEvents": self.events,
            "displayTimeUnit": "ms",
            "otherData": {"producer": "deeplearning4j_tpu.telemetry"},
        }

    def export_chrome_trace(self, path: str) -> str:
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(self.chrome_trace(), fh)
        return path


_GLOBAL_RECORDER = SpanRecorder()


def get_recorder() -> SpanRecorder:
    """The process-wide default span recorder."""
    return _GLOBAL_RECORDER


_STACK = threading.local()     # .spans: the open spans of this thread;
#                                .ids: the unit of work they belong to
_ROOT_IDS = itertools.count(1)  # one per root span (next() is atomic)


def _open_spans() -> list:
    try:
        return _STACK.spans
    except AttributeError:
        _STACK.spans = []
        return _STACK.spans


def _unit_ids() -> dict:
    return getattr(_STACK, "ids", None) or {}


class identified:  # noqa: N801 - used as a function: ``with identified(...)``
    """Spans this thread opens inside carry ``ids`` (``window=``, ``batch=``:
    the ordinal of the unit of work in its epoch); an inner ``identified``
    adds to the outer one's and wins where both name a key."""

    __slots__ = ("_ids", "_outer")

    def __init__(self, **ids):
        self._ids = ids

    def __enter__(self) -> None:
        self._outer = _unit_ids()
        _STACK.ids = {**self._outer, **self._ids}

    def __exit__(self, *exc) -> None:
        _STACK.ids = self._outer


_SECONDS: dict = {}      # span name -> its child of dl4jtpu_span_seconds
_SECONDS_IN = None       # the registry those children belong to


def _seconds_of(name: str):
    """``dl4jtpu_span_seconds{name}`` of the default registry, looked up once
    a name (a per-batch span may not pay two locked lookups every time)."""
    global _SECONDS_IN
    reg = get_registry()
    if reg is not _SECONDS_IN:  # a test put another default registry in place
        _SECONDS.clear()
        _SECONDS_IN = reg
    child = _SECONDS.get(name)
    if child is None:
        child = _SECONDS[name] = reg.histogram(
            "dl4jtpu_span_seconds", "host span durations",
            labelnames=("name",)).labels(name=name)
    return child


class Span:
    """One named region; context manager or explicit ``start()``/``stop()``.

    After ``start()``, ``parent`` is the enclosing span's name (None for a
    root) and ``dispatch`` the root's identifier. ``args`` may be added to
    until ``stop()``; they land in the in-memory event, under the
    identifiers of the unit of work the thread is in (:func:`identified`)."""

    def __init__(self, name: str, **args):
        self.name = str(name)
        self.args = dict(args)
        self.ids: dict = {}
        self.parent: Optional[str] = None
        self.dispatch: Optional[int] = None
        self._annotation = None
        self._t0: Optional[float] = None
        self.duration_s: Optional[float] = None

    def start(self) -> "Span":
        if self._t0 is not None:
            raise RuntimeError(f"span {self.name!r} already started")
        stack = _open_spans()
        if stack:
            self.parent, self.dispatch = stack[-1].name, stack[-1].dispatch
        else:
            self.parent, self.dispatch = None, next(_ROOT_IDS)
        stack.append(self)
        self.ids = _unit_ids()
        try:
            import jax  # noqa: PLC0415 - keep telemetry importable without jax

            self._annotation = jax.profiler.TraceAnnotation(self.name,
                                                            **self.ids)
            self._annotation.__enter__()
        except Exception:
            self._annotation = None  # no profiler backend: host-only span
        self._t0 = time.perf_counter()
        return self

    def stop(self) -> float:
        if self._t0 is None:
            raise RuntimeError(f"span {self.name!r} was never started")
        t0, self._t0 = self._t0, None
        dur = time.perf_counter() - t0
        if self._annotation is not None:
            try:
                self._annotation.__exit__(None, None, None)
            finally:
                self._annotation = None
        stack = _open_spans()
        if self in stack:  # with it goes what it left open, if closed out of order
            del stack[stack.index(self):]
        self.duration_s = dur
        _GLOBAL_RECORDER.add({
            "name": self.name,
            "ph": "X",
            "ts": t0 * 1e6,
            "dur": dur * 1e6,
            "pid": os.getpid(),
            "tid": threading.get_ident(),
            "args": {**self.ids, **self.args, "parent": self.parent,
                     "dispatch": self.dispatch},
        })
        _seconds_of(self.name).observe(dur)
        return dur

    def __enter__(self) -> "Span":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()


def span(name: str, **args) -> Span:
    """``with span("dl4j.fit.launch"): ...`` — the usual entry point."""
    return Span(name, **args)
