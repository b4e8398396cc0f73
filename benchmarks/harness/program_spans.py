"""The program's own spans, read where the harness's are read.

``deeplearning4j_tpu.telemetry.spans.span`` enters a
``jax.profiler.TraceAnnotation`` under a name that starts with ``dl4j.``, so
in a traced run each span is a host event of the window's ``.xplane.pb`` on
the device trace's clock (``docs/observability.md`` lists the names). This
module reads them once per trace and offers the two things the per-layer
metrics ask for:

- ``idle_ms_per_dispatch(run, names)``: device-idle milliseconds of device 0
  that fall inside spans of those names, per ``dl4j.fit.dispatch`` span in
  the window. Per dispatch, not per boundary: a window with one dispatch
  still gives a number, and in a steady run a dispatch has one boundary, so
  the parts add up to ``dispatch_gap_ms``.
- ``span_seconds(name)``: the seconds the program's span store
  (``dl4jtpu_span_seconds{name}`` in its default registry) holds for a name,
  for what runs in set-up and so never inside the traced window.

A program without the spans (the parent of the PR that added them) gives
``None`` from both, and the line leaves the metric out; a program with them
gives a number, 0.0 where nothing fell under the name.
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass

from . import trace as tr

PREFIX = "dl4j."
DISPATCH = "dl4j.fit.dispatch"
SPAN_SECONDS = "dl4jtpu_span_seconds"  # the program's histogram family


@dataclass(frozen=True)
class ProgramSpan:
    name: str     # with its ``dl4j.`` prefix
    start: float  # ns on the trace's clock
    end: float


@functools.lru_cache(maxsize=4)
def _load(path: str, _mtime: float) -> tuple:
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    spans.append(ProgramSpan(e.name, e.start_ns,
                                             e.start_ns + e.duration_ns))
    return tuple(sorted(spans, key=lambda s: s.start))


def load(directory: str) -> tuple:
    """Every ``dl4j.*`` host event of the ``.xplane.pb`` files under
    ``directory``, sorted by start (read once per file)."""
    spans = []
    for path in tr.find_xplane_files(directory):
        spans.extend(_load(path, os.path.getmtime(path)))
    return tuple(sorted(spans, key=lambda s: s.start))


def of_run(run) -> tuple:
    return load(run.trace_dir)


def idle_inside(trace, spans, names, device_index: int = 0) -> tuple:
    """``(idle_ns, dispatches)``: device-idle nanoseconds of one device that
    fall inside the spans called ``names`` (clipped to the window), and the
    number of ``dl4j.fit.dispatch`` spans that start in the window."""
    lo, hi = trace.window
    inside = tr.union(tr.clip([(s.start, s.end) for s in spans
                               if s.name in names], lo, hi))
    gaps = trace.gaps(device_index)
    idle = tr.total(inside) - tr.total(tr.subtract(inside, gaps))
    dispatches = sum(1 for s in spans
                     if s.name == DISPATCH and lo <= s.start < hi)
    return idle, dispatches


def idle_ms_per_dispatch(run, names) -> float | None:
    if run.trace is None:
        return None
    idle, dispatches = idle_inside(run.trace, of_run(run), set(names))
    return idle / 1e6 / dispatches if dispatches else None


def span_seconds(name: str) -> float | None:
    """Seconds under ``name`` in the program's span store, whole process."""
    try:
        from deeplearning4j_tpu.telemetry import get_registry
    except ImportError:
        return None
    family = get_registry().snapshot().get(SPAN_SECONDS)
    if family is None:  # a program whose spans keep no seconds
        return None
    return float(sum(row["sum"] for row in family["values"]
                     if row["labels"].get("name") == name))
