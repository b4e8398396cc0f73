"""What the per-layer metrics of ``fit(iterator)`` ask of the program's spans
(``program_spans`` reads them from the traced window's ``.xplane.pb``):

- ``span_share(trace, spans, names)``: seconds of the spans called ``names``
  inside the traced window, over the window, in percent;
- ``idle_share(trace, spans, names)``: device-idle seconds of device 0 inside
  those spans (``program_spans.idle_inside``), over the window, in percent.

Both are shares of the window and not milliseconds a dispatch: the per-batch
path has no dispatch, and shares of one window add up to
``device_idle_share``. A ``dl4j.fit.epoch`` span in the window says that the
program has the spans: without one both give ``None`` and the line leaves the
metric out; with one, a name under which nothing fell reads 0.0.
``of_run(run, share, names)`` is what a reader calls.
"""

from __future__ import annotations

from . import program_spans
from . import trace as tr

EPOCH = "dl4j.fit.epoch"


def has_epoch(trace, spans) -> bool:
    lo, hi = trace.window
    return any(s.name == EPOCH and s.end > lo and s.start < hi for s in spans)


def span_share(trace, spans, names) -> float | None:
    if not has_epoch(trace, spans):
        return None
    lo, hi = trace.window
    inside = tr.union(tr.clip([(s.start, s.end) for s in spans
                               if s.name in names], lo, hi))
    return 100.0 * tr.total(inside) / (hi - lo)


def idle_share(trace, spans, names) -> float | None:
    if not has_epoch(trace, spans):
        return None
    idle, _ = program_spans.idle_inside(trace, spans, set(names))
    lo, hi = trace.window
    return 100.0 * idle / (hi - lo)


def of_run(run, share, names) -> float | None:
    if run.trace is None:
        return None
    return share(run.trace, program_spans.of_run(run), names)
