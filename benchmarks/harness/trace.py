"""Capture a profiler trace of the measured window and reduce it to what the
per-layer metrics read: per device the intervals in which an operation ran,
each with its HLO opcode and a bucket; on the host the harness's own spans
(``bench.*`` TraceAnnotations), on the same clock.

Extends ``scripts/analyze_trace.py`` (PR 22 inventory): that script split
"XLA Ops" from "Async XLA Ops" and dropped control-flow umbrellas, but summed
durations; here busy time is the union of intervals over the window, so it
gives a busy/idle share, exposed collective time and idle gaps attributed to
what the host was doing.

What a v5e trace of today's code looks like (one look, PR 23): the device
plane ``/device:TPU:<n>`` has the lines "XLA Modules", "XLA Ops" (synchronous
execution windows, event name = the HLO instruction's text), "Async XLA Ops"
(copy-start..copy-done style spans that overlap compute) and sometimes
"Steps"; ``/host:CPU`` has one line per thread, the Python threads named
"python", carrying the TraceAnnotations. All timestamps are ns on one clock.
"""

from __future__ import annotations

import functools
import glob
import os
import re
from dataclasses import dataclass, field

from .spans import PREFIX, WINDOW

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
UMBRELLAS = {"while", "conditional", "call"}  # span their body's own events
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'
COPY_OPCODES = {"copy", "copy-start", "copy-done", "transpose", "bitcast",
                "reshape", "slice", "dynamic-slice", "dynamic-update-slice",
                "concatenate", "pad"}


# ------------------------------------------------------------------ capture
def start(directory: str) -> None:
    """Start the profiler with the Python tracer off (it records every call
    and would both slow the host and swell the file)."""
    import jax
    from jax.profiler import ProfileOptions

    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    os.makedirs(directory, exist_ok=True)
    jax.profiler.start_trace(directory, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


# ---------------------------------------------------------------- HLO names
def _skip_shape(text: str) -> str:
    """``text`` after one HLO shape: a tuple ``(...)`` or ``dtype[dims]{layout}``."""
    text = text.lstrip()
    if text.startswith("("):
        depth = 0
        for i, ch in enumerate(text):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                return text[i + 1:]
        return ""
    m = re.match(r"[\w]+\[[^\]]*\](\{[^}]*\})?", text)
    return text[m.end():] if m else text


@functools.lru_cache(maxsize=None)  # a step's ops repeat every step
def opcode_of(event_name: str) -> str:
    """The HLO opcode of an "XLA Ops" event, whose name is the instruction's
    text: ``%name = <shape> opcode(operands), attrs``. A name that is not in
    that form is its own opcode."""
    head, sep, rest = event_name.partition(" = ")
    if not sep:
        return event_name.lstrip("%").split("(")[0].split(".")[0]
    m = re.match(r"\s*([\w\-]+)\(", _skip_shape(rest))
    return m.group(1) if m else "unknown"


def op_label(event_name: str) -> str:
    """``%name`` without the instruction text: what a breakdown prints."""
    return event_name.partition(" = ")[0].lstrip("%")


@functools.lru_cache(maxsize=None)
def bucket_of(event_name: str) -> str:
    """One of ``collective, pallas, mxu, copy, reduce, elementwise, other``.

    ``mxu``: convolutions, dots and the output fusions rooted in one
    (``kind=kOutput``; on the TPU that is how XLA fuses into a convolution).
    ``pallas``: a Mosaic kernel. ``copy``: data movement and layout changes
    that compute nothing, alone or as a fusion named for it."""
    op = opcode_of(event_name)
    label = op_label(event_name)
    if op.startswith(COLLECTIVES) or (
            op in ("async-start", "async-done", "async-update")
            and any(c in label for c in COLLECTIVES)):
        return "collective"
    if op == "custom-call":
        return "pallas" if MOSAIC_TARGET in event_name else "other"
    if op in ("convolution", "dot"):
        return "mxu"
    if op in COPY_OPCODES or (op in ("async-start", "async-done")
                              and label.startswith(("slice", "copy",
                                                    "dynamic-slice"))):
        return "copy"
    if op == "fusion":
        if label.startswith(("copy", "transpose", "bitcast_fusion",
                             "slice", "dynamic-slice", "pad",
                             "concatenate")):
            return "copy"
        if "kind=kOutput" in event_name or label.startswith(
                ("convolution", "dot")):
            return "mxu"
        if "kind=kInput" in event_name or "reduce" in label:
            return "reduce"
        return "elementwise"
    if op.startswith("reduce") or op == "select-and-scatter":
        return "reduce"
    return "other"


# ------------------------------------------------------------ interval sets
def union(intervals):
    """Merged, sorted, non-overlapping ``[(start, end)]``."""
    out = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals) -> float:
    return float(sum(b - a for a, b in intervals))


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a, b):
    """The part of the merged set ``a`` that no interval of merged ``b`` covers."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def innermost(spans) -> dict:
    """``{name: [(start, end)]}``: for each name the parts of its spans that
    no span started later covers. Spans of one thread nest, so that is the
    innermost one; where two threads' spans overlap the later start wins."""
    out, stack, cursor = {}, [], 0.0

    def close(upto):
        # what lies between the cursor and ``upto`` is the top span's own
        nonlocal cursor
        if upto > cursor:
            out.setdefault(stack[-1].name, []).append((cursor, upto))
            cursor = upto

    for s in sorted(spans, key=lambda s: (s.start, -s.end)):
        while stack and stack[-1].end <= s.start:
            close(stack[-1].end)
            stack.pop()
        if stack:
            close(s.start)
        cursor = max(cursor, s.start)
        stack.append(s)
    while stack:
        close(stack[-1].end)
        stack.pop()
    return out


# -------------------------------------------------------------------- model
@dataclass
class Op:
    start: float
    end: float
    name: str      # the event's full name (HLO text)
    bucket: str


@dataclass
class DeviceTrace:
    name: str
    ops: list = field(default_factory=list)        # "XLA Ops" minus umbrellas
    async_ops: list = field(default_factory=list)  # "Async XLA Ops"

    def busy(self, window):
        return union(clip([(o.start, o.end) for o in self.ops], *window))


@dataclass
class HostSpan:
    name: str     # without the ``bench.`` prefix
    start: float
    end: float


@dataclass
class TraceData:
    """Times are nanoseconds on the trace's clock."""

    devices: list
    spans: list     # HostSpan, sorted by start
    window: tuple   # (start, end): the ``bench.window`` span, else the ops' extent

    # -- the numbers the final line carries
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        return sum(total(d.busy(self.window)) for d in self.devices) \
            / len(self.devices) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s()

    # -- shares of device-busy time
    def bucket_seconds(self) -> dict:
        """Per bucket the union of its ops' intervals, mean over devices."""
        out = {}
        for d in self.devices:
            per = {}
            for o in d.ops:
                per.setdefault(o.bucket, []).append((o.start, o.end))
            for b, iv in per.items():
                out[b] = out.get(b, 0.0) + total(union(clip(iv, *self.window)))
        return {b: v / len(self.devices) / 1e9 for b, v in out.items()}

    def bucket_share(self, bucket: str) -> float:
        busy = self.busy_s()
        return self.bucket_seconds().get(bucket, 0.0) / busy if busy else 0.0

    # -- collectives
    def collective_seconds(self) -> tuple:
        """``(running, exposed)``: seconds in which a collective ran on a
        device, and the part of them in which no other operation did; mean
        over devices. A collective's interval is its synchronous op on "XLA
        Ops" (the ``-done`` of an async pair is where the device waits) and
        its span on "Async XLA Ops"."""
        running = exposed = 0.0
        for d in self.devices:
            coll = union(clip(
                [(o.start, o.end) for o in d.ops if o.bucket == "collective"]
                + [(o.start, o.end) for o in d.async_ops
                   if o.bucket == "collective"], *self.window))
            compute = union(clip([(o.start, o.end) for o in d.ops
                                  if o.bucket != "collective"], *self.window))
            running += total(coll)
            exposed += total(subtract(coll, compute))
        n = len(self.devices) * 1e9
        return running / n, exposed / n

    # -- idle gaps, attributed to what the host was doing
    def gaps(self, device_index: int = 0):
        d = self.devices[device_index]
        return subtract([self.window], d.busy(self.window))

    def span_at(self, t: float) -> str:
        """The innermost harness span (other than the window) covering ``t``."""
        best = None
        for s in self.spans:
            if s.start > t:
                break
            if s.end >= t and s.name != WINDOW:
                if best is None or s.start >= best.start:
                    best = s
        return best.name if best else "outside_spans"

    def gap_seconds_by_span(self, device_index: int = 0) -> dict:
        out = {}
        for a, b in self.gaps(device_index):
            name = self.span_at((a + b) / 2)
            out[name] = out.get(name, 0.0) + (b - a) / 1e9
        return out

    def gaps_between(self, span_name: str, device_index: int = 0) -> list:
        """For each pair of consecutive ``span_name`` spans, the device-idle
        seconds between the last op started under the first and the first op
        after it that runs under the second: the gap a dispatch boundary
        leaves on the device."""
        busy = self.devices[device_index].busy(self.window)
        spans = [s for s in self.spans if s.name == span_name]
        out = []
        for cur, nxt in zip(spans, spans[1:]):
            # from the middle of one span to the middle of the next there is
            # exactly one boundary; what is idle in between is its gap
            lo, hi = (cur.start + cur.end) / 2, (nxt.start + nxt.end) / 2
            out.append(total(subtract([(lo, hi)], busy)) / 1e9)
        return out

    def gap_seconds_by_program_span(self, program_spans,
                                    device_index: int = 0) -> dict:
        """Idle seconds of one device under the innermost span of the
        program that covers them (``program_spans``: anything with ``name``,
        ``start`` and ``end`` on this clock, as ``program_spans.load``
        gives). Idle time under no span of the program keeps the harness's
        name for it (``gap_seconds_by_span``), so the parts still add up to
        the window's idle time."""
        gaps = self.gaps(device_index)
        out = {}
        for name, iv in innermost(program_spans).items():
            iv = union(clip(iv, *self.window))
            idle = total(iv) - total(subtract(iv, gaps))
            if idle > 0:
                out[name] = idle / 1e9
        covered = union([(s.start, s.end) for s in program_spans])
        for a, b in subtract(gaps, covered):
            name = self.span_at((a + b) / 2)
            out[name] = out.get(name, 0.0) + (b - a) / 1e9
        return out

    def breakdown(self, top: int = 10, program_spans=()) -> dict:
        per = {}
        for d in self.devices:
            for o in d.ops:
                if self.window[0] <= o.start < self.window[1]:
                    # fusion.12 and fusion.97 are one row: a step has
                    # thousands of ops and ten rows must say something
                    key = f"{o.bucket}:" + re.sub(
                        r"[.\d]+$", "", op_label(o.name))
                    per[key] = per.get(key, 0.0) + (o.end - o.start)
        n = len(self.devices) * 1e9
        ops = sorted(((k, v / n) for k, v in per.items()),
                     key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gap_seconds_by_program_span(program_spans).items(),
                      key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


# --------------------------------------------------------------------- load
def _cpu_backend_ops(host_planes) -> list:
    """The CPU backend has no device plane: its XLA ops run on host threads
    of ``/host:CPU``. Read them as one pseudo-device so that the traced path
    can be rehearsed end to end without a chip. Only ``load``'s
    ``allow_cpu_backend``, which the tests alone pass, reaches this."""
    dev = DeviceTrace("cpu-backend")
    for plane in host_planes:
        for line in plane.lines:
            if line.name == "python":
                continue
            for e in line.events:
                if e.name.startswith(("$", "ThunkExecutor", PREFIX)):
                    continue
                dev.ops.append(Op(e.start_ns, e.start_ns + e.duration_ns,
                                  e.name, bucket_of(e.name)))
    return [dev] if dev.ops else []


def find_xplane_files(directory: str):
    return sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                            recursive=True))


def load(directory: str, max_devices: int | None = None,
         allow_cpu_backend: bool = False) -> TraceData:
    """Reduce the ``.xplane.pb`` under ``directory``. Raises when no device
    plane with an operation is in it: a traced run in which nothing ran on
    the device has measured nothing, and host events never stand in for it
    (``allow_cpu_backend`` is for the tests' rehearsal on the CPU)."""
    from jax.profiler import ProfileData

    files = find_xplane_files(directory)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    devices, spans, host_planes = [], [], []
    for path in files:
        for plane in ProfileData.from_file(path).planes:
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE in lines:
                dev = DeviceTrace(plane.name)
                for e in lines[OPS_LINE].events:
                    if opcode_of(e.name) in UMBRELLAS:
                        continue
                    dev.ops.append(Op(e.start_ns, e.start_ns + e.duration_ns,
                                      e.name, bucket_of(e.name)))
                if ASYNC_LINE in lines:
                    for e in lines[ASYNC_LINE].events:
                        dev.async_ops.append(Op(
                            e.start_ns, e.start_ns + e.duration_ns, e.name,
                            bucket_of(e.name)))
                if dev.ops:
                    devices.append(dev)
            elif plane.name.startswith("/host"):
                host_planes.append(plane)
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(PREFIX):
                            spans.append(HostSpan(
                                e.name.removeprefix(PREFIX), e.start_ns,
                                e.start_ns + e.duration_ns))
    if not devices and allow_cpu_backend:
        devices = _cpu_backend_ops(host_planes)
    if not devices:
        raise ValueError(f"no device plane with an operation on its "
                         f"{OPS_LINE!r} line in the trace under {directory}")
    devices.sort(key=lambda d: d.name)
    if max_devices is not None:
        devices = devices[:max_devices]
    spans.sort(key=lambda s: s.start)
    win = next((s for s in spans if s.name == WINDOW), None)
    if win is not None:
        window = (win.start, win.end)
    else:
        window = (min(o.start for d in devices for o in d.ops),
                  max(o.end for d in devices for o in d.ops))
    return TraceData(devices=devices, spans=spans, window=window)
