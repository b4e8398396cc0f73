"""Kimi Delta Attention's recurrence against its roofline: the least time
the recurrence's work in the window could take, over the traced time of
**whatever implements it**: operations whose scope path holds
``kda_recurrence`` (``ops/kda.py`` runs under that ``jax.named_scope``) and
kernels named ``kda_*``. A later change that swaps the implementation moves
this number without an edit here. Source: device trace; the shapes, the chunk
and the item size are the run's own ``kda_recurrence`` selection's.

The least time is of the mathematics, once forward and once backward a KDA
sublayer a step, whatever ``remat`` recomputes. For ``B`` sequences of ``T``
positions, ``H`` heads, keys ``K`` and values ``V`` wide, chunks of ``C``:

- operations, forward, a position and head: the two score matrices and the
  triangular system over the earlier half of the chunk on average (``C/2``
  columns: ``K`` for each score, ``V`` for the system and ``V`` for the
  output's own part) and three products with the ``K x V`` state (the
  correction's read, the output's read, the update): ``2 (C/2 (2K + 2V) +
  3KV)``; backward twice that;
- bytes, forward: ``q``, ``k``, ``v`` read and ``o`` written once at the
  run's item size, the float32 log-decays ``g`` (``K`` a head) and ``beta``
  read once; backward: those and their cotangents, twice the forward's;
- each pass the larger of its operations over the chip's bf16 peak and its
  bytes over the HBM peak (``run.peaks``); both are bound by their bytes at
  the published shapes (0.49 and 0.99 ms a sublayer).

Steps in the window are the generator's own count (``attempted``), as
``train_step_mfu`` takes its samples; the KDA sublayers are the ``b<i>K_*``
vertices seen over those operations. Nothing where the run made no
``kda_recurrence`` selection (a program from before the site), where its
selections name more than one shape, where no such operation is in the
window, or where the trace has no device plane (the tests' CPU rehearsal: a
share of a roofline is a device's time over a device's peaks, never 0 and
never a host's).
"""

import re

from benchmarks.harness import scopes
from benchmarks.harness import trace as tr

SCOPE = "kda_recurrence"
KERNELS = ("kda_",)
KDA_VERTEX = re.compile(r"^b\d+K_")
NO_DEVICE_PLANE = "cpu-backend"   # harness/trace.py's stand-in device


def flops_and_bytes(B, T, H, K, V, chunk, itemsize):
    """``((forward FLOPs, bytes), (backward FLOPs, bytes))`` of one KDA
    sublayer's recurrence over one batch."""
    tokens = float(B) * T * H
    flops = tokens * 2.0 * (chunk / 2.0 * (2 * K + 2 * V) + 3.0 * K * V)
    moved = tokens * (itemsize * (2 * K + 2 * V) + 4.0 * (K + 1))
    return (flops, moved), (2.0 * flops, 2.0 * moved)


def least_seconds(shapes, peaks) -> float:
    """Of one sublayer's forward and backward pass over one batch."""
    return sum(max(flops / peaks["bf16_flops_per_s"],
                   moved / peaks["hbm_bytes_per_s"])
               for flops, moved in flops_and_bytes(*shapes))


def implements(kernel, path) -> bool:
    return (kernel or "").startswith(KERNELS) or SCOPE in path


def traced(trace, joined):
    """``(seconds, sublayers)``: the window's time in whatever implements
    the recurrence (mean over devices) and the KDA vertices it ran under."""
    spent, vertices = 0.0, set()
    for dev in trace.devices:
        iv = []
        for op in dev.ops:
            kernel, path, _ = joined.of(op)
            if implements(kernel, path):
                iv.append((op.start, op.end))
                vertices.update(s for s in path if KDA_VERTEX.match(s))
        spent += tr.total(tr.union(tr.clip(iv, *trace.window)))
    return spent / len(trace.devices) / 1e9, len(vertices)


def shapes_of(run):
    """``(B, T, H, K, V, chunk, itemsize)`` of the run's ``kda_recurrence``
    selections; ``None`` where the run made none, or they differ."""
    log = run.result.get("program", {}).get("selection_log") or []
    said = {tuple(int(rec["ctx"][k]) for k in ("B", "T", "H", "K", "V",
                                               "chunk", "itemsize"))
            for rec in log if rec["site"] == SCOPE
            and rec.get("mode") != "reference"}
    return said.pop() if len(said) == 1 else None


def read(run):
    if run.trace is None or any(d.name == NO_DEVICE_PLANE
                                for d in run.trace.devices):
        return None
    shapes = shapes_of(run)
    joined = scopes.of_run(run) if shapes else None
    if joined is None:
        return None
    seconds, sublayers = traced(run.trace, joined)
    steps = int(run.result.get("attempted", 0))
    if not seconds or not sublayers or not steps:
        return None
    return 100.0 * steps * sublayers * least_seconds(shapes, run.peaks) \
        / seconds
