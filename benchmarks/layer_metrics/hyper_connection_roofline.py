"""The kernels of the hyper-connected residual against their roofline: the
sum over the traced ``hc_*`` events (``hc_maps_fwd`` / ``_bwd``, ``hc_read_fwd``
/ ``_bwd``, ``hc_write_fwd`` / ``_bwd``) of the least time each could take,
over the sum of their traced durations. Nothing where no such event is in the
window (the CPU and a mesh take the jax.numpy path: a share of a roofline is
never 0) or where the run's ``hyper_connection`` selections name no one shape.
Source: device trace; ``N``, ``n``, ``D`` and the item size are the run's own
``hyper_connection`` selections'.

One event is one piece of one sublayer over all ``N`` tokens. With ``X`` the
streams [N, n * D] (a unit: ``N n D`` items), ``y`` a sublayer's input or
output [N, D] (a part: a unit over ``n``), the float32 maps [N, n (2 + n)]
and the float32 projection ``P`` [n D, n (2 + n)], each array read or written
once, as a training graph calls the kernels (the maps and the read hand ``X``
on, so their backward kernels read the cotangent seen so far and write the
sum):

- ``hc_maps_fwd``: reads ``X`` and ``P``; writes ``X P`` and the mean square.
- ``hc_maps_bwd``: reads ``X``, the cotangent seen, ``P`` and the [N, n (2 +
  n) + 1] cotangent; writes ``dX`` and ``dP``. Two products.
- ``hc_read_fwd``: reads ``X``, the maps, ``gamma``; writes a part.
- ``hc_read_bwd``: reads ``X``, the cotangent seen, a part, the maps; writes
  ``dX``, the maps' cotangent and ``dgamma``.
- ``hc_write_fwd``: reads ``X``, the maps, a part; writes ``X'``.
- ``hc_write_bwd``: reads ``dX'``, ``X``, a part, the maps; writes ``dX``, a
  part and the maps' cotangent.

Operations are those of the mathematics (one multiply-add a term of a sum:
the projection's ``highest`` passes count once). The least time of an event
is the larger of its FLOPs over the chip's bf16 peak and its bytes over the
HBM peak (``run.peaks``): every one is bound by its bytes at the published
widths, so no real duration reads over 100.
"""

from benchmarks.harness.scopes import kernel_name

PREFIX = "hc_"
# (units of X, parts, copies of the maps, copies of P) moved; multiply-adds
# a feature of a unit
MOVED = {
    "hc_maps_fwd": (1, 0, 1, 1), "hc_maps_bwd": (3, 0, 1, 2),
    "hc_read_fwd": (1, 1, 1, 0), "hc_read_bwd": (3, 1, 2, 0),
    "hc_write_fwd": (2, 1, 1, 0), "hc_write_bwd": (3, 2, 2, 0),
}


def kernel_ops(dev):
    """``(op, kernel name)`` of every hyper-connection kernel call on one
    device."""
    for op in dev.ops:
        if op.bucket == "pallas":
            name = kernel_name(op.name) or ""
            if name.startswith(PREFIX):
                yield op, name


def flops_and_bytes(kernel: str, N, n, D, itemsize):
    """Operations and bytes one call of ``kernel`` needs, from its shapes."""
    m = n * (2 + n)
    units, parts, maps, proj = MOVED[kernel]
    moved = (units * N * n * D * itemsize + parts * N * D * itemsize
             + maps * N * (m + 1) * 4 + proj * n * D * m * 4)
    per_feature = {"hc_maps_fwd": m + 1, "hc_maps_bwd": 2 * m + 1,
                   "hc_read_fwd": 1 + 3 / n, "hc_read_bwd": 2 + 6 / n,
                   "hc_write_fwd": n + 1, "hc_write_bwd": 2 * n + 2}[kernel]
    return 2.0 * per_feature * N * n * D, float(moved)


def least_seconds(kernel, shapes, peaks) -> float:
    flops, moved = flops_and_bytes(kernel, *shapes)
    return max(flops / peaks["bf16_flops_per_s"],
               moved / peaks["hbm_bytes_per_s"])


def share(trace, shapes, peaks):
    """Least over traced seconds of the window's ``hc_*`` events; ``None``
    where the window has none."""
    lo, hi = trace.window
    least = traced = 0.0
    for dev in trace.devices:
        for op, kernel in kernel_ops(dev):
            if lo <= op.start < hi and kernel in MOVED:
                least += least_seconds(kernel, shapes, peaks)
                traced += (op.end - op.start) / 1e9
    return least / traced if traced else None


def streams_of(run):
    """``(N, n, D, itemsize)`` of the run's ``fused`` ``hyper_connection``
    selections; ``None`` where the run made none, or they differ (one set of
    shapes would not describe the events)."""
    log = run.result.get("program", {}).get("selection_log") or []
    said = {tuple(int(rec["ctx"][k]) for k in ("N", "n", "D", "itemsize"))
            for rec in log
            if rec["site"] == "hyper_connection" and rec["variant"] == "fused"
            and rec.get("mode") != "reference"}
    return said.pop() if len(said) == 1 else None


def read(run):
    if run.trace is None:
        return None
    shapes = streams_of(run)
    if shapes is None:
        return None
    got = share(run.trace, shapes, run.peaks)
    return None if got is None else 100.0 * got
