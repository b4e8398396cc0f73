"""The flash attention kernels against their roofline: the sum over the
traced ``flash_*`` events (``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``)
of the least time each could take, over the sum of their traced durations.
Nothing where no such event is in the window (the CPU, a mesh and a short
sequence take the XLA path: a share of a roofline is never 0). Source: device
trace; whether the call is causal is the run's own ``attention`` selection's.

One event is one attention layer's whole batch: ``BH`` query heads of ``T``
positions and head size ``D`` over ``BHkv`` shared key/value heads. Operations
are those of the mathematics, whatever the kernel does to reach them: a
product over the score matrix is ``2 * BH * D * n`` with ``n = T (T + 1) / 2``
entries under ``causal`` and ``T^2`` otherwise;

- ``flash_fwd``: two products (``q k^T``, ``p v``). It reads ``q``, ``k``,
  ``v`` and writes ``o`` and the float32 ``lse``.
- ``flash_bwd_dq``: three (``q k^T``, ``do v^T``, ``ds k``). It reads ``q``,
  ``k``, ``v``, ``do`` and the float32 ``lse`` and ``delta``, and writes
  ``dq``.
- ``flash_bwd_dkv``: four (``q k^T``, ``do v^T``, ``p^T do``, ``ds^T q``). It
  reads what ``flash_bwd_dq`` reads and writes ``dk`` and ``dv``.

A shared key/value head counts once a group, read or written. The least time
of an event is the larger of its FLOPs over the chip's bf16 peak and its
bytes over the HBM peak (``run.peaks``: the device's row of
``harness/peaks.json``), so no real duration reads over 100.
"""

from benchmarks.harness.scopes import kernel_name

PREFIX = "flash_"
ITEMSIZE = {"bfloat16": 2, "float32": 4}
PRODUCTS = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}


def kernel_ops(dev):
    """``(op, kernel name)`` of every flash kernel call on one device."""
    for op in dev.ops:
        if op.bucket == "pallas":
            name = kernel_name(op.name) or ""
            if name.startswith(PREFIX):
                yield op, name


def flops_and_bytes(kernel: str, B, T, H, Hkv, D, itemsize, causal):
    """Operations and bytes one call of ``kernel`` needs, from its shapes."""
    entries = T * (T + 1) / 2 if causal else T * T
    flops = PRODUCTS[kernel] * 2.0 * B * H * D * entries
    query, shared = B * H * T * D * itemsize, B * Hkv * T * D * itemsize
    rows = B * H * T * 4                       # lse, delta: float32
    if kernel == "flash_fwd":
        moved = 2 * query + 2 * shared + rows          # q, o; k, v; lse
    elif kernel == "flash_bwd_dq":
        moved = 3 * query + 2 * shared + 2 * rows      # q, do, dq; k, v
    else:
        moved = 2 * query + 4 * shared + 2 * rows      # q, do; k, v, dk, dv
    return float(flops), float(moved)


def least_seconds(kernel, shapes, peaks) -> float:
    flops, moved = flops_and_bytes(kernel, *shapes)
    return max(flops / peaks["bf16_flops_per_s"],
               moved / peaks["hbm_bytes_per_s"])


def share(trace, shapes, peaks):
    """Least over traced seconds of the window's ``flash_*`` events; ``None``
    where the window has none."""
    lo, hi = trace.window
    least = traced = 0.0
    for dev in trace.devices:
        for op, kernel in kernel_ops(dev):
            if lo <= op.start < hi and kernel in PRODUCTS:
                least += least_seconds(kernel, shapes, peaks)
                traced += (op.end - op.start) / 1e9
    return least / traced if traced else None


def causal_of(run):
    """``causal`` of the run's ``flash`` selections; ``None`` where the run
    made none, or they differ (one set of shapes would not describe them)."""
    log = run.result.get("program", {}).get("selection_log") or []
    said = {bool(rec.get("ctx", {}).get("causal")) for rec in log
            if rec["site"] == "attention" and rec["variant"] == "flash"
            and rec.get("mode") != "reference"}
    return said.pop() if len(said) == 1 else None


def read(run):
    if run.trace is None:
        return None
    causal = causal_of(run)
    if causal is None:
        return None
    p, s = run.cell.params, run.cell.sizes
    shapes = (int(p["batch_per_chip"]), int(p["seq_len"]),
              int(s["num_attention_heads"]), int(s["num_key_value_heads"]),
              int(s["head_dim"]), ITEMSIZE[s["dtype"]], causal)
    got = share(run.trace, shapes, run.peaks)
    return None if got is None else 100.0 * got
