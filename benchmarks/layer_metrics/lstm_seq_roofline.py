"""The seq-fused LSTM kernels against their roofline: the sum over the
traced ``lstm_seq_*`` events of the least time each could take, over the sum
of their traced durations. Nothing where no such event is in the window (the
CPU and a mesh take the XLA path: a share of a roofline is never 0), and
nothing where the window's Mosaic calls have no names
(``lstm_seq_time_share``). Source: device trace.

One event is one layer's whole sequence: T time steps of the recurrent
``h @ RW`` ([B,H] x [H,4H]) and the cell's elementwise math; ``x @ W`` runs
outside the kernel. From B, T, H of the cell's sizes:

- forward: ``2*B*H*4H`` FLOPs a time step. It reads ``zx`` [T,B,4H], ``RW``
  [H,4H], ``h0``, ``c0`` and three peephole rows, and writes ``ys`` and, in
  training, the five gate residuals the backward reads ([T,B,H] each), and
  ``hT``, ``cT``. The lean kernel (no residuals) writes ``ys`` alone.
- backward: twice the FLOPs (``dz @ RW^T`` and ``h^T @ dz``). It reads
  ``dys``, the five residuals and ``ys`` ([T,B,H] each), ``RW``, ``h0``,
  ``c0``, the peepholes and two [B,H] cotangents, and writes ``dzx``
  [T,B,4H], ``dRW``, ``dh0``, ``dc0`` and three peephole gradients.

The least time of an event is the larger of its FLOPs over the chip's bf16
peak and its bytes over the HBM peak (``harness/peaks.json``).
"""

from benchmarks.harness.gate import peaks_row

from benchmarks.layer_metrics.lstm_seq_time_share import kernel_ops, told_apart

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def flops_and_bytes(kernel: str, B: int, T: int, H: int, itemsize: int):
    """Operations and bytes one call of ``kernel`` needs, from its shapes."""
    seq, seq4, mat, row, small = T * B * H, T * B * 4 * H, H * 4 * H, B * H, 3 * H
    masked = T * B if "masked" in kernel else 0
    if kernel.endswith("_bwd"):
        flops = 2 * 2 * B * H * 4 * H * T
        moved = (7 * seq + seq4 + 2 * mat + 6 * row + 2 * small + masked)
    else:
        flops = 2 * B * H * 4 * H * T
        residuals = 0 if kernel.endswith("_lean") else 5 * seq
        moved = seq4 + mat + 4 * row + small + seq + residuals + masked
    return float(flops), float(moved * itemsize)


def least_seconds(kernel, B, T, H, itemsize, peaks) -> float:
    flops, moved = flops_and_bytes(kernel, B, T, H, itemsize)
    return max(flops / peaks["bf16_flops_per_s"],
               moved / peaks["hbm_bytes_per_s"])


def share(trace, B, T, H, itemsize, peaks):
    """Least over traced seconds of the window's ``lstm_seq_*`` events;
    ``None`` where the window has none."""
    lo, hi = trace.window
    least = traced = 0.0
    for dev in trace.devices:
        for op, kernel in kernel_ops(dev):
            if lo <= op.start < hi:
                least += least_seconds(kernel, B, T, H, itemsize, peaks)
                traced += (op.end - op.start) / 1e9
    return least / traced if traced else None


def read(run):
    if run.trace is None or not told_apart(run.trace):
        return None
    if not any(True for dev in run.trace.devices for _ in kernel_ops(dev)):
        return None  # and no peaks row is asked of a device without one
    import jax

    p, sizes = run.cell.params, run.cell.sizes
    got = share(
        run.trace, int(p["batch_per_chip"]), int(p["seq_len"]),
        int(sizes["rnn_size"]), ITEMSIZE[sizes["dtype"]],
        peaks_row(jax.devices()[0].device_kind))
    return None if got is None else 100.0 * got
