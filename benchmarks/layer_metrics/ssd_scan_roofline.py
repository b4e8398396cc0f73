"""The chunked state-space scan's kernels against their roofline: the sum
over the traced ``ssd_scan_*`` events of the least time each could take,
over the sum of their traced durations. Nothing where no such event is in
the window (a share of a roofline is never 0). Source: device trace.

One event is one Mamba block's whole batch: ``B`` sequences of ``T``
positions in chunks of ``L``, ``H`` heads of ``P`` channels reading ``G``
groups of ``B``/``C`` projections ``N`` wide. Operations are those of the
chunked algorithm, whatever the kernel does to reach them:

- forward, a chunk: a group's ``C B^T`` scores (``2 L L N``), then a head's
  ``M x`` (``2 L L P``), ``C S`` and the state's update (``2 L N P`` each).
  It reads ``x``, ``B``, ``C`` and ``dt`` with its running sum in two
  layouts (float32), and writes ``y`` and the state at each chunk's start
  (float32 [H, P, N] a chunk).
- backward, a chunk: the scores again and their gradient into ``dB`` and
  ``dC`` (``6 L L N`` a group); a head's ``dy x^T`` and ``M^T dy`` (``4 L L
  P``) and five products with a state or its gradient (``10 L N P``). It
  reads what the forward read, ``dy`` and the saved states, and writes ``dx``,
  ``dB``, ``dC`` and four float32 gradient arrays of ``dt``'s shape.

The least time of an event is the larger of its FLOPs over the chip's bf16
peak and its bytes over the HBM peak (``run.peaks``: the device's row of
``harness/peaks.json``).
"""

from benchmarks.layer_metrics.ssd_scan_time_share import kernel_ops

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def flops_and_bytes(kernel: str, B, T, H, P, G, N, L, itemsize):
    """Operations and bytes one call of ``kernel`` needs, from its shapes."""
    chunks = B * (T / L)
    wide = B * T * (H * P + 2 * G * N) * itemsize     # x (or y), B, C
    scalars = 4 * B * T * H * 4                        # dt, cum: two layouts
    states = chunks * H * P * N * 4
    if kernel.endswith("_bwd"):
        flops = chunks * (G * 6.0 * L * L * N
                          + H * (4.0 * L * L * P + 10.0 * L * N * P))
        moved = (2 * wide + B * T * H * P * itemsize   # x B C, dx dB dC; dy
                 + 2 * scalars + states)
    else:
        flops = chunks * (G * 2.0 * L * L * N
                          + H * (2.0 * L * L * P + 4.0 * L * N * P))
        moved = wide + B * T * H * P * itemsize + scalars + states
    return float(flops), float(moved)


def least_seconds(kernel, shapes, peaks) -> float:
    flops, moved = flops_and_bytes(kernel, *shapes)
    return max(flops / peaks["bf16_flops_per_s"],
               moved / peaks["hbm_bytes_per_s"])


def share(trace, shapes, peaks):
    """Least over traced seconds of the window's ``ssd_scan_*`` events;
    ``None`` where the window has none."""
    lo, hi = trace.window
    least = traced = 0.0
    for dev in trace.devices:
        for op, kernel in kernel_ops(dev):
            if lo <= op.start < hi:
                least += least_seconds(kernel, shapes, peaks)
                traced += (op.end - op.start) / 1e9
    return least / traced if traced else None


def read(run):
    if run.trace is None:
        return None
    p, s = run.cell.params, run.cell.sizes
    shapes = (int(p["batch_per_chip"]), int(p["seq_len"]),
              int(s["mamba_num_heads"]), int(s["mamba_head_dim"]),
              int(s["n_groups"]), int(s["ssm_state_size"]),
              int(s["chunk_size"]), ITEMSIZE[s["dtype"]])
    got = share(run.trace, shapes, run.peaks)
    return None if got is None else 100.0 * got
