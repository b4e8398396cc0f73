"""The expert layers' grouped-product kernels against their roofline: the sum
over the traced ``grouped_matmul_*`` events (``_fwd``, ``_dlhs``, ``_drhs``)
of the least time each could take, over the sum of their traced durations.
Nothing where no such event is in the window (the CPU, a mesh and a program
before PR 30 take ``ragged_dot``), or where the program's counters are absent.
Sources: device trace for the time, program counter for the rows.

What an event must do depends on the routing: it multiplies the rows that
landed on the experts held, which the program counts (``rows_held`` over
``tokens`` of ``dl4jtpu_layer_counter_total``, summed over the expert layers
and the process's dispatches: the mean rows of a layer's step, the same for
every event). With ``rows`` of them, ``E`` experts of ``[D, F]`` up and ``[F,
D]`` down: every event is ``2 * rows * D * F`` operations; ``_fwd`` and
``_dlhs`` read the rows and all ``E`` matrices once and write as many rows,
``_drhs`` reads two sets of rows and writes the ``E`` matrices. Padding a
group to whole row tiles is the kernel's own cost and counts for nothing. The
least time is the larger of the operations over the chip's bf16 peak and the
bytes over its HBM peak (``run.peaks``)."""

from benchmarks.harness.scopes import kernel_name
from benchmarks.layer_metrics.moe_rows_per_token import counter_sums

PREFIX = "grouped_matmul_"
ITEMSIZE = {"bfloat16": 2, "float32": 4}


def kernel_ops(dev):
    for op in dev.ops:
        if op.bucket == "pallas" and (kernel_name(op.name) or "") \
                .startswith(PREFIX):
            yield op


def flops_and_bytes(rows: float, D: int, F: int, E: int, itemsize: int):
    """Operations and bytes of one event over ``rows`` rows."""
    return 2.0 * rows * D * F, itemsize * (E * D * F + rows * (D + F))


def least_seconds(rows, D, F, E, itemsize, peaks) -> float:
    flops, moved = flops_and_bytes(rows, D, F, E, itemsize)
    return max(flops / peaks["bf16_flops_per_s"],
               moved / peaks["hbm_bytes_per_s"])


def share(trace, least: float):
    """``least`` seconds an event over the traced seconds of the window's
    ``grouped_matmul_*`` events; ``None`` where the window has none."""
    lo, hi = trace.window
    events, traced = 0, 0.0
    for dev in trace.devices:
        for op in kernel_ops(dev):
            if lo <= op.start < hi:
                events += 1
                traced += (op.end - op.start) / 1e9
    return events * least / traced if traced else None


def read(run):
    sums = counter_sums()
    if run.trace is None or not sums or not sums.get("tokens"):
        return None
    p, s = run.cell.params, run.cell.sizes
    rows = (sums.get("rows_held", 0.0) / sums["tokens"]
            * int(p["batch_per_chip"]) * int(p["seq_len"]))
    got = share(run.trace, least_seconds(
        rows, int(s["hidden_size"]), int(s["moe_intermediate_size"]),
        int(s["n_routed_experts"]), ITEMSIZE[s["dtype"]], run.peaks))
    return None if got is None else 100.0 * got
