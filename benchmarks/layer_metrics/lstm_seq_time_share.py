"""Share of device-busy time in the seq-fused LSTM kernels: the Mosaic
custom calls whose ``pallas_call`` name starts with ``lstm_seq_``
(``lstm_seq_fwd``, ``lstm_seq_bwd``, ``lstm_seq_lean`` and their masked
twins). 0.0 where no such event is in the window: the CPU and a mesh take
the XLA path. Nothing where a Mosaic call of the window has no name to tell
it by (a program from before ``name=``: ``%jvp__.20``). Source: device
trace."""

from benchmarks.harness import trace as tr
from benchmarks.harness.scopes import kernel_name

PREFIX = "lstm_seq_"


def kernel_ops(dev):
    """``(op, kernel name)`` of every seq-fused LSTM call on one device."""
    for op in dev.ops:
        if op.bucket == "pallas":
            name = kernel_name(op.name)
            if name.startswith(PREFIX):
                yield op, name


def told_apart(trace) -> bool:
    """Whether every Mosaic call of the trace carries a kernel's name."""
    return all(kernel_name(op.name) for dev in trace.devices
               for op in dev.ops if op.bucket == "pallas")


def read(run):
    if run.trace is None or not told_apart(run.trace):
        return None
    busy = run.trace.busy_s()
    spent = sum(
        tr.total(tr.union(tr.clip([(op.start, op.end)
                                   for op, _ in kernel_ops(dev)],
                                  *run.trace.window)))
        for dev in run.trace.devices) / len(run.trace.devices) / 1e9
    return 100.0 * spent / busy if busy else 0.0
