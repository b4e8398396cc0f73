"""Share of device-busy time in the chunked state-space scan's kernels: the
Mosaic custom calls whose ``pallas_call`` name starts with ``ssd_scan_``
(``ssd_scan_fwd``, ``ssd_scan_bwd``). 0.0 where no such event is in the
window (the CPU and a mesh take the jax.numpy variant): a share of the busy
time, where 0 is a reading. Source: device trace."""

from benchmarks.harness import trace as tr
from benchmarks.harness.scopes import kernel_name

PREFIX = "ssd_scan_"


def kernel_ops(dev):
    """``(op, kernel name)`` of every scan kernel call on one device."""
    for op in dev.ops:
        if op.bucket == "pallas":
            name = kernel_name(op.name) or ""
            if name.startswith(PREFIX):
                yield op, name


def read(run):
    if run.trace is None:
        return None
    busy = run.trace.busy_s()
    spent = sum(
        tr.total(tr.union(tr.clip([(op.start, op.end)
                                   for op, _ in kernel_ops(dev)],
                                  *run.trace.window)))
        for dev in run.trace.devices) / len(run.trace.devices) / 1e9
    return 100.0 * spent / busy if busy else 0.0
