"""Share of device-busy time in the latent attention sublayers: operations
whose scope path (``harness/scopes.py``: joined in from the compiled
program's text) has a vertex ``b<i>A_norm`` or ``b<i>A_mixer`` as
``models/xing4.py`` names them, forward and backward (the low-rank
projections, the latent norms, the rotary embedding, the transposes around
the kernels, the output projection), and the flash kernels themselves by
their names (``flash_*``). 0.0 where no operation is either; nothing from a
program that does not offer its text. Source: device trace."""

import re

from benchmarks.harness import scopes
from benchmarks.harness import trace as tr

ATTENTION_BLOCK = re.compile(r"^b\d+A_")
KERNELS = ("flash_",)


def share(trace, joined, block, kernels=()) -> float:
    """Busy time in operations under a scope that ``block`` matches, or in
    a kernel whose name starts with one of ``kernels``, over all busy time;
    mean over devices."""
    spent = 0.0
    for dev in trace.devices:
        iv = []
        for op in dev.ops:
            kernel, path, _ = joined.of(op)
            if (kernels and (kernel or "").startswith(kernels)) or any(
                    block.match(s) for s in path):
                iv.append((op.start, op.end))
        spent += tr.total(tr.union(tr.clip(iv, *trace.window)))
    busy = trace.busy_s() * 1e9 * len(trace.devices)
    return spent / busy if busy else 0.0


def read(run):
    joined = scopes.of_run(run)
    if joined is None:
        return None
    return 100.0 * share(run.trace, joined, ATTENTION_BLOCK, KERNELS)
