"""Share of device-busy time in the expert blocks: operations whose scope
path (``harness/scopes.py``: joined in from the compiled program's text) has
a vertex of an ``E`` block, ``b<i>E_norm``, ``b<i>E_mixer`` or ``b<i>E_add``
as ``models/nemotron_h.py`` names them, forward and backward, and the
grouped products themselves by their names: the Mosaic kernels
``grouped_matmul_*``, and ``ragged-dot-*``, as XLA names the custom call it
runs ``jax.lax.ragged_dot`` as, whose instruction carries no scope of the
program (12.9% of busy time on the v5e, PR 30). Only expert blocks have either.
0.0 where no operation is either; nothing from a program that does not offer
its text. Source: device trace."""

import re

from benchmarks.harness import scopes
from benchmarks.harness import trace as tr

EXPERT_BLOCK = re.compile(r"^b\d+E_")
GROUPED_PRODUCTS = ("ragged-dot", "grouped_matmul_")


def share(trace, joined) -> float:
    """Busy time under an expert block's scope over all busy time."""
    spent = 0.0
    for dev in trace.devices:
        iv = []
        for op in dev.ops:
            kernel, path, _ = joined.of(op)
            if (kernel or "").startswith(GROUPED_PRODUCTS) or any(
                    EXPERT_BLOCK.match(s) for s in path):
                iv.append((op.start, op.end))
        spent += tr.total(tr.union(tr.clip(iv, *trace.window)))
    busy = trace.busy_s() * 1e9 * len(trace.devices)
    return spent / busy if busy else 0.0


def read(run):
    joined = scopes.of_run(run)
    if joined is None:
        return None
    return 100.0 * share(run.trace, joined)
