"""Share of device-busy time in the Kimi Delta Attention sublayers:
operations whose scope path (``harness/scopes.py``: joined in from the
compiled program's text) has a vertex ``b<i>K_norm``, ``b<i>K_mixer`` or
``b<i>K_add`` as ``models/kimi_linear.py`` names them, forward and backward
(the projections, the short convolutions, the gates, the recurrence, the
gated head norm, the output projection, the block's norm and residual add),
and kernels named ``kda_*`` by their names, scope or none (none exists yet:
the recurrence is jax.numpy under the scope ``kda_recurrence``). 0.0 where no
operation is either; nothing from a program that does not offer its text.
Source: device trace."""

import re

from benchmarks.harness import scopes
from benchmarks.layer_metrics.latent_attention_time_share import share

KDA_BLOCK = re.compile(r"^b\d+K_")
KERNELS = ("kda_",)


def read(run):
    joined = scopes.of_run(run)
    if joined is None:
        return None
    return 100.0 * share(run.trace, joined, KDA_BLOCK, KERNELS)
