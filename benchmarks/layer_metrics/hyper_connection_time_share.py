"""Share of device-busy time in the hyper-connection pieces: operations
whose scope path (``harness/scopes.py``) has a vertex ``b<i>H_maps`` (the
maps' projection and the Sinkhorn normalisation), ``b<i>H_pre`` (the
streams read into a sublayer's input) or ``b<i>H_post`` (the streams mixed
and the sublayer's output written back) as ``models/xing4.py`` names them,
forward and backward. The pieces are bound by the bytes of the ``n``-wide
residual stream, not by their operations. 0.0 where no operation is under
such a scope; nothing from a program that does not offer its text. Source:
device trace."""

import re

from benchmarks.harness import scopes
from benchmarks.layer_metrics.latent_attention_time_share import share

HYPER_CONNECTION = re.compile(r"^b\d+H_")


def read(run):
    joined = scopes.of_run(run)
    if joined is None:
        return None
    return 100.0 * share(run.trace, joined, HYPER_CONNECTION)
