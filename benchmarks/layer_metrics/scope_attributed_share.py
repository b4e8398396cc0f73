"""Share of device-busy time in operations that carry a layer, vertex,
``loss`` or ``optimizer_update`` scope, or a Mosaic kernel's name
(``harness/scopes.py``: the scope is joined in from the compiled program's
text). 0.0 where no operation of the backend's trace carries either; nothing
from a program that does not offer its text. Leaves ``dl4j_scopes.json``
beside the trace for ``python3 -m benchmarks.harness.scopes <cell>``.
Source: device trace."""

from benchmarks.harness import scopes


def read(run):
    joined = scopes.of_run(run)
    if joined is None:
        return None
    return 100.0 * scopes.attributed_share(run.trace, joined)
