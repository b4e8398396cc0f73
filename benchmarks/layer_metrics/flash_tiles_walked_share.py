"""Entries of the score square the attention path computes, over the whole
square, in percent, in the programs this run trained with: 100 times the
``tiles_walked_share`` of the run's ``attention`` selections, the largest
where shapes differ (records of the reference-mode twin are left out). The
flash kernels under ``causal`` visit only the tiles at or under the diagonal:
``(n + 1) / 2n`` for ``n`` tiles a side (53.1 at 16). 100.0 where a ``flash``
selection says no share (a program from before the bound: every tile was
walked and the upper triangle masked) and where the selection is the XLA path
(the CPU, a mesh, a short sequence: it materialises the whole square and
masks it). Nothing where the run has no attention selection. Source:
``kernel_select.selection_log()``."""


def read(run):
    log = run.result.get("program", {}).get("selection_log")
    if log is None:
        return None
    shares = [rec.get("tiles_walked_share", 1.0) for rec in log
              if rec["site"] == "attention" and rec.get("mode") != "reference"]
    return 100.0 * max(shares) if shares else None
