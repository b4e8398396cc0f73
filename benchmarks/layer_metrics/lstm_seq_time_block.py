"""Time steps a grid step of the seq-fused LSTM kernels, in the programs this
run trained with: the ``time_block`` of the run's ``lstm_seq`` selections, the
smallest where shapes differ (records of the reference-mode twin are left
out). 1 means the blocking did not engage; 0.0 where the site took another
path than the seq kernels (the CPU and a mesh take the XLA one). Nothing where
the run has no LSTM site, or its program chose the seq kernels and says no
block (a program from before the blocking). Source:
``kernel_select.selection_log()``."""

SEQ_VARIANT = "seqfused"


def read(run):
    log = run.result.get("program", {}).get("selection_log")
    if log is None:
        return None
    blocks = [rec.get("time_block") if rec["variant"] == SEQ_VARIANT else 0.0
              for rec in log
              if rec["site"] == "lstm_seq" and rec.get("mode") != "reference"]
    if not blocks or None in blocks:
        return None
    return float(min(blocks))
