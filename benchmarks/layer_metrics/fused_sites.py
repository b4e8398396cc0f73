"""Kernel-selection sites whose selected variant is a Mosaic one, in the
programs this run trained with (records of the reference-mode twin are left
out). Source: ``kernel_select.selection_log()``."""

XLA_VARIANTS = {"reference", "xla"}


def read(run):
    log = run.result.get("program", {}).get("selection_log")
    if log is None:
        return None
    chosen = {}
    for rec in log:
        if rec.get("mode") != "reference":
            chosen[rec["site"]] = rec["variant"]
    return sum(1 for v in chosen.values() if v not in XLA_VARIANTS)
