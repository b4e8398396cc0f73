"""Rows coalesced per decoder tick: ``rows_total`` over ``batches_total`` of
``InferenceService.stats()``, as deltas over the window. Source: the
program's counters."""


def read(run):
    svc = run.result.get("program", {}).get("service")
    if not svc or not svc["batches_total"]:
        return None
    return svc["rows_total"] / svc["batches_total"]
