"""Decoder ticks per second of window: the delta of ``batches_total`` of
``InferenceService.stats()`` over the window. Source: the program's
counter."""


def read(run):
    svc = run.result.get("program", {}).get("service")
    if not svc:
        return None
    return svc["batches_total"] / run.result["elapsed_s"]
