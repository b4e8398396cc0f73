"""Host bytes ``fit`` handed to the device over the window's seconds, in GB/s
(1e9): staged windows at their ``device_put``, per-batch steps at the jitted
call. Nothing from a program without the counter. Source: the program's
``dl4jtpu_fit_host_bytes_total{path}``, delta over the window (the
generator's stopwatch)."""

FAMILY = "dl4jtpu_fit_host_bytes_total{"


def read(run):
    counts = run.result.get("program", {}).get("fit_iterator", {})
    handed = [v for k, v in counts.items() if k.startswith(FAMILY)]
    return sum(handed) / run.result["elapsed_s"] / 1e9 if handed else None
