"""The fullest held expert's rows over the mean of the held experts' rows:
the program's counters ``rows_fullest`` (summed over steps) over
``rows_held`` / experts held, over the expert layers and every dispatch of
the process. 1.0 is even; the grouped products' time follows the fullest.
Nothing from a program without the counters. Source: program counter."""

from benchmarks.layer_metrics.moe_rows_per_token import counter_sums


def read(run):
    sums = counter_sums()
    if not sums or not sums.get("rows_held"):
        return None
    held = int(run.cell.sizes["n_routed_experts"])
    return sums.get("rows_fullest", 0.0) * held / sums["rows_held"]
