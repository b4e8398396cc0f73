"""Rows that landed on the experts held here, a token a block: the
program's counters ``rows_held`` over ``tokens`` of
``dl4jtpu_layer_counter_total{layer,counter}``, summed over the expert
layers and every dispatch of the process. Even routing gives ``top_k * held
/ router_width`` (6 x 8 / 128 = 0.375). Nothing from a program without the
counters. Source: program counter."""

FAMILY = "dl4jtpu_layer_counter_total"


def counter_sums() -> dict | None:
    """``{counter: sum over layers}`` of the program's layer counters."""
    try:
        from deeplearning4j_tpu.telemetry import get_registry

        family = get_registry().snapshot().get(FAMILY)
    except (ImportError, AttributeError):
        return None
    if not family:
        return None
    sums: dict = {}
    for row in family["values"]:
        name = row["labels"].get("counter")
        sums[name] = sums.get(name, 0.0) + row["value"]
    return sums


def read(run):
    sums = counter_sums()
    if not sums or not sums.get("tokens"):
        return None
    return sums.get("rows_held", 0.0) / sums["tokens"]
