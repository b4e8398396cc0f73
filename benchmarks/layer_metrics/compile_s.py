"""Seconds the backend compiler ran during set-up (0 when every program came
from the persistent cache). Source: ``jax.monitoring`` listeners."""


def read(run):
    return run.setup_compile["compile_seconds"]
