"""Backend compile requests inside the measured window; must read 0.
Source: ``jax.monitoring`` listeners."""


def read(run):
    return run.window_compile["backend_compiles"]
