"""Programs served from JAX's persistent compilation cache during set-up:
0 on a checkout's first run, every program of the cell afterwards. Source:
``jax.monitoring`` listeners."""


def read(run):
    return run.setup_compile["persistent_cache_hits"]
