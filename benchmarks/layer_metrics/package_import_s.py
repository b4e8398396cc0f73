"""Seconds ``import deeplearning4j_tpu`` took in this process (jax is
imported before it by the harness and is not in it). Source: the program's
counter ``deeplearning4j_tpu.import_seconds``; a program without it reports
nothing."""


def read(run):
    import deeplearning4j_tpu

    return getattr(deeplearning4j_tpu, "import_seconds", None)
