"""Device-idle milliseconds inside ``dl4j.fit.prepare`` (inputs to
executable in hand: ``jnp.asarray``, ``_staged_args``, ``signature``,
``cm.aot``) and ``dl4j.fit.launch`` (the executable's call), per dispatch in
the traced window; 0.0 where no idle falls in them. Source: the program's
spans on the device trace."""

from benchmarks.harness.program_spans import idle_ms_per_dispatch


def read(run):
    return idle_ms_per_dispatch(run, ["dl4j.fit.prepare", "dl4j.fit.launch"])
