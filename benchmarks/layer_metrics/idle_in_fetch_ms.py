"""Device-idle milliseconds inside ``dl4j.fit.fetch`` (``np.asarray`` of the
losses: the synchronisation), per dispatch in the traced window: what the
device waits after its last operation until the host has the result; 0.0
where no idle falls in the span. Source: the program's span on the device
trace."""

from benchmarks.harness.program_spans import idle_ms_per_dispatch


def read(run):
    return idle_ms_per_dispatch(run, ["dl4j.fit.fetch"])
