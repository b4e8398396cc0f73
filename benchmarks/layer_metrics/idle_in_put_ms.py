"""Device-idle milliseconds inside ``dl4j.parallel_wrapper.data``
(``ParallelWrapper.fit_on_device``'s ``np.asarray`` and ``global_put`` of the
staged batches and masks), per dispatch in the traced window; 0.0 where no
idle falls in the span. Source: the program's span on the device trace."""

from benchmarks.harness.program_spans import idle_ms_per_dispatch


def read(run):
    return idle_ms_per_dispatch(run, ["dl4j.parallel_wrapper.data"])
