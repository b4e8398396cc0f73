"""Share of the traced window the training thread spent waiting for its
iterator's next item: the seconds of the ``dl4j.fit.next_batch`` spans (for
``fit``'s ``AsyncDataSetIterator`` the wait on its queue) over the window.
0.0 where the spans are there and took no time; nothing from a program whose
``fit`` opens no ``dl4j.fit.epoch``. Source: the program's span on the device
trace's clock."""

from benchmarks.harness.fit_iterator_spans import span_share, of_run


def read(run):
    return of_run(run, span_share, ["dl4j.fit.next_batch"])
