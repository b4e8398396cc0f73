"""Share of the traced window in which device 0 ran nothing while the
training thread waited for its iterator's next item (inside
``dl4j.fit.next_batch``): the data was not there. 0.0 is a reading; nothing
from a program without the spans. Source: the program's span on the device
trace."""

from benchmarks.harness.fit_iterator_spans import idle_share, of_run


def read(run):
    return of_run(run, idle_share, ["dl4j.fit.next_batch"])
