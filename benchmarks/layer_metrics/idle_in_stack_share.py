"""Share of the traced window in which device 0 ran nothing while the
training thread stacked the next staged window from its batches (inside
``dl4j.fit.stack``: ``BucketedStager``'s pad and ``np.stack``). The staged
path's double buffer covers the transfer and not this. 0.0 is a reading;
nothing from a program without the spans. Source: the program's span on the
device trace."""

from benchmarks.harness.fit_iterator_spans import idle_share, of_run


def read(run):
    return of_run(run, idle_share, ["dl4j.fit.stack"])
