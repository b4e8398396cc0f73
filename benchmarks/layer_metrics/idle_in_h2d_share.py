"""Share of the traced window in which device 0 ran nothing while the
training thread handed it host arrays: inside ``dl4j.fit.put`` (the staged
window's ``device_put`` enqueue) and ``dl4j.fit.step`` (the per-batch jitted
step's call with its implicit transfer). 0.0 is a reading; nothing from a
program without the spans. Source: the program's spans on the device
trace."""

from benchmarks.harness.fit_iterator_spans import idle_share, of_run


def read(run):
    return of_run(run, idle_share, ["dl4j.fit.put", "dl4j.fit.step"])
