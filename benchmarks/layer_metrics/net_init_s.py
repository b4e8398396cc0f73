"""Seconds of ``net.init()`` (parameter, state and optimizer-state creation)
in set-up, every net the run built (the twin too); 0.0 for a span that never
ran. Read after the window, inside which nothing initialises. Source: the
program's ``dl4j.net.init`` span, from its ``dl4jtpu_span_seconds`` store."""

from benchmarks.harness.program_spans import span_seconds


def read(run):
    return span_seconds("dl4j.net.init")
