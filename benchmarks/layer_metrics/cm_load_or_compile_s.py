"""Seconds the compile manager spent in ``.compile()`` in set-up: the
backend compile, or the load of the executable from the persistent cache;
0.0 for a span that never ran. Source: the program's ``dl4j.cm.compile``
span, from its ``dl4jtpu_span_seconds`` store."""

from benchmarks.harness.program_spans import span_seconds


def read(run):
    return span_seconds("dl4j.cm.compile")
