"""Seconds the compile manager spent admitting programs in set-up: the
argument-sharding check, the memory record and ``admission_check`` (which
traces the program again); 0.0 for a span that never ran. Source: the
program's ``dl4j.cm.admission`` span, from its ``dl4jtpu_span_seconds``
store."""

from benchmarks.harness.program_spans import span_seconds


def read(run):
    return span_seconds("dl4j.cm.admission")
