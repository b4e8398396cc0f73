"""Seconds the compile manager spent in ``jitted.lower(*args)`` (tracing and
lowering, before the persistent cache can be asked) in set-up; 0.0 for a
span that never ran. Read after the window, inside which nothing compiles
(``compiles_in_window``). Source: the program's ``dl4j.cm.lower`` span, from
its ``dl4jtpu_span_seconds`` store."""

from benchmarks.harness.program_spans import span_seconds


def read(run):
    return span_seconds("dl4j.cm.lower")
