"""Device idle milliseconds per traced scheduler tick: every idle gap of
the trace — those that fall in a ``serve.*`` span of the program and those
``outside`` (between two ticks: the benchmark's own loop) — over the
``serve.tick`` spans. The table by phase is in the run's log."""

from benchmarks import program_spans


def read(spans, facts, trace, info):
    return program_spans.idle_ms_per_unit(
        program_spans.of_run(trace, info), "serve.tick")
