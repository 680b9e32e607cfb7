"""Device idle milliseconds per traced training step: every idle gap of the
trace, put down to the ``fit`` span of the program that covers its middle
(or ``outside``), over the ``train_step`` spans. The table by phase is in
the run's log."""

from benchmarks import program_spans


def read(spans, facts, trace, info):
    return program_spans.idle_ms_per_unit(
        program_spans.of_run(trace, info), "train_step")
