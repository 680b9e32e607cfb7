"""Leaf device time of the train program under no device scope over
its leaf time, in %: how far to trust the metrics that read the scopes."""

from benchmarks import program_scopes


def read(spans, facts, trace, info):
    return program_scopes.unscoped_pct(
        program_scopes.of_run(trace, info), (program_scopes.TRAIN_MODULE,))
