"""Device milliseconds a ``jit_train_step`` call spends in the ``embed`` scope,
all directions: the look-ups and the embedding gradient's scatter-add."""

from benchmarks import program_scopes


def read(spans, facts, trace, info):
    return program_scopes.ms_per_call(
        program_scopes.of_run(trace, info), program_scopes.TRAIN_MODULE,
        scopes=('embed',))
