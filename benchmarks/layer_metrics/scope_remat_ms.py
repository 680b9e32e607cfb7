"""Device milliseconds a ``jit_train_step`` call spends in recompute instructions
(direction ``remat`` of the program's own table, ``trace.compiled_programs()``),
every device scope but ``optimizer``. The table by scope is in the run's log."""

from benchmarks import program_scopes


def read(spans, facts, trace, info):
    return program_scopes.ms_per_call(
        program_scopes.of_run(trace, info), program_scopes.TRAIN_MODULE,
        directions=('remat',), but=('optimizer',))
