"""Device milliseconds a ``jit_train_step`` call spends in the ``moe.route``
scope, all directions: router, top-k, ``plan_rows``, the gathers into expert
order, the combine's scatter-add, the load-bias step."""

from benchmarks import program_scopes


def read(spans, facts, trace, info):
    return program_scopes.ms_per_call(
        program_scopes.of_run(trace, info), program_scopes.TRAIN_MODULE,
        scopes=('moe.route',))
