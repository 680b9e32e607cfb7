"""Device milliseconds a ``jit_decode`` call spends in the ``moe.route`` scope:
router, top-k, ``plan_rows``' sort and search, gathers and scatter-adds."""

from benchmarks import program_scopes


def read(spans, facts, trace, info):
    return program_scopes.ms_per_call(
        program_scopes.of_run(trace, info), "jit_decode", scopes=('moe.route',))
