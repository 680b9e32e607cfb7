"""Device milliseconds a ``jit_decode`` call spends in the ``mlp`` and ``moe.*``
scopes: dense MLP or shared expert, routing and index work, grouped products."""

from benchmarks import program_scopes


def read(spans, facts, trace, info):
    return program_scopes.ms_per_call(
        program_scopes.of_run(trace, info), "jit_decode", scopes=('mlp', 'moe'))
