"""Device milliseconds a ``jit_decode`` call spends in the ``gdn.*`` scopes:
the linear-attention layers' products and gates, the convolution with its
tail, the one-token rule over the slots' states."""

from benchmarks import program_scopes


def read(spans, facts, trace, info):
    return program_scopes.ms_per_call(
        program_scopes.of_run(trace, info), "jit_decode", scopes=('gdn',))
