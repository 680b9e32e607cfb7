"""Device milliseconds a ``jit_decode`` call spends in the ``ssm.*`` scopes:
the selective-scan layers' products, the convolution with its tail, the
one-token scan over the slots' states. A program without those scopes gives
nothing to read."""

from benchmarks import program_scopes


def read(spans, facts, trace, info):
    return program_scopes.ms_per_call(
        program_scopes.of_run(trace, info), "jit_decode",
        scopes=("ssm",)) or None
