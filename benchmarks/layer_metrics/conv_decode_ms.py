"""Device milliseconds a ``jit_decode`` call spends in the ``conv.*``
scopes: the short-convolution layers' two products, their gates, the taps
over every slot's tail and the tail's shift. A program without those scopes
gives nothing to read."""

from benchmarks import program_scopes


def read(spans, facts, trace, info):
    return program_scopes.ms_per_call(
        program_scopes.of_run(trace, info), "jit_decode",
        scopes=("conv",)) or None
