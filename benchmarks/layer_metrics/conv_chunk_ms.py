"""Device milliseconds a ``jit_prefill`` call spends in the ``conv.*``
scopes: the short-convolution layers' two products over a chunk, their
gates, the taps from the slot's tail on and the write of the chunk's last
values. A program without those scopes gives nothing to read."""

from benchmarks import program_scopes


def read(spans, facts, trace, info):
    return program_scopes.ms_per_call(
        program_scopes.of_run(trace, info), "jit_prefill",
        scopes=("conv",)) or None
