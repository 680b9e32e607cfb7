"""Device milliseconds a ``jit_prefill`` call spends in the ``ssm.*``
scopes: the selective-scan layers' products over a chunk, the convolution
from the slot's tail on, the scan of the chunk from the slot's state. A
program without those scopes gives nothing to read."""

from benchmarks import program_scopes


def read(spans, facts, trace, info):
    return program_scopes.ms_per_call(
        program_scopes.of_run(trace, info), "jit_prefill",
        scopes=("ssm",)) or None
