"""Device milliseconds a ``jit_decode`` call spends in the ``ssm.*`` scopes
of a configuration that publishes the scan's sizes (``mamba_expand``): the
scan layers' products, the convolution with its tail, the inner norms, the
scan over the slots' states — 26 layers of 28. A program without those
scopes, or another configuration, gives nothing to read."""

from benchmarks import program_scopes


def read(spans, facts, trace, info):
    if "mamba_expand" not in info["ctx"].config:
        return None
    return program_scopes.ms_per_call(
        program_scopes.of_run(trace, info), "jit_decode",
        scopes=("ssm",)) or None
