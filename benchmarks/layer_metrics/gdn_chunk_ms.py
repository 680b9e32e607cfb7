"""Device milliseconds a ``jit_prefill`` call spends in the ``gdn.*``
scopes: the linear-attention layers' products and gates, the convolution,
and the chunked rule whole — the triangular systems as well as the kernel."""

from benchmarks import program_scopes


def read(spans, facts, trace, info):
    return program_scopes.ms_per_call(
        program_scopes.of_run(trace, info), "jit_prefill", scopes=('gdn',))
