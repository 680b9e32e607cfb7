"""Device milliseconds a ``jit_decode`` call spends in the ``attn.*`` scopes:
projections, the cache write, the paged kernel or the gathered scores."""

from benchmarks import program_scopes


def read(spans, facts, trace, info):
    return program_scopes.ms_per_call(
        program_scopes.of_run(trace, info), "jit_decode", scopes=('attn',))
