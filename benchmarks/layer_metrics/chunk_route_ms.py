"""Device milliseconds a ``jit_prefill`` call (one prompt chunk) spends in the
``moe.route`` scope: router, top-k, ``plan_rows``, gathers, scatter-adds."""

from benchmarks import program_scopes


def read(spans, facts, trace, info):
    return program_scopes.ms_per_call(
        program_scopes.of_run(trace, info), "jit_prefill", scopes=('moe.route',))
