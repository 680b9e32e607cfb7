"""Device milliseconds a traced scheduler tick (``serve.tick``) spends in the
``attn.cache`` scope of both serving programs: the pool's row scatter, a
ring's write and their index arithmetic."""

from benchmarks import program_scopes


def read(spans, facts, trace, info):
    return program_scopes.ms_per_tick(trace, info, ("attn.cache",))
