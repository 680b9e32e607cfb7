"""Device milliseconds a ``jit_decode`` call spends in the ``attn.*`` scopes
where many query heads read one key-value head (a configuration with
``attn_layer_period``): the two attention layers' products, the pool's row
scatter, the paged kernel over a pool one lane tile wide. A program without
those scopes, or another configuration, gives nothing to read."""

from benchmarks import program_scopes


def read(spans, facts, trace, info):
    if "attn_layer_period" not in info["ctx"].config:
        return None
    return program_scopes.ms_per_call(
        program_scopes.of_run(trace, info), "jit_decode",
        scopes=("attn",)) or None
