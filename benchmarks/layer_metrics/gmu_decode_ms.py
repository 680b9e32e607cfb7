"""Device milliseconds a ``jit_decode`` call spends in the ``gmu`` scope:
the gated memory units — the gate's product, its SiLU times the last scan
layer's output, the out product. A program without that scope gives nothing
to read."""

from benchmarks import program_scopes


def read(spans, facts, trace, info):
    return program_scopes.ms_per_call(
        program_scopes.of_run(trace, info), "jit_decode",
        scopes=("gmu",)) or None
