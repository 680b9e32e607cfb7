"""Device milliseconds a ``jit_prefill`` call spends in the ``gmu`` and
``attn.cross`` scopes: the mixers of the layers ABOVE the last layer that
writes a cache — seven memory units and seven walks of the shared pool — which
a chunk sends its last valid row through and no other
(``serving/samba_y.py``). One row's products read their weights and little
else (~0.5 ms together); the same layers over a chunk's 512 rows would read
an order more. A program without those scopes gives nothing to read."""

from benchmarks import program_scopes


def read(spans, facts, trace, info):
    return program_scopes.ms_per_call(
        program_scopes.of_run(trace, info), "jit_prefill",
        scopes=("gmu", "attn.cross")) or None
