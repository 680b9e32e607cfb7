"""Device milliseconds a ``jit_decode`` call spends in the ``attn.cross``
scope: the walks of the ONE paged key-value layer that several layers read —
the full layer's own and every cross layer's, eight a step — and nothing
else (their query and out products, the two maps' difference and the sub-norm
are under ``attn.proj``; the window layers' rings under ``attn.core``). The
time ``shared_kv_walk_roofline`` holds against its floor, read from the
program's scopes where that one reads the kernel's name. A program without
that scope gives nothing to read."""

from benchmarks import program_scopes


def read(spans, facts, trace, info):
    return program_scopes.ms_per_call(
        program_scopes.of_run(trace, info), "jit_decode",
        scopes=("attn.cross",)) or None
