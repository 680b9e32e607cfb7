"""Device milliseconds a ``jit_train_step`` call spends in the ``optimizer``
scope: global norm, clip, AdamW, apply, the ZeRO gather and scatter."""

from benchmarks import program_scopes


def read(spans, facts, trace, info):
    return program_scopes.ms_per_call(
        program_scopes.of_run(trace, info), program_scopes.TRAIN_MODULE,
        scopes=('optimizer',))
