"""Device milliseconds a ``jit_train_step`` call spends in the ``head``, ``loss``
and ``mtp`` scopes, all directions: final norm, logits product, the loss,
the prediction module's join."""

from benchmarks import program_scopes


def read(spans, facts, trace, info):
    return program_scopes.ms_per_call(
        program_scopes.of_run(trace, info), program_scopes.TRAIN_MODULE,
        scopes=('head', 'loss', 'mtp'))
