"""Device milliseconds a ``jit_decode`` call spends in the ``ssm.norm``
scope: the RMS norms on the scan's step, ``B`` and ``C``, between the
product that makes them and the ones that use them. A program without the
scope (a scan without inner norms) gives nothing to read."""

from benchmarks import program_scopes


def read(spans, facts, trace, info):
    return program_scopes.ms_per_call(
        program_scopes.of_run(trace, info), "jit_decode",
        scopes=("ssm.norm",)) or None
