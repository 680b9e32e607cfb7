"""Host milliseconds of a training step in the program's working spans
(``data_fetch``, ``shard_batch``, ``train_step`` dispatch, ``fit.log``),
summed per step, median over the traced steps. The wait for the device
(``fit.fetch_metrics``) is left out."""

from benchmarks import program_spans


def read(spans, facts, trace, info):
    return program_spans.fit_host_ms(program_spans.of_run(trace, info))
