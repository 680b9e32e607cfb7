"""Host milliseconds of a scheduler tick: the program's ``serve.tick`` span
less the waiting spans inside it (``serve.prefill.wait``,
``serve.decode.wait``), median over the traced ticks. What the host spends
admitting, building a chunk, dispatching, scheduling and book-keeping."""

from benchmarks import program_spans


def read(spans, facts, trace, info):
    return program_spans.host_ms_per_unit(
        program_spans.of_run(trace, info), "serve.tick")
