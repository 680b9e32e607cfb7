"""The part of a first token's wait spent on the request's own chunks (its
first chunk dispatched to its first token): mean of the program's
``serving_prefill_run`` histogram over the window's first tokens, in ms.
With ``ttft_queue_ms`` and ``ttft_prefill_wait_ms`` it sums to
``ttft_mean_ms``."""

from benchmarks import program_spans


def read(spans, facts, trace, info):
    waits = program_spans.first_token_waits(facts, info)
    return None if waits is None else waits["prefill_run"]
