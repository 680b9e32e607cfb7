"""The part of a first token's wait spent behind other prompts' chunks
(admitted to its own first chunk dispatched): mean of the program's
``serving_prefill_wait`` histogram over the window's first tokens, in ms.
With ``ttft_queue_ms`` and ``ttft_prefill_run_ms`` it sums to
``ttft_mean_ms``."""

from benchmarks import program_spans


def read(spans, facts, trace, info):
    waits = program_spans.first_token_waits(facts, info)
    return None if waits is None else waits["prefill_wait"]
