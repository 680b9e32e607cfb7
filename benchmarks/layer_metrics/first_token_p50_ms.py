"""Median of first token minus submission over the first tokens that fall
in the window, in a closed-loop cell whose prompts are many chunks long and
that does not report ``ttft_mean_ms`` (its mean swings with the seed's
order of 16-chunk prompts in the FIFO). A client's round is its first
token's wait plus its answer's gaps, so the wait moves the tokens a second
the loop completes: the sample ``ttft_p50_ms`` reads, under the end-to-end
metric this cell has (the manifest holds a reader's cells to the metric it
moves). Tens of samples in a traced window: recorded, not judged by."""

from benchmarks import stats


def read(spans, facts, trace, info):
    v = stats.percentile(facts.get("ttft_s") or [], 50)
    return None if v is None else 1e3 * v
