"""50th percentile of first token minus submission, over the first
tokens that fall in the window (tens of samples: recorded, too few to
judge a PR by)."""

from benchmarks import stats


def read(spans, facts, trace, info):
    v = stats.percentile(facts.get("ttft_s") or [], 50)
    return None if v is None else 1e3 * v
