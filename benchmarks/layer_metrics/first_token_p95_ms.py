"""95th percentile of first token minus submission over the first tokens
that fall in the window: ``first_token_p50_ms`` says why this cell has a
reader of its own for the sample ``ttft_p95_ms`` reads."""

from benchmarks import stats


def read(spans, facts, trace, info):
    v = stats.percentile(facts.get("ttft_s") or [], 95)
    return None if v is None else 1e3 * v
