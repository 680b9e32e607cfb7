"""Share of its roofline (memory bound) that the paged decode kernel
reaches: the live context's K and V, read once a layer a tick."""

from benchmarks import readers


def read(spans, facts, trace, info):
    if not readers.on_device(trace) or not facts.get("context_tokens"):
        return None
    k = readers.kernel(info, "paged_decode")
    found = readers.kernel_seconds(trace, k.TRACE_NAMES)
    if not found:
        return None
    cfg = info["ctx"].config
    ctx_tokens = sum(facts["context_tokens"]) / len(facts["context_tokens"])
    one = k.count(facts["slots"], int(ctx_tokens),
                  cfg["num_attention_heads"], cfg["head_dim"])
    return readers.roofline_share(
        [(calls, one) for calls, _ in found.values()],
        sum(s for _, s in found.values()), readers.peaks(info))
