"""Share of its roofline that the flash forward kernel reaches."""

from benchmarks import readers


def read(spans, facts, trace, info):
    if not readers.on_device(trace):
        return None
    k = readers.kernel(info, "flash_fwd")
    found = readers.kernel_seconds(trace, k.TRACE_NAMES)
    if not found:
        return None
    cfg = info["ctx"].config
    one = k.count(facts["rows"] // info["ctx"].chips, facts["seq_len"],
                  cfg["num_attention_heads"], cfg["head_dim"])
    return readers.roofline_share(
        [(calls, one) for calls, _ in found.values()],
        sum(s for _, s in found.values()), readers.peaks(info))
