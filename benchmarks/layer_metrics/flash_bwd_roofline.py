"""Share of its roofline that the flash backward reaches (the fused
kernel, or the split dq + dkv pair, whichever the step program holds)."""

from benchmarks import readers


def read(spans, facts, trace, info):
    if not readers.on_device(trace):
        return None
    k = readers.kernel(info, "flash_bwd")
    found = readers.kernel_seconds(trace, k.TRACE_NAMES)
    if not found:
        return None
    cfg, chips = info["ctx"].config, info["ctx"].chips
    floors = [(calls, k.count(facts["rows"] // chips, facts["seq_len"],
                              cfg["num_attention_heads"], cfg["head_dim"],
                              variant=name))
              for name, (calls, _) in found.items()]
    return readers.roofline_share(
        floors, sum(s for _, s in found.values()), readers.peaks(info))
