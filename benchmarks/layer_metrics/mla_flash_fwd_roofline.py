"""Share of its roofline that the latent-attention flash forward kernel
reaches, the floor counted at the published widths (128 + 64 scores, 128
values, causal half)."""

from benchmarks import readers


def read(spans, facts, trace, info):
    if not readers.on_device(trace):
        return None
    k = readers.kernel(info, "mla_flash_fwd")
    found = readers.kernel_seconds(trace, k.TRACE_NAMES)
    if not found:
        return None
    cfg = info["ctx"].config
    one = k.count(facts["rows"] // info["ctx"].chips, facts["seq_len"],
                  cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                  cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    return readers.roofline_share(
        [(calls, one) for calls, _ in found.values()],
        sum(s for _, s in found.values()), readers.peaks(info))
