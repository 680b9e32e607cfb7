"""Share of its roofline that the latent-attention flash backward reaches
(the split dq + dkv kernels together), the floor counted at the published
widths."""

from benchmarks import readers


def read(spans, facts, trace, info):
    if not readers.on_device(trace):
        return None
    k = readers.kernel(info, "mla_flash_bwd")
    found = readers.kernel_seconds(trace, k.TRACE_NAMES)
    if not found:
        return None
    cfg, chips = info["ctx"].config, info["ctx"].chips
    floors = [(calls, k.count(facts["rows"] // chips, facts["seq_len"],
                              cfg["num_attention_heads"],
                              cfg["qk_nope_head_dim"],
                              cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                              variant=name))
              for name, (calls, _) in found.items()]
    return readers.roofline_share(
        floors, sum(s for _, s in found.values()), readers.peaks(info))
