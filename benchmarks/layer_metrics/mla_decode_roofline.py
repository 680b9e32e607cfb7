"""Share of its roofline (memory bound) that the latent decode kernel
reaches: the live context's latents, 576 values of 2 bytes a key at the
published widths, read once a latent-attention layer a decode step
(``kernels/mla_decode.py``). Times and calls are the trace's; the context
and the occupancy are what the harness counted after each tick."""

from benchmarks import readers


def read(spans, facts, trace, info):
    if not readers.on_device(trace) or not facts.get("context_tokens") \
            or not facts.get("occupancy"):
        return None
    cfg = info["ctx"].config
    if "kv_lora_rank" not in cfg:
        return None
    k = readers.kernel(info, "mla_decode")
    found = readers.kernel_seconds(trace, k.TRACE_NAMES)
    if not found:
        return None
    rows = sum(facts["occupancy"]) / len(facts["occupancy"])
    context = sum(facts["context_tokens"]) / len(facts["context_tokens"])
    one = k.count(rows, context, int(cfg["num_attention_heads"]),
                  int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"]),
                  int(cfg["kv_lora_rank"]))
    return readers.roofline_share(
        [(calls, one) for calls, _ in found.values()],
        sum(s for _, s in found.values()), readers.peaks(info))
