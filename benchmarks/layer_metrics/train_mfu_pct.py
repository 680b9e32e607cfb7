"""Model FLOP/s utilization: operations the forward and backward passes
require per token (no recompute, causal attention) x tokens per step over
the median step time, over chips x the chip's published bf16 peak."""

from benchmarks import readers


def read(spans, facts, trace, info):
    ctx = info["ctx"]
    if not facts.get("median_step_s") or ctx.devices[0].platform != "tpu":
        return None
    per_token = readers.kernel(info, ctx.config["model_flops"]) \
        .train_flops_per_token(ctx.config, facts["seq_len"])
    rate = facts["tokens_per_step"] / facts["median_step_s"]
    peak = readers.peaks(info)["bf16_flops_per_s"] * ctx.chips
    return 100.0 * per_token * rate / peak
