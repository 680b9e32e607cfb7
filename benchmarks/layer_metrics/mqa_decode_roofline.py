"""Share of its roofline (memory bound) that decode attention reaches where
MANY query heads read ONE key-value head: ``paged_decode`` with every query
head in one block over a pool one lane tile wide. The floor of a call (an
attention layer a step) is K and V of the live context at the PUBLISHED
widths (``num_key_value_heads x head_dim`` values of 2 bytes a key, once),
the queries in and the outputs out (``kernels/kv_decode.py``); the pages a
fold fetches past a query are the kernel's own cost. Times and calls are the
trace's; the context and the occupancy are what the harness counted after
each tick. Read only where the configuration has scan layers beside its
attention (``attn_layer_period``): the other families' calls of the same
kernel are ``kv_decode_roofline``'s, ``gqa_decode_roofline``'s and
``paged_decode_roofline``'s."""

from benchmarks import readers


def read(spans, facts, trace, info):
    if not readers.on_device(trace) or not facts.get("context_tokens") \
            or not facts.get("occupancy"):
        return None
    cfg = info["ctx"].config
    if "attn_layer_period" not in cfg or "num_key_value_heads" not in cfg:
        return None
    k = readers.kernel(info, "kv_decode")
    found = readers.kernel_seconds(trace, ("paged_decode",))
    if not found:
        return None
    rows = sum(facts["occupancy"]) / len(facts["occupancy"])
    context = sum(facts["context_tokens"]) / len(facts["context_tokens"])
    heads = int(cfg["num_attention_heads"])
    one = k.count(rows, context, heads, int(cfg["num_key_value_heads"]),
                  int(cfg["hidden_size"]) // heads)
    return readers.roofline_share(
        [(calls, one) for calls, _ in found.values()],
        sum(s for _, s in found.values()), readers.peaks(info))
