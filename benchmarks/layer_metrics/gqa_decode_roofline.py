"""Share of its roofline (memory bound) that decode attention reaches in
the short-convolution family's attention layers: ``paged_decode`` with 4
query heads to each key-value head of 64 — half a lane tile. The floor of
a call (a layer a step) is K and V of the live context at the PUBLISHED
widths (``num_key_value_heads x head_dim`` values of 2 bytes a key, once:
the query heads that share a key-value head read its tile once), the
queries in and the outputs out (``kernels/kv_decode.py``); whatever the pool
pads and the pages a fold fetches past a query are the kernel's own cost.
Times and calls are the trace's; the context and the occupancy are what the
harness counted after each tick. A configuration without convolution layers
gives nothing to read here (``kv_decode_roofline``, ``paged_decode_roofline``
read the other families' calls of the same kernel)."""

from benchmarks import readers


def read(spans, facts, trace, info):
    if not readers.on_device(trace) or not facts.get("context_tokens") \
            or not facts.get("occupancy"):
        return None
    cfg = info["ctx"].config
    if "conv_L_cache" not in cfg or "num_key_value_heads" not in cfg:
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
