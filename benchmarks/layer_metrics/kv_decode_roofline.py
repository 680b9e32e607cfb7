"""Share of its roofline (memory bound) that decode attention reaches over
both kinds of cache: ``paged_decode`` in the layers that keep every token
(K and V of the live context, once a layer a step) and
``paged_decode_window`` in the window layers (K and V of the last
``sliding_window`` tokens of every running row; every prompt of the cell is
at least the window, so a running row holds exactly that many). Times and
calls are the trace's; the context and the occupancy are what the harness
counted after each tick. A call's layer kind follows from its kernel's
name, and its query heads from the configuration's per-layer list."""

from benchmarks import readers


def read(spans, facts, trace, info):
    if not readers.on_device(trace) or not facts.get("context_tokens") \
            or not facts.get("occupancy"):
        return None
    cfg = info["ctx"].config
    if "sliding_window" not in cfg or "layer_types" not in cfg:
        return None
    k = readers.kernel(info, "kv_decode")
    found = readers.kernel_seconds(trace, k.TRACE_NAMES)
    if not found:
        return None
    rows = sum(facts["occupancy"]) / len(facts["occupancy"])
    context = sum(facts["context_tokens"]) / len(facts["context_tokens"])
    layers = range(int(cfg["num_hidden_layers"]))
    floors = []
    for name, (calls, _) in found.items():
        window = name.endswith("_window")
        want = "sliding_attention" if window else "full_attention"
        heads = [cfg["num_attention_heads_per_layer"][l] for l in layers
                 if cfg["layer_types"][l] == want]
        if not heads:
            return None
        keys = rows * int(cfg["sliding_window"]) if window else context
        for h in heads:     # a step calls the kernel once a layer
            floors.append((calls / len(heads), k.count(
                rows, keys, int(h), int(cfg["num_key_value_heads"]),
                int(cfg["head_dim"]))))
    return readers.roofline_share(
        floors, sum(s for _, s in found.values()), readers.peaks(info))
