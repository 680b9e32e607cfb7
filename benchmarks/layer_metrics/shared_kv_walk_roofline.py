"""Share of its roofline (memory bound) that decode attention reaches over
the ONE paged key-value layer that several layers read: ``paged_decode``,
called by the full layer and by every cross layer, each walking the same
pages (``kernels/shared_kv_walk.py``). The floor is the decode steps' walks:
``jit_decode`` calls in the trace x the reading layers (1 + the cross
layers: ``(num_hidden_layers / 2 - 2) / 2``) x K and V of the live context
at 5,120 B a token, the queries in and the two maps' outputs out. The time
is ALL of the kernel's in the trace, the one-row walks of the prefill
program's upper half among them (7 a chunk over ONE request's context: under
1 % of a decode step's bytes, left out of the floor). The context and the
occupancy are what the harness counted after each tick."""

from benchmarks import readers


def read(spans, facts, trace, info):
    if not readers.on_device(trace) or not facts.get("context_tokens") \
            or not facts.get("occupancy"):
        return None
    cfg = info["ctx"].config
    if "mb_per_layer" not in cfg or "num_key_value_heads" not in cfg:
        return None
    k = readers.kernel(info, "shared_kv_walk")
    found = readers.kernel_seconds(trace, k.TRACE_NAMES)
    steps = trace.get("modules", {}).get("jit_decode", [0, 0.0])[0]
    if not found or not steps:
        return None
    rows = sum(facts["occupancy"]) / len(facts["occupancy"])
    context = sum(facts["context_tokens"]) / len(facts["context_tokens"])
    heads = int(cfg["num_attention_heads"])
    readers_of_pool = 1 + (int(cfg["num_hidden_layers"]) // 2 - 2) // 2
    one = k.count(rows, context, heads, int(cfg["num_key_value_heads"]),
                  int(cfg["hidden_size"]) // heads)
    return readers.roofline_share(
        [(steps * readers_of_pool, one)],
        sum(s for _, s in found.values()), readers.peaks(info))
