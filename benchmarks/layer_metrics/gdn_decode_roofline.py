"""Share of its roofline (memory bound) that the one-token gated delta rule
reaches: the live rows' float32 states read and written once a
linear-attention layer a decode step (``kernels/gdn_decode.py``). Times and
calls are the trace's; the rows are the occupancy the harness counted after
each tick."""

from benchmarks import readers


def read(spans, facts, trace, info):
    if not readers.on_device(trace) or not facts.get("occupancy"):
        return None
    cfg = info["ctx"].config
    if "linear_num_value_heads" not in cfg:
        return None
    k = readers.kernel(info, "gdn_decode")
    found = readers.kernel_seconds(trace, k.TRACE_NAMES)
    if not found:
        return None
    rows = sum(facts["occupancy"]) / len(facts["occupancy"])
    one = k.count(rows, int(cfg["linear_num_value_heads"]),
                  int(cfg["linear_num_key_heads"]),
                  int(cfg["linear_key_head_dim"]),
                  int(cfg["linear_value_head_dim"]))
    return readers.roofline_share(
        [(calls, one) for calls, _ in found.values()],
        sum(s for _, s in found.values()), readers.peaks(info))
