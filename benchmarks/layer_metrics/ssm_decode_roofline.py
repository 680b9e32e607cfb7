"""Share of its roofline (memory bound) that the selective scan's one-token
step reaches: the live rows' float32 states read and written once a scan
layer a decode step (``kernels/ssm_decode.py``). Times and calls are the
trace's; the rows are the occupancy the harness counted after each tick. A
configuration without scan layers, or a trace without the kernel, gives
nothing to read."""

from benchmarks import readers


def read(spans, facts, trace, info):
    if not readers.on_device(trace) or not facts.get("occupancy"):
        return None
    cfg = info["ctx"].config
    if "mb_per_layer" not in cfg:
        return None
    k = readers.kernel(info, "ssm_decode")
    found = readers.kernel_seconds(trace, k.TRACE_NAMES)
    if not found:
        return None
    rows = sum(facts["occupancy"]) / len(facts["occupancy"])
    assumed = cfg["assumed"]
    one = k.count(rows, int(assumed["expand"]) * int(cfg["hidden_size"]),
                  int(assumed["d_state"]))
    return readers.roofline_share(
        [(calls, one) for calls, _ in found.values()],
        sum(s for _, s in found.values()), readers.peaks(info))
