"""Share of its roofline (memory bound) that the selective scan's one-token
step reaches in a configuration that publishes the scan's sizes
(``mamba_expand``, ``mamba_d_state``: 26 scan layers of 28, 256 slots — the
state is the step's largest read after the weights): the live rows' float32
states read and written once a scan layer a decode step
(``kernels/ssm_decode.py``, the count the fifth family's reader takes: a
kernel's floor is the same work whatever implements it). Times and calls are
the trace's; the rows are the occupancy the harness counted after each tick.
A configuration without those keys, or a trace without the kernel, gives
nothing to read."""

from benchmarks import readers


def read(spans, facts, trace, info):
    if not readers.on_device(trace) or not facts.get("occupancy"):
        return None
    cfg = info["ctx"].config
    if "mamba_expand" not in cfg or "mamba_d_state" not in cfg:
        return None
    k = readers.kernel(info, "ssm_decode")
    found = readers.kernel_seconds(trace, k.TRACE_NAMES)
    if not found:
        return None
    rows = sum(facts["occupancy"]) / len(facts["occupancy"])
    one = k.count(rows, int(cfg["mamba_expand"]) * int(cfg["hidden_size"]),
                  int(cfg["mamba_d_state"]))
    return readers.roofline_share(
        [(calls, one) for calls, _ in found.values()],
        sum(s for _, s in found.values()), readers.peaks(info))
