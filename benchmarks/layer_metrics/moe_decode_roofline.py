"""Share of their roofline (memory bound) that the held experts' grouped
products reach in the DECODE program alone (``moe_gmm_decode``; the
accepted ``moe_serve_roofline`` reads both programs' as one share). A gated
expert MLP is three products a layer (gate, up, down), so a third of the
kernel's calls are on each shape. The floor of a call is the harness's
EXPECTATION of it — the rows the mean occupancy of the decode batch puts on
the held experts and the matrices of the held experts it expects to hit
(``kernels/moe_serve.py``, imported as it is) — never a number the program
reports; times and calls are the trace's. The padding of each expert's run
to whole tiles, and a call that re-reads a matrix, show as a lower share."""

from benchmarks import readers


def read(spans, facts, trace, info):
    if not readers.on_device(trace) or not facts.get("occupancy"):
        return None
    cfg = info["ctx"].config
    if "router_experts" not in cfg or "moe_intermediate_size" not in cfg:
        return None
    k = readers.kernel(info, "moe_serve")
    found = readers.kernel_seconds(trace, ("moe_gmm_decode",))
    if not found:
        return None
    (calls, seconds), = found.values()
    h, f = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    tokens = sum(facts["occupancy"]) / len(facts["occupancy"])
    if not tokens:
        return None
    rows = k.expected_rows(cfg, tokens)
    hit = k.expected_experts_hit(cfg, tokens)
    return readers.roofline_share(
        [(2 * calls / 3, k.count(rows, hit, h, f)),        # gate, up
         (calls / 3, k.count(rows, hit, f, h))],           # down
        seconds, readers.peaks(info))
