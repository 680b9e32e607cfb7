"""Share of their roofline (memory bound) that the held experts' grouped
products reach in the two serving programs (``moe_gmm_decode``,
``moe_gmm_prefill``). A gated expert MLP is three products a layer (gate,
up, down), so a third of a kernel's calls are on each shape. The floor of a
call is the harness's EXPECTATION of it — the rows the cell's traffic puts
on the held experts and the matrices of the held experts it expects to hit
(``kernels/moe_serve.py``), from the mean occupancy of the decode batch and
a full prefill chunk — never a number the program reports; times and calls
are the trace's. The padding of each expert's run to whole tiles, and a
call that re-reads a matrix, show as a lower share."""

from benchmarks import readers


def read(spans, facts, trace, info):
    if not readers.on_device(trace) or not facts.get("occupancy"):
        return None
    cfg = info["ctx"].config
    if "router_experts" not in cfg or "moe_intermediate_size" not in cfg:
        return None
    k = readers.kernel(info, "moe_serve")
    found = readers.kernel_seconds(trace, k.TRACE_NAMES)
    if not found:
        return None
    h, f = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    chunk = next((int(o.split("=")[1]) for o in cfg["serve"]["overrides"]
                  if o.startswith("Serving.prefill_chunk=")), None)
    tokens = {"moe_gmm_decode":
              sum(facts["occupancy"]) / len(facts["occupancy"]),
              "moe_gmm_prefill": chunk}
    floors = []
    for name, (calls, _) in found.items():
        if not tokens.get(name):
            return None
        rows = k.expected_rows(cfg, tokens[name])
        hit = k.expected_experts_hit(cfg, tokens[name])
        floors.append((2 * calls / 3, k.count(rows, hit, h, f)))   # gate, up
        floors.append((calls / 3, k.count(rows, hit, f, h)))       # down
    return readers.roofline_share(
        floors, sum(s for _, s in found.values()), readers.peaks(info))
