"""Rows of the fullest held expert over the mean of the held experts, in
the worst expert layer of a decode step, mean over the window's last steps:
the program's own ``serving_moe_load_max_over_mean`` histogram (filled from
the decode program's outputs, which ride to the host with the tokens). 1.0
is perfect balance. Like ``moe_experts_hit_pct`` a fact about the traffic
and the weights, not a lever: the grouped products pad each expert's rows
to whole tiles and are bound by the read of the matrices, so imbalance costs
a step little until one expert's rows pass a tile
(``moe_serve_passes_per_layer``).
A program without the histogram (the parent of the PR that added it) gives
nothing to read."""


def read(spans, facts, trace, info):
    try:
        from fleetx_tpu.observability.metrics import get_registry
    except ImportError:
        return None
    hist = get_registry().histogram("serving_moe_load_max_over_mean")
    n = int((facts.get("counters") or {}).get("engine_steps") or 0)
    last = hist.last(n) if n and hasattr(hist, "last") else []
    return sum(last) / len(last) if last else None
