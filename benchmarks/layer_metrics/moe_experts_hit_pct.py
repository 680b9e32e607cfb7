"""Share of the held experts that a decode step hits (at least one row),
mean over the step's expert layers and over the window's last steps: the
program's own ``serving_moe_experts_hit`` histogram (filled from the decode
program's outputs, which ride to the host with the tokens) over the
configuration's held experts, in %. A decode step reads the matrices of the
experts it hits, so this is the share of the experts' bytes a step moves. A
program without the histogram gives nothing to read."""


def read(spans, facts, trace, info):
    try:
        from fleetx_tpu.observability.metrics import get_registry
    except ImportError:
        return None
    held = info["ctx"].config.get("num_experts")
    hist = get_registry().histogram("serving_moe_experts_hit")
    n = int((facts.get("counters") or {}).get("engine_steps") or 0)
    last = hist.last(n) if n and hasattr(hist, "last") else []
    if not last or not held:
        return None
    return 100.0 * sum(last) / len(last) / float(held)
