"""Turns the held experts' loop takes in an expert layer of a decode step:
the program's own ``serving_moe_passes_total`` counter (all layers of every
decode step fetched since the engine was built; it rides the decode
program's outputs to the host with the tokens) over the steps that were
fetched with it (the count of ``serving_moe_load_max_over_mean``, recorded
in the same call) and over the configuration's expert layers. 1.0: every
step's sorted rows fit one pass; a second pass walks the sorted rows again
(their gather, the grouped products' launch, the scatter-add), which happens
when one expert's rows pass a tile or the padding of many does. A program without the counter (the
parent of the PR that added it) gives nothing to read."""


def read(spans, facts, trace, info):
    try:
        from fleetx_tpu.observability.metrics import get_registry
    except ImportError:
        return None
    cfg = info["ctx"].config
    n = int(cfg.get("num_hidden_layers") or 0)
    layers = n - sum(int(l) < n for l in cfg.get("mlp_only_layers", []))
    reg = get_registry()
    passes = reg.counter("serving_moe_passes_total").value
    steps = getattr(reg.histogram("serving_moe_load_max_over_mean"),
                    "total_count", 0)
    if not passes or not steps or not layers:
        return None
    return float(passes) / float(steps) / layers
