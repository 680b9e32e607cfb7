"""Share of their roofline that the grouped expert products reach
(``moe_gmm``, ``moe_gmm_t``, ``moe_tgmm`` together). The rows are the
harness's own: the load the cell's traffic is expected to put on the held
experts (``kernels/moe_gmm.py:expected_rows``), not a number the program
reports; a seed whose router sends this chip more rows than that reads a
lower share, one that sends fewer a higher one (the program's
``moe_held_share`` counter says which). The times and the calls are the
trace's. An execution of a gated expert MLP multiplies by two matrices,
gate-and-up and down, so half of a kernel's calls are on each; over the
traced steps every execution (forward, its recomputations, the input and
the weight gradients) multiplies each layer's rows once, in however many
passes: the operations follow the rows, the experts' matrices are charged
once a call. The padding of each expert's run to whole tiles shows as a
lower share."""

from benchmarks import readers


def read(spans, facts, trace, info):
    if not readers.on_device(trace):
        return None
    k = readers.kernel(info, "moe_gmm")
    found = readers.kernel_seconds(trace, k.TRACE_NAMES)
    steps = trace["modules"].get("jit_train_step", [0, 0.0])[0]
    if set(found) != set(k.TRACE_NAMES) or not steps:
        return None
    cfg = info["ctx"].config
    h, f, held = cfg["hidden_size"], cfg["moe_intermediate_size"], \
        cfg["n_routed_experts"]
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] \
        + cfg.get("num_nextn_predict_layers", 0)
    rows_a_layer = k.expected_rows(
        cfg, facts["tokens_per_step"] / info["ctx"].chips)
    floors = []
    for name, (calls, _) in found.items():
        per_shape = calls / 2
        rows = rows_a_layer * layers * steps / per_shape
        for kk, nn in ((h, 2 * f), (f, h)):
            floors.append((per_shape, k.count(rows, kk, nn, held,
                                              variant=name)))
    return readers.roofline_share(
        floors, sum(s for _, s in found.values()), readers.peaks(info))
