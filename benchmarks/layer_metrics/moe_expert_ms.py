"""Device milliseconds per train step in the grouped expert products of
the sparse-expert layers (``ops/grouped_matmul.py``: ``moe_gmm``,
``moe_gmm_t``, ``moe_tgmm``): forward, recomputed forward and backward of
every expert layer. The gathers of rows and the scatter-adds back into the
tokens' sums are XLA ops and are not in it."""

from benchmarks import readers


def read(spans, facts, trace, info):
    if not readers.on_device(trace):
        return None
    k = readers.kernel(info, "moe_gmm")
    found = readers.kernel_seconds(trace, k.TRACE_NAMES)
    steps = trace["modules"].get("jit_train_step", [0, 0.0])[0]
    if not found or not steps:
        return None
    return 1e3 * sum(s for _, s in found.values()) / steps
