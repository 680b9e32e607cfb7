"""Device milliseconds a decode step spends in the held experts' grouped
products (``moe_gmm_decode``: gate, up and down of every expert layer). The
routing, the sort of the rows, their gathers and the scatter-adds back are
XLA ops and are not in it."""

from benchmarks import readers


def read(spans, facts, trace, info):
    if not readers.on_device(trace):
        return None
    found = readers.kernel_seconds(trace, ("moe_gmm_decode",))
    steps = trace["modules"].get("jit_decode", [0, 0.0])[0]
    if not found or not steps:
        return None
    return 1e3 * found["moe_gmm_decode"][1] / steps
