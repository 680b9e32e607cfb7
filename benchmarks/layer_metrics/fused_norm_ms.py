"""Device milliseconds per train step in the fused residual + LayerNorm
kernels, forward and backward. Not a roofline share: XLA hands these
kernels operands that it keeps in on-chip memory (layout ``S(1)`` in the
trace), so the HBM byte floor of ``kernels/fused_norm.py`` does not bound
them (it read 178 % on the v5e, PERF.md)."""

from benchmarks import readers


def read(spans, facts, trace, info):
    if not readers.on_device(trace):
        return None
    k = readers.kernel(info, "fused_norm")
    found = readers.kernel_seconds(trace, k.TRACE_NAMES)
    steps = trace["modules"].get("jit_train_step", [0, 0.0])[0]
    if not found or not steps:
        return None
    return 1e3 * sum(s for _, s in found.values()) / steps
