"""Bytes of all the engine's cache buffers, in GB: the program's own
``serving_kv_cache_bytes`` gauge (set in ``serve.gauges``; the paged pool
of the layers that keep every token plus, where the family has them, the
window layers' rings). Fixed when the engine is built: it says what the
cell's memory is spent on beside the weights. A program without the gauge
gives nothing to read."""


def read(spans, facts, trace, info):
    try:
        from fleetx_tpu.observability.metrics import get_registry
    except ImportError:
        return None
    value = get_registry().gauge("serving_kv_cache_bytes").value
    return float(value) / 1e9 if value else None
