"""Bytes of the constant-size state a slot, in GB: the program's own
``serving_state_cache_bytes`` gauge (the linear-attention layers' recurrent
states and convolution tails, all slots; set when the engine is built). What
a decode step reads and writes whole whatever the contexts are. A program
without the gauge, or a family without such a state, gives nothing to
read."""


def read(spans, facts, trace, info):
    try:
        from fleetx_tpu.observability.metrics import get_registry
    except ImportError:
        return None
    value = get_registry().gauge("serving_state_cache_bytes").value
    return float(value) / 1e9 if value else None
