"""Peak device memory on the fullest chip, buffers in use plus the
compiled programs' reserved temporaries (the runtime counts them apart),
read when the window closes and before the reference runs."""


def read(spans, facts, trace, info):
    ctx = info["ctx"]
    if ctx.devices[0].platform != "tpu":
        return None
    return ctx.memory_peak_bytes / 1e9
