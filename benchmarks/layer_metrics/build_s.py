"""Seconds from process start until the program's objects stand with the
seeded weights in them (imports, config, engine, parameter placement),
less the runtime's own start-up of the chip, as in ``setup_s``."""


def read(spans, facts, trace, info):
    ctx = info["ctx"]
    if ctx.t_build_done is None:
        return None
    return ctx.t_build_done - ctx.t_start - ctx.chip_start_s
