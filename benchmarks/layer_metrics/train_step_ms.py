"""Wall milliseconds per training step: the median gap between the ends
of consecutive steps in the window (host clock; ``fit`` fetches each
step's metrics, so the device is drained at every step's end). The median
keeps a traced run's pause, while the profiler writes its file, out of
it; the end-to-end rate is taken over all the window's time."""


def read(spans, facts, trace, info):
    step = facts.get("median_step_s")
    return None if step is None else 1e3 * step
