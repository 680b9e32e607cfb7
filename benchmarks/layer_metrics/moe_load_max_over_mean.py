"""Rows of the fullest held expert over the mean of the held experts, in
the worst expert layer: the mean over the window's steps of the program's
own ``moe_load_max_over_mean``, a histogram of its process-wide
``MetricsRegistry`` that ``fit`` fills from each step's metrics
(``models/mla_moe/module.py:STEP_COUNTERS``). 1.0 is perfect balance; the
grouped products pad each expert's rows to whole tiles, so imbalance costs
them little, but on the deployment's other chips it is the all-to-all's
and the slowest expert's time. A program without the registry, or one that
never recorded the counter (the parent of the PR that added it), gives
nothing to read."""


def read(spans, facts, trace, info):
    try:
        from fleetx_tpu.observability.metrics import get_registry
    except ImportError:
        return None
    hist = get_registry().histogram("moe_load_max_over_mean")
    n = int(facts.get("n_steps") or 0)
    last = hist.last(n) if n and hasattr(hist, "last") else []
    return sum(last) / len(last) if last else None
