"""Device milliseconds per train step that the core's op line spends in
collective ops (all-gather, reduce-scatter, all-reduce and their -done
halves): time a collective held the core, not hidden behind compute."""

from benchmarks import readers


def read(spans, facts, trace, info):
    if not readers.on_device(trace):
        return None
    steps = trace["modules"].get("jit_train_step", [0, 0.0])[0]
    return 1e3 * trace["collective_s"] / steps if steps else None
