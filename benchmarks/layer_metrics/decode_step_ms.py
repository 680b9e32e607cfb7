"""Device milliseconds per execution of the serving decode program
(``XLA Modules`` line of the trace). The engine's own timer is not read:
it stops at ``device_get``, which the prefill step calls only on a
prompt's last chunk, so it times the dispatch of the others."""

from benchmarks import readers


def read(spans, facts, trace, info):
    if not readers.on_device(trace):
        return None
    calls, seconds = trace["modules"].get("jit_decode", [0, 0.0])
    return 1e3 * seconds / calls if calls else None
