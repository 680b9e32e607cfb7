"""Share of the RECURRENCE's roofline (memory bound) that the selective scan
reaches over a prefill chunk (``kernels/ssm_chunk.py``). The time is ALL the
device spends on the scan in a ``jit_prefill`` call — the program's
``ssm.core`` scope: the ``ssm_chunk`` kernel AND what stands in front of it
(the casts to float32, ``B`` and ``C`` laid along the lanes, the slot's
state cut out and put back) — so work moved into the kernel raises the share
and work pushed out of it cannot. Calls are the kernel's in the trace; a
call's tokens are the engine's prefill chunk (every prompt of the cell is
whole chunks)."""

from benchmarks import program_scopes, readers


def read(spans, facts, trace, info):
    if not readers.on_device(trace):
        return None
    cfg = info["ctx"].config
    if "mb_per_layer" not in cfg:
        return None
    chunk = [o.split("=")[1] for o in cfg["serve"]["overrides"]
             if o.startswith("Serving.prefill_chunk=")]
    k = readers.kernel(info, "ssm_chunk")
    found = readers.kernel_seconds(trace, k.TRACE_NAMES)
    us = program_scopes.scope_us(program_scopes.of_run(trace, info),
                                 ("jit_prefill",), scopes=("ssm.core",))
    if not found or not chunk or not us:
        return None
    assumed = cfg["assumed"]
    one = k.count(int(chunk[0]),
                  int(assumed["expand"]) * int(cfg["hidden_size"]),
                  int(assumed["d_state"]))
    return readers.roofline_share(
        [(calls, one) for calls, _ in found.values()], us / 1e6,
        readers.peaks(info))
