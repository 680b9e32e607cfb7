"""Share of the RECURRENCE's roofline (memory bound) that the selective scan
reaches over a prefill chunk in a configuration that publishes the scan's
sizes (``mamba_expand``, ``mamba_d_state``), as ``ssm_chunk_roofline`` reads
it for the fifth family (``kernels/ssm_chunk.py``): the time is ALL the
device spends on the scan in a ``jit_prefill`` call — the program's
``ssm.core`` scope: the ``ssm_chunk`` kernel AND what stands in front of it
(the casts to float32, ``B`` and ``C`` laid along the lanes, the slot's
state cut out and put back). Calls are the kernel's in the trace; a call's
tokens are the engine's prefill chunk (every prompt of the cell is whole
chunks)."""

from benchmarks import program_scopes, readers


def read(spans, facts, trace, info):
    if not readers.on_device(trace):
        return None
    cfg = info["ctx"].config
    if "mamba_expand" not in cfg or "mamba_d_state" not in cfg:
        return None
    chunk = [o.split("=")[1] for o in cfg["serve"]["overrides"]
             if o.startswith("Serving.prefill_chunk=")]
    k = readers.kernel(info, "ssm_chunk")
    found = readers.kernel_seconds(trace, k.TRACE_NAMES)
    us = program_scopes.scope_us(program_scopes.of_run(trace, info),
                                 ("jit_prefill",), scopes=("ssm.core",))
    if not found or not chunk or not us:
        return None
    one = k.count(int(chunk[0]),
                  int(cfg["mamba_expand"]) * int(cfg["hidden_size"]),
                  int(cfg["mamba_d_state"]))
    return readers.roofline_share(
        [(calls, one) for calls, _ in found.values()], us / 1e6,
        readers.peaks(info))
