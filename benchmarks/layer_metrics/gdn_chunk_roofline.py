"""Share of the RECURRENCE's roofline that the chunked gated delta rule
reaches over a prefill chunk (``kernels/gdn_chunk.py``: what the rule needs
for the chunk's tokens, whatever a chunked form spends). The time is ALL the
device spends on the rule in a ``jit_prefill`` call — the program's
``gdn.core`` scope: the ``gdn_chunk`` kernel AND what stands in front of it
(the triangular systems, the decayed products, today XLA) — so work moved
into the kernel raises the share and work pushed out of it cannot. Calls
are the kernel's in the trace; a call's tokens are the engine's prefill
chunk (every prompt of the cell is whole chunks)."""

from benchmarks import program_scopes, readers


def read(spans, facts, trace, info):
    if not readers.on_device(trace):
        return None
    cfg = info["ctx"].config
    if "linear_num_value_heads" not in cfg:
        return None
    chunk = [o.split("=")[1] for o in cfg["serve"]["overrides"]
             if o.startswith("Serving.prefill_chunk=")]
    k = readers.kernel(info, "gdn_chunk")
    found = readers.kernel_seconds(trace, k.TRACE_NAMES)
    us = program_scopes.scope_us(program_scopes.of_run(trace, info),
                                 ("jit_prefill",), scopes=("gdn.core",))
    if not found or not chunk or not us:
        return None
    one = k.count(int(chunk[0]), int(cfg["linear_num_value_heads"]),
                  int(cfg["linear_num_key_heads"]),
                  int(cfg["linear_key_head_dim"]),
                  int(cfg["linear_value_head_dim"]))
    return readers.roofline_share(
        [(calls, one) for calls, _ in found.values()], us / 1e6,
        readers.peaks(info))
