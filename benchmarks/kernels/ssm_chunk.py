"""The selective scan over one prefill chunk (``ops/selective_scan.py``:
``ssm_chunk``), one call a scan layer a chunk.

What the RECURRENCE needs for the chunk's tokens — not what an
implementation spends, so the share reads the same work whatever runs it:
the sequence's state in and out once (float32, ``states * channels``), a
token's ``x`` and step in and ``y`` out (``channels`` values each, float32
as the kernel takes them), its ``B`` and ``C``, the layer's ``A`` and ``D``
once, and ``7 * states + 3`` operations a channel a token (as ``ssm_decode``
counts a row). ``ssm_chunk_roofline`` divides by ALL the device spends in
the program's ``ssm.core`` scope of a ``jit_prefill`` call — the kernel and
what stands in front of it (the casts, ``B`` and ``C`` laid along the lanes,
the slot's state cut out and put back) — the kernel's calls being the count
of calls."""

TRACE_NAMES = ("ssm_chunk",)


def count(tokens: float, channels: int, states: int) -> dict:
    state = states * channels * 4
    a_token = (3 * channels + 2 * states) * 4
    return {"flops": tokens * channels * (7 * states + 3),
            "bytes": 3 * state + channels * 4 + tokens * a_token}
