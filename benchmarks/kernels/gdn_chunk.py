"""The gated delta rule over one prefill chunk (``ops/gated_delta.py``:
``gdn_chunk``), one call a linear-attention layer a chunk.

What the RECURRENCE needs for the chunk's tokens — not what a chunked form
spends, so the share reads the same work whatever implements it: the
sequence's state in and out once (float32), ``q``, ``k``, ``v``, decay and
write strength in and ``o`` out a token, ``7 * dk * dv`` operations a value
head a token (as ``gdn_decode`` counts a row). The kernel named so runs the
part of the chunked form that is carried from chunk to chunk; the
triangular systems in front of it are XLA today. ``gdn_chunk_roofline``
divides by the time of BOTH (the program's ``gdn.core`` scope of a
``jit_prefill`` call), the kernel's calls being the count of calls."""

TRACE_NAMES = ("gdn_chunk",)


def count(tokens: float, value_heads: int, key_heads: int, dk: int,
          dv: int) -> dict:
    state = value_heads * dk * dv * 4
    a_token = (2 * key_heads * dk + 2 * value_heads * dv
               + 2 * value_heads) * 4
    return {"flops": 7 * tokens * value_heads * dk * dv,
            "bytes": 2 * state + tokens * a_token}
