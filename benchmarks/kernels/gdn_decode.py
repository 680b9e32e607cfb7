"""The gated delta rule's one-token step over the decode slots
(``ops/gated_delta.py``: ``gdn_decode``), one call a linear-attention layer
a decode step.

What the algorithm needs of one call: the LIVE rows' states read and
written once (float32, ``value heads * dk * dv`` values a row each way),
``q`` and ``k`` (key heads), ``v``, the decay and the write strength in and
``o`` out (float32 as the kernel takes them), and ``7 * dk * dv``
operations a value head a row: decay (1), ``S k`` (2), the outer-product
write (2), ``S q`` (2). A row that is not live costs nothing."""

TRACE_NAMES = ("gdn_decode",)


def count(rows: float, value_heads: int, key_heads: int, dk: int,
          dv: int) -> dict:
    state = value_heads * dk * dv * 4
    inputs = (2 * key_heads * dk + value_heads * dv + 2 * value_heads) * 4
    return {"flops": 7 * rows * value_heads * dk * dv,
            "bytes": rows * (2 * state + inputs + value_heads * dv * 4)}
