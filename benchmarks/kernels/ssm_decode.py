"""The selective scan's one-token step over the decode slots
(``ops/selective_scan.py``: ``ssm_decode``), one call a scan layer a decode
step.

What the algorithm needs of one call: the LIVE rows' states read and written
once (float32, ``states * channels`` values a row each way), a row's ``x``
and step in and ``y`` out (``channels`` values each, float32 as the kernel
takes them), its ``B`` and ``C`` (``states`` values each), the layer's ``A``
and ``D`` once, and ``7 * states + 3`` operations a channel a row: the
exponent's argument, the exponential, the decay, the write (2), the read
(2); the step times the input and the skip (3). A row that is not live costs
nothing."""

TRACE_NAMES = ("ssm_decode",)


def count(rows: float, channels: int, states: int) -> dict:
    state = states * channels * 4
    a_row = (3 * channels + 2 * states) * 4
    return {"flops": rows * channels * (7 * states + 3),
            "bytes": rows * (2 * state + a_row) + state + channels * 4}
