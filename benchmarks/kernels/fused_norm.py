"""Fused residual-add + LayerNorm (``ops/fused_norm.py``: ``fused_norm_fwd``
and ``fused_norm_bwd``). A memory-bound pass: what counts is the row
tensors read and written.

Forward: the plain site (ln1, ln_f) reads x and writes out (2 row
tensors); the residual site (ln2) reads x and r and writes out and their
sum (4). Backward: reads the summed input and d(out), writes dx (3), plus
the incoming d(sum) at a residual site (4). A traced call does not say
which site it is, so a cell's reader gives the mean over its sites.
"""

TRACE_NAMES = ("fused_norm_fwd", "fused_norm_bwd")
ROW_TENSORS = {"fused_norm_fwd": {"plain": 2, "residual": 4},
               "fused_norm_bwd": {"plain": 3, "residual": 4}}


def count(rows: int, hidden: int, row_tensors: float,
          dtype_bytes: int = 2) -> dict:
    """One call over ``[rows, hidden]``: about 8 operations an element
    (mean, variance, normalise, affine), two float32 statistics a row."""
    return {"flops": 8 * rows * hidden,
            "bytes": int(row_tensors * rows * hidden * dtype_bytes)
            + rows * 2 * 4}
