"""Causal flash attention, forward (``ops/flash_attention.py``:
``flash_fwd``): operations and bytes the algorithm needs, from shapes."""

TRACE_NAMES = ("flash_fwd",)


def count(batch: int, seq: int, heads: int, head_dim: int,
          dtype_bytes: int = 2, causal: bool = True) -> dict:
    """One call on q, k, v ``[batch, seq, heads, head_dim]``: QK^T and PV
    are 2 * seq * seq * head_dim multiply-adds each per head, of which a
    causal mask needs half; q, k, v are read and o written once, plus the
    float32 log-sum-exp row per head."""
    full = 2 * 2 * batch * heads * seq * seq * head_dim
    io = 4 * batch * seq * heads * head_dim * dtype_bytes
    return {"flops": full // 2 if causal else full,
            "bytes": io + batch * heads * seq * 4}
