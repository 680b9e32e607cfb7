"""Causal flash attention, backward (``ops/flash_attention.py``): the
fused single-pass kernel ``flash_bwd_fused``, or the split pair
``flash_bwd_dq`` + ``flash_bwd_dkv`` where the fused one does not admit
the shape. Operations and bytes the algorithm needs, from shapes."""

TRACE_NAMES = ("flash_bwd_fused", "flash_bwd_dq", "flash_bwd_dkv")
# matrix products per (query block, key block): S = QK^T is recomputed,
# then dP = dO V^T, dV = P^T dO, dQ = dS K, dK = dS^T Q
PRODUCTS = {"flash_bwd_fused": 5, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}
# [batch, seq, heads, head_dim] tensors read + written
TENSORS = {"flash_bwd_fused": 5 + 3,    # q k v o do -> dq dk dv
           "flash_bwd_dq": 5 + 1, "flash_bwd_dkv": 5 + 2}


def count(batch: int, seq: int, heads: int, head_dim: int,
          dtype_bytes: int = 2, causal: bool = True,
          variant: str = "flash_bwd_fused") -> dict:
    """One call of ``variant`` on ``[batch, seq, heads, head_dim]`` operands."""
    full = PRODUCTS[variant] * 2 * batch * heads * seq * seq * head_dim
    io = TENSORS[variant] * batch * seq * heads * head_dim * dtype_bytes
    return {"flops": full // 2 if causal else full,
            "bytes": io + 2 * batch * heads * seq * 4}   # lse and delta
