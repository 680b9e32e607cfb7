"""Causal latent-attention flash backward (``ops/mla_attention.py``): the
split pair ``mla_flash_bwd_dq`` + ``mla_flash_bwd_dkv``, each recomputing
S = QK^T from the saved log-sum-exp. Operations and bytes the algorithm
needs at the published widths (``nope`` + ``rope`` scores, ``v`` values);
the kernels' 128-deep rotary products are not counted, so they show."""

TRACE_NAMES = ("mla_flash_bwd_dq", "mla_flash_bwd_dkv")


def count(batch: int, seq: int, heads: int, nope: int, rope: int, v: int,
          variant: str, dtype_bytes: int = 2, causal: bool = True) -> dict:
    """One call of ``variant``. Widths contracted or produced per (query,
    key) pair: S recomputed (nope + rope) and dP = dO V^T (v) in both;
    dQ = dS K (nope + rope) in ``dq``; dV = P^T dO (v) and dK = dS^T Q
    (nope + rope) in ``dkv``."""
    qk = nope + rope
    widths = {"mla_flash_bwd_dq": qk + v + qk,
              "mla_flash_bwd_dkv": qk + v + v + qk}[variant]
    full = 2 * batch * heads * seq * seq * widths
    read = heads * (qk + nope + 2 * v) + rope          # q k_nope v do, k_rope
    written = {"mla_flash_bwd_dq": heads * qk,
               "mla_flash_bwd_dkv": heads * (nope + v + rope)}[variant]
    io = batch * seq * (read + written) * dtype_bytes
    return {"flops": full // 2 if causal else full,
            "bytes": io + 2 * batch * heads * seq * 4}   # lse and delta
