"""Causal latent-attention flash forward (``ops/mla_attention.py``:
``mla_flash_fwd``): operations and bytes the algorithm needs, from the
published widths. A score is a ``nope``-wide product per head plus a
``rope``-wide product whose key all heads share; values are ``v`` wide.
The kernel runs the rotary product 128 deep where 64 are needed: that
padding is its own cost and is not counted here, so it shows."""

TRACE_NAMES = ("mla_flash_fwd",)


def count(batch: int, seq: int, heads: int, nope: int, rope: int, v: int,
          dtype_bytes: int = 2, causal: bool = True) -> dict:
    """One call: QK^T over ``nope + rope`` and PV over ``v`` are
    2 * seq * seq * width operations each per head, of
    which a causal mask needs half; q (nope + rope), k_nope, v per head
    and the one shared rotary key are read, o written, plus the float32
    log-sum-exp row per head."""
    full = 2 * batch * heads * seq * seq * (nope + rope + v)
    io = batch * seq * (heads * (2 * nope + rope + 2 * v) + rope) \
        * dtype_bytes
    return {"flops": full // 2 if causal else full,
            "bytes": io + batch * heads * seq * 4}
