"""Decode over the paged pool of latents in the absorbed form
(``ops/mla_paged_attention.py``: ``mla_paged_decode``): ``heads`` queries of
the latent's width against ONE key-value head whose value is the key's
leading part.

What the algorithm needs of one call: every key a query sees read once at
the PUBLISHED width (``kv_lora_rank + qk_rope_head_dim`` values of 2 bytes:
the pool's padding lanes and the pages fetched past a query are the
kernel's own cost), the absorbed queries in and the latent-space outputs
out, and ``2 * heads * (key width + value width)`` operations a key."""

TRACE_NAMES = ("mla_paged_decode",)


def count(batch: float, keys_seen: float, heads: int, key_width: int,
          value_width: int, dtype_bytes: int = 2) -> dict:
    return {"flops": 2 * keys_seen * heads * (key_width + value_width),
            "bytes": keys_seen * key_width * dtype_bytes
            + batch * heads * (key_width + value_width) * dtype_bytes}
