"""Paged decode attention (``ops/paged_attention.py``: ``paged_decode``):
one query row per head against each request's cached context.

What the algorithm needs is the live context only: for ``context_tokens``
cached tokens in all (summed over the batch's rows), K and V are read once
(2 * heads * head_dim values a token) and QK^T and PV are
2 * heads * head_dim multiply-adds a token each. Pages fetched for padding
are the kernel's own cost and do not count."""

TRACE_NAMES = ("paged_decode",)


def count(batch: int, context_tokens: int, heads: int, head_dim: int,
          dtype_bytes: int = 2) -> dict:
    return {"flops": 2 * 2 * context_tokens * heads * head_dim,
            "bytes": 2 * context_tokens * heads * head_dim * dtype_bytes
            + 2 * batch * heads * head_dim * dtype_bytes}    # q in, o out
