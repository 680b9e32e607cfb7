"""Decode attention over ONE paged key-value layer that several layers read
(``ops/paged_attention.py``: ``paged_decode``, called by the full layer and
by every cross layer of ``serving/samba_y.py``), with two score maps a head
pair (differential attention).

What the algorithm needs of one call (one reading layer, one decode step):
K and V of the live context once (``2 * kv_heads * head_dim`` values of
``dtype_bytes`` a token: 5,120 B at 20 heads of 64 in bfloat16), a row's
queries in (``heads * head_dim``) and its two maps' outputs out (``heads * 2
* head_dim`` float32: every map multiplies its pair's two value heads, and
the difference is taken outside the kernel), and for every query head
``Q K^T`` over ``head_dim`` and ``P V`` over ``2 * head_dim``. That the same
pages are read by every reading layer is the architecture's cost, not the
kernel's: each call's floor is its own walk. The zero half of a query the
kernel multiplies, and pages fetched past a query, are the kernel's own
cost and do not count."""

TRACE_NAMES = ("paged_decode",)


def count(batch: float, context_tokens: float, heads: int, kv_heads: int,
          head_dim: int, dtype_bytes: int = 2) -> dict:
    return {"flops": 2 * context_tokens * heads * 3 * head_dim,
            "bytes": 2 * context_tokens * kv_heads * head_dim * dtype_bytes
            + batch * heads * head_dim * (dtype_bytes + 2 * 4)}
