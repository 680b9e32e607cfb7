"""Decode attention over two kinds of cache (``ops/paged_attention.py``
with fewer key-value heads than query heads): ``paged_decode`` walks a
request's pages in the layers that keep every token, ``paged_decode_window``
walks the last ``window`` tokens of a slot's ring in the window layers.

What the algorithm needs: K and V of the keys a query sees, once
(``2 * kv_heads * head_dim`` values a key; the query heads that share a
key-value head read its tile once), the queries in and the outputs out, and
``QK^T`` and ``PV`` for every QUERY head. Pages fetched for padding are
the kernel's own cost and do not count."""

TRACE_NAMES = ("paged_decode", "paged_decode_window")


def count(batch: float, keys_seen: float, heads: int, kv_heads: int,
          head_dim: int, dtype_bytes: int = 2) -> dict:
    """One call: ``keys_seen`` keys in all (summed over the batch's rows),
    ``batch`` rows with a query each."""
    return {"flops": 2 * 2 * keys_seen * heads * head_dim,
            "bytes": 2 * keys_seen * kv_heads * head_dim * dtype_bytes
            + 2 * batch * heads * head_dim * dtype_bytes}     # q in, o out
