"""Paged KV cache: a preallocated page pool + a host-side page allocator.

The training-era ``DecodeCache`` (``models/gpt/model.py``) is one dense
``[layers, batch, max_len, heads, head_dim]`` buffer per generate() call:
every row pays ``max_len`` slots whether its request is 4 tokens or 4000,
and the buffer's batch dim is welded to one call's lifetime. Serving needs
the vLLM-style shape instead: ONE pool of fixed-size pages allocated for
the process lifetime, per-request *block tables* mapping logical token
positions to pool pages, and a host-side allocator that admits or refuses
requests against real free capacity ("Compiler-First State Space Duality
and Portable O(1) Autoregressive Caching for Inference", PAPERS.md, is the
O(1)-append blueprint this follows).

Pool layout (K and V each)::

    [layers, num_pages, page_size, heads * head_dim]

Heads and head_dim share the minor dim on purpose: a TPU buffer is tiled
(8, 128) over its two minor dims, so ``[..., heads, 64]`` either pads
every row to 128 lanes or is stored page-dim-minor, where a page is not
contiguous and every jitted step first transposes the whole pool
(``ops/paged_attention.py``). ``heads * head_dim`` keeps a token's K (or
V) row one contiguous line and the pool unpadded.

Page 0 is the reserved **null page**: block-table filler slots and masked
(inactive) batch rows point at it, so the jitted steps can scatter/gather
with fully static shapes and no host-side branching — garbage written to
or read from page 0 is always masked out of the attention scores.

Sharding: ``pool_shardings`` places the page dim over ``fsdp`` and the
heads dim over ``tensor``, so cache capacity scales with the mesh the same
way the reference's dp-sharded serving scaled batch
(``inference_engine.py:128-163``); the engine keeps the pool constrained
through every jitted step.
"""

from __future__ import annotations

from typing import Any, Optional

import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fleetx_tpu.observability import tsan

#: reserved scratch page — never allocated, always masked when read
NULL_PAGE = 0


class PageAllocatorError(ValueError):
    """A page-accounting violation: double-free, freeing a page that was
    never handed out, or an invalid (non-positive) allocation size.

    A real exception, NOT an ``assert`` — under ``python -O`` an assert
    vanishes and a double-free silently corrupts the free list (the same
    page handed to two requests ⇒ cross-request KV corruption). Exhaustion
    is NOT an error: ``alloc`` returns None for that, and the scheduler's
    preempt-and-swap path handles it.
    """


def init_pool(cfg: Any, num_pages: int, page_size: int,
              dtype: Any = None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Allocate the (K, V) page pools for a GPT config.

    ``num_pages`` INCLUDES the reserved null page, so usable capacity is
    ``(num_pages - 1) * page_size`` token slots per layer, and the pool's
    bytes are ``2 (K, V) * layers * num_pages * page_size * heads *
    head_dim * itemsize``.

    A family whose layers do not all keep every token sizes two caches
    (``serving/swa_moe.py:init_cache``; docs/serving.md "Sizing the
    pool"): this pool over its FULL-attention layers and KEY-VALUE heads
    only — ``2 * full_layers * num_pages * page_size * kv_heads * head_dim
    * itemsize`` — which is what ``num_pages``, admission, growth and
    preemption count; and for its window layers a ring a decode slot,
    ``2 * window_layers * (1 + max_batch * ceil((window + prefill_chunk) /
    page_size)) * page_size * kv_heads * head_dim * itemsize``, which does
    not depend on ``max_seq_len`` or ``num_pages`` at all.
    """
    dtype = dtype or cfg.dtype
    shape = (cfg.num_layers, int(num_pages), int(page_size),
             cfg.num_attention_heads * cfg.head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def pool_shardings(mesh: Mesh) -> NamedSharding:
    """The pool's mesh placement: pages over ``fsdp``, heads over ``tensor``.

    Pool dims are ``(layers, pages, page_size, heads * head_dim)``; the
    spec is the registry's ``serving_kv`` family rule
    (``parallel/rules.py:kv_pool_spec``) — the page dim shards over the
    ZeRO axis (capacity scales with fsdp degree) and the heads dim over
    the Megatron axis, and shardcheck audits page/head divisibility for
    every serving config statically.
    """
    from fleetx_tpu.parallel.rules import kv_pool_spec

    return NamedSharding(mesh, kv_pool_spec())


class PageAllocator:
    """Host-side free-list allocator over the pool's page ids.

    The engine's default admission policy is **lazy** (vLLM-style): a
    request is admitted on its prompt pages plus a small headroom
    watermark, grows one page at a time as decode crosses page
    boundaries, and the scheduler preempts the youngest request when the
    pool runs dry (``ServingEngine._grow_or_preempt``). The allocator
    itself is policy-free — it hands out and reclaims page ids,
    all-or-nothing, and raises :class:`PageAllocatorError` on any
    accounting violation. ``internal_fragmentation`` reports
    reserved-but-unwritten slack so the occupancy gauge stays honest
    under either policy (reserve-up-front remains available via
    ``ServingConfig.lazy_alloc = False`` for A/B measurement).

    Thread-confinement: the allocator is owned by the engine's scheduler
    thread; ``FLEETX_TSAN=1`` (``observability/tsan.py``) flags any
    cross-thread alloc/free — the preemption path mutates free-list state
    mid-decode, so the kill-one drill runs it sanitized.
    """

    def __init__(self, num_pages: int, page_size: int):
        assert num_pages >= 2, "need at least the null page + one usable page"
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        # LIFO free list → recently-freed (cache-warm) pages are reused first
        self._free = list(range(self.num_pages - 1, NULL_PAGE, -1))
        self._allocated: set[int] = set()
        tsan.register_object(self, "page-allocator")

    # ------------------------------------------------------------- capacity
    @property
    def usable_pages(self) -> int:
        """Pages that can ever be handed out (pool minus the null page)."""
        return self.num_pages - 1

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def allocated_pages(self) -> int:
        return len(self._allocated)

    def pages_needed(self, tokens: int) -> int:
        """Pages required to hold ``tokens`` KV entries."""
        return max(-(-int(tokens) // self.page_size), 1)

    def can_allocate(self, n: int) -> bool:
        """Whether ``n`` pages are free right now."""
        return n <= len(self._free)

    def fits_ever(self, n: int) -> bool:
        """Whether ``n`` pages could EVER be satisfied — False is the
        permanent-refusal signal (the request is larger than the pool)."""
        return n <= self.usable_pages

    # ------------------------------------------------------------ alloc/free
    def alloc(self, n: int) -> Optional[list[int]]:
        """Allocate ``n`` pages, or None (leaving state untouched) when
        the free list cannot satisfy the request — never a partial grant.

        The two failure modes are distinct on purpose: exhaustion (the
        pool is merely full right now) returns None so schedulers can
        wait or preempt, while ``n <= 0`` raises
        :class:`PageAllocatorError` — a zero/negative ask is a caller
        bug, and conflating it with exhaustion used to make "admit on 0
        prompt pages" look like an OOM.
        """
        tsan.note_access(self, "alloc")
        if n <= 0:
            raise PageAllocatorError(f"invalid allocation size {n}")
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        self._allocated.update(pages)
        return pages

    def free(self, pages: list[int]) -> None:
        """Return ``pages`` to the free list.

        Raises :class:`PageAllocatorError` on a page that is not
        currently allocated (double-free / never-allocated / null page) —
        state up to the offending page is already returned, so this is a
        crash-the-replica signal, not a recoverable one.
        """
        tsan.note_access(self, "free")
        for p in pages:
            if p not in self._allocated:
                raise PageAllocatorError(
                    f"freeing unallocated page {p} (double-free or foreign "
                    f"id); {len(self._allocated)} pages currently out")
            self._allocated.discard(p)
            self._free.append(p)

    # ------------------------------------------------------------- metrics
    def occupancy(self) -> float:
        """Allocated fraction of usable pages (the page-occupancy gauge)."""
        return len(self._allocated) / max(self.usable_pages, 1)

    def internal_fragmentation(self, used_slots: int) -> float:
        """Reserved-but-unwritten fraction of the allocated slots.

        ``used_slots`` is the engine's count of token positions actually
        written across live requests; everything else inside allocated
        pages is reservation overhead of the admission policy.
        """
        allocated_slots = len(self._allocated) * self.page_size
        if allocated_slots <= 0:
            return 0.0
        return 1.0 - min(int(used_slots), allocated_slots) / allocated_slots
