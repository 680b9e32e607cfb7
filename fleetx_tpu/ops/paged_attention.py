"""Pallas paged-attention decode kernel: block tables walked in-kernel,
as far as each row's own context reaches; the KV pool read by layer index.

The kernel takes the WHOLE pool ``[layers, pages, page_size, heads·head_dim]``
and never a layer of it: both pools stay where they are (``pl.ANY``), the
layer index and the per-request page ids arrive as **scalar prefetch**
operands (``pltpu.PrefetchScalarGridSpec``), and the kernel copies page
``(layer, table[b, p])`` of the pool into VMEM itself
(``pltpu.make_async_copy``). So the serving layer scan can carry the pool
as one buffer (``serving/decode.py``): no layer slice is cut out in front
of the kernel, and no dense page view ``pool[layer, block_tables] → [B,
pages_per_req·page_size, heads, head_dim]`` materialises either. An
online-softmax accumulator in f32 VMEM scratch (the
``ops/flash_attention.py`` m/l/acc discipline) folds the pages into the
output without ever holding more than two slots of ``pages_per_step``
``[page_size, head_block·head_dim]`` tiles of K and of V live
(`pick_pages_per_step`: the pages that move 512 KB a pool; below). Both
products of a fold follow the POOL'S dtype and nothing else: a bfloat16
pool takes one MXU pass, bfloat16 probabilities into the value tile as it
landed (as prefill and the gather path do), a float32 pool the exact
multi-pass product; m, l and the accumulator are f32 either way.

Why heads and head_dim are ONE minor dim: a TPU buffer is tiled (8, 128)
over its two minor dims, so a 64-wide ``head_dim`` minor either pads every
row to 128 lanes (2× the pool) or — what the runtime picks for a
``[…, heads, 64]`` shape — makes the PAGE dim minor-most, where no page is
contiguous and every program that reads pages first transposes the whole
pool (v5e compile, PR 28: two 5.25 GB temporaries for a 2.82 GB pool).
``heads·head_dim`` is a multiple of 128 for every geometry the kernel
admits, so the pool is row-major, unpadded, and a page's rows are whole
contiguous lines. The kernel therefore sees K/V tiles ``[page_size,
head_block·head_dim]`` and separates the heads on the MXU: the query of
a head block is laid out block-diagonally (``[head_block,
head_block·head_dim]``, row *h* holding head *h*'s query in its own
lanes, zero elsewhere), ``scores = Qdiag · Kᵀ`` then contracts each row
over its own head's lanes alone, and ``P · V`` yields every head's
probabilities against every head's values, of which the finish keeps the
diagonal blocks.

Grid: ``(batch, head-block)``, ONE grid step a row, and inside it a loop
whose length is read from the row's own ``lens``: row *b* folds
``page_groups_walked(lens[b])`` groups of ``pages_per_step`` pages — the
groups up to the one that holds its query position — and stops; an
inactive row (``lens < 0``) folds none. Nothing past a row's query
position is fetched, indexed or stepped over, so the kernel's time follows
the batch's live context and not the width of the block table (a
rectangular ``(batch, head-block, page-group)`` grid pays ~0.8 µs for
every step of every row, whether it fetches or not: PERF.md, PR 30). A
fold starts the next group's copies (two VMEM slots a pool, one DMA
semaphore a pool and slot) before it waits for its own, and a row's last
fold starts the first group of the next row that has a context: the grid
runs in order (``"arbitrary"``) and a two-word SMEM note carries what is
in flight from one grid step to the next. Null pages (``NULL_PAGE``),
pages past a request's allocation (lazy lifecycle: block-table tails) and
pages another shard owns — holes that can lie BELOW ``lens`` — are
masked in-kernel, as are key positions beyond ``lens``: callers hand the
raw block tables over, the wrapper rewrites invalid entries to ``-1``
(the kernel's skip sentinel), and a page that does not count is never
read: its copy fetches local page 0 instead, so stale or non-finite
pages past the query cannot reach the accumulator.

Fewer key-value heads than query heads, and a window. The pool's minor
dimension is ``kv_heads·head_dim``; with ``group = heads / kv_heads > 1``
query heads to a key-value head, the block-diagonal query has a row for
every QUERY head of the block (row *r* holds its query in the lanes of
key-value head ``r // group``), so a key-value tile is copied once and
read by all the query heads that share it. Which lanes a row owns is a
0/1 mask the wrapper makes once and the kernel keeps in VMEM (its block
index never changes), and the finish folds each row's own ``head_dim``
lanes out of its ``[rows, kv_heads·head_dim]`` accumulator. A grouped head
may be whole lane tiles (``head_dim`` a multiple of 128: the row's query is
concatenated under every key-value head, the finish adds the lane slices) or
HALF of one (``head_dim`` 64, 32 query heads over 8 key-value heads: Mosaic
has no concatenation at half-tile offsets, so a 0/1 matrix ``[head_dim,
lanes]`` repeats the row on the MXU — each value passes as it is — and its
transpose folds the accumulator's own lanes out at ``HIGHEST`` precision,
exactly: one term of every sum is not zero); any other width is refused.
With a ``window`` a row's walk does not start at its first page but at the group
that holds position ``lens − window + 1``: keys older than the window are
neither fetched nor scored, whatever the context. With ``heads ==
kv_heads`` and no window a float32 pool's outputs are to the bit what they
were before either existed (``tests/test_swa_moe.py`` holds a digest).

How a fold's tile is fetched follows the layout. **Pages a fold follow the
bytes a fold moves** (`pick_pages_per_step`): a fold's cost is the core's
own — a loop step, the copies started and waited for, the scalar work of
its pages — and is not hidden behind the copies, so a pool half as wide
takes twice the pages for the same bytes (16 pages of 1,024 bfloat16 lanes,
32 of 512). **A ring is fetched in one copy a pool a fold**: a caller that
keeps a row's last tokens in a ring it laid out itself (``window`` + one
prefill chunk of tokens a slot, ``ring_pages`` consecutive pages of the
buffer, logical page *j* at ``j mod ring_pages``: ``serving/swa_moe.py``)
says so (``ring_pages=``, each row's first ring page in the block table's
place). The fold then divides the ring, so an aligned group of a ring's
pages is one contiguous run of the buffer that never straddles the wrap —
one ``make_async_copy`` of ``[pages, page_size, lanes]`` a pool where the
table path starts a copy a page — and with nothing that grows with its
pages a ring's fold is as large as the VMEM budget allows (32 pages of 512
lanes, 16 of 1,024). No table entry is read and no page flagged: every
page of a ring is valid, so the positions' mask is the whole mask, and what
the ring holds at positions the query does not see is READ and multiplied
by a zero probability — it has to be finite (a ring is zeros until a
program writes it). Through a block table the guarantee above stands: a
page that does not count is never read. Same keys, same masks, same order
of sums: at the same pages a fold the ring fetch gives the table path's
bits (``tests/test_swa_moe.py``).

Contract mirrors ``ops/flash_attention.py`` exactly:

- ``paged_attention_supported(...)`` gates the path; rejected shapes keep
  the gather — degrade, never break (``serving/decode.py`` makes the
  choice ONCE at ``make_step_fns`` time so the jit cache still holds one
  entry).
- CPU runs the kernel in interpret mode (``ops.interpret()``), which is how
  the serving parity suite pins token-identity without a TPU.
- Under a multi-device mesh the kernel is a Mosaic custom call GSPMD
  cannot partition, so ``paged_attention_sharded`` runs it per-device via
  ``shard_map``: pool pages sharded over ``fsdp``, heads over ``tensor``
  (the ``parallel/rules.py`` ``serving_kv`` family stays the one spec
  source), with a cross-shard flash-decoding combine (global running max
  + rescaled numerator/denominator psum) over the page axis.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fleetx_tpu import ops

_VMEM = pltpu.VMEM

_NEG_INF = -1e30

#: the reserved filler page — must match ``serving.paged_cache.NULL_PAGE``
#: (pinned by a test; importing it here would cycle ops ← serving ← ops).
NULL_PAGE = 0

#: live VMEM budget of a grid step: the kernel's K/V page tiles (two
#: slots a pool) plus the block-diagonal query and the f32
#: accumulator/m/l scratch. It bounds ``pages_per_step`` for wide models
#: and rejects only pathological page_size × head_dim configs.
_PAGED_VMEM_BUDGET_BYTES = 4 * 1024 * 1024

#: widest head block: the block-diagonal product does head_block× the
#: needed MXU work and its scratch is head_block² · head_dim, so the block
#: stays narrow; decode attention is DMA-bound and the MXU otherwise idle
_MAX_HEAD_BLOCK = 16

#: what the block tables, a scalar-prefetch operand, may take of the core's
#: 1 MB of scalar memory (the lengths, the layer and the compiler's own
#: scalars share it)
_TABLE_SMEM_BYTES = 960 * 1024

#: what one fold moves, a pool: 16 pages of ``[16, 1024]`` bfloat16 (the 345M
#: serving geometry and Laguna's full layers), 32 of ``[16, 512]``
#: (SmallThinker's). A fold's cost is the core's own and is not hidden behind
#: the copies: ~0.39 µs whatever its pages (a loop step, two products, the
#: accumulator's rescale) plus ~0.064 µs a page (its table entry and
#: predicate read at the start and at the fold, two copies started and two
#: waited for, its rows of the mask) — the SAME 0.058 µs a page on a pool
#: half as wide, so the pages of a fold follow its bytes and the larger
#: fold wins until VMEM ends it. The kernel alone on the v5e, 8 /
#: 16 pages a fold (PR 43's sweep, on PR 41's one-pass product; docs/serving.md
#: has PR 30's and PR 38's): 24 layer calls at the 345M geometry, 64 rows of
#: 128–767 tokens 5.80 / 4.87 ms (HBM floor 3.50), 64 rows of 1,023 11.12 /
#: 8.70 (floor 7.87), 8 live rows of 64 0.97 / 0.91; Laguna's 3 full layers,
#: 48 query rows over 8 × 128, contexts to 9k, 5.09 / 4.10 (floor 3.38);
#: SmallThinker's 2, 28 rows over 4 × 128, 512 lanes, contexts 4k–12k, at 16 /
#: 32 pages 3.46 / 3.03 (floor 1.71). Two slots a pool of 512 KB are 2 MB of
#: the 4 MB budget; 1 MB a pool does not fit. The pages of a row's last group
#: past its query (17 % of what the decode cell's folds copy, 3 % at long
#: contexts) are copied from page 0 for nothing, and for free: with their
#: copies skipped — not started, not waited for — the same sweep read 5.10 ms
#: for 4.87 and 8.98 for 8.70 (a branch a page costs more than two copies
#: nobody waits long for), and with a group's upper half neither fetched nor
#: multiplied where the query does not reach it, 4.80 and 8.73: a page's
#: cost is what the unrolled fold does for it whether it counts or not. A
#: heavier page (float32, a wider block) takes fewer pages for the same
#: bytes, down to the one the VMEM budget allows
_FOLD_BYTES = 512 * 1024

#: what one fold of a RING moves, a pool. A ring's pages are one run of the
#: buffer, fetched in one copy a pool whatever their number, so nothing of a
#: fold's cost grows with its pages and the fold is as large as the VMEM
#: budget and the coarser start of the window's walk allow (a 4,096-key
#: window on 512-key groups: 9 folds fetch 4,608 keys): 32 pages of ``[16,
#: 512]``, 16 of ``[16, 1024]``. The whole decode program at the 512-lane
#: recipe's sizes on the v5e: 27.75 ms with both caches through tables 8
#: pages a fold, 22.59 with a ring fold of 16 pages, 22.01 with 32; the
#: window kernel 8.07 -> 3.57 ms a step, 83 % of its HBM floor (PERF.md, PR 39)
_RING_FOLD_BYTES = 512 * 1024


def pick_head_block(num_heads: int, head_dim: int,
                    dtype: Any = jnp.float32) -> int:
    """Widest head block ≤ `_MAX_HEAD_BLOCK` dividing ``num_heads`` that
    Mosaic can address: the K/V tile's lanes are ``block · head_dim`` wide
    (a multiple of 128, or all the heads), and the block's rows — the
    block-diagonal query, the m/l outputs — are a multiple of the dtype's
    sublane tile (8 rows of 4 bytes, 16 of 2) or all the heads.
    0 when no block qualifies."""
    sublanes = 8 * 4 // jnp.dtype(dtype).itemsize
    for hb in range(min(num_heads, _MAX_HEAD_BLOCK), 0, -1):
        if num_heads % hb:
            continue
        if hb == num_heads or (hb % sublanes == 0
                               and hb * head_dim % 128 == 0):
            return hb
    return 0


def _step_vmem_bytes(pages: int, page_size: int, hb: int, head_dim: int,
                     dtype: Any, group: int = 1) -> int:
    """Live VMEM of one grid step folding ``pages`` pages a fold; ``hb``
    key-value heads a block, ``group`` query heads to each."""
    esize = jnp.dtype(dtype).itemsize
    width, rows = hb * head_dim, hb * group
    tiles = 2 * 2 * pages * page_size * width * esize   # K+V, two slots
    scratch = rows * width * (esize + 4) + 2 * rows * 128 * 4  # (rows, 1) pads
    if group > 1:
        scratch += rows * width * 4                     # the lane mask
        if head_dim % 128:
            scratch += head_dim * width * 4             # the lane spread
    return tiles + scratch


def pick_pages_per_step(*, num_heads: int, head_dim: int, page_size: int,
                        pages_per_req: int, dtype: Any = jnp.float32,
                        num_kv_heads: Optional[int] = None,
                        ring_pages: Optional[int] = None) -> int:
    """Pages one fold takes, from the bytes of a page of the pool it is
    given (``page_size · head block · head_dim · itemsize``, a pool): the
    power of two that moves `_FOLD_BYTES` a pool (at least one page), no
    more than a request has, halved until its two slots a pool fit the
    VMEM budget; 0 when not even one page does. The head block is picked
    over the KEY-VALUE heads (``num_kv_heads``; all the heads when None),
    each with ``num_heads / num_kv_heads`` query rows. With
    ``ring_pages`` — the caller's pages are a ring of that many
    consecutive pages of the buffer — the target is `_RING_FOLD_BYTES` and
    the count also divides the ring, so that an aligned group of a ring's
    pages is one run of the buffer and never straddles the wrap."""
    kv = num_kv_heads or num_heads
    hb = pick_head_block(kv, head_dim, dtype)
    if hb == 0 or num_heads % kv:
        return 0
    page_bytes = page_size * hb * head_dim * jnp.dtype(dtype).itemsize
    target = _FOLD_BYTES if ring_pages is None else _RING_FOLD_BYTES
    most = max(target // page_bytes, 1)
    g = 1 << (most.bit_length() - 1)
    while g and (g > pages_per_req or _step_vmem_bytes(
            g, page_size, hb, head_dim, dtype, num_heads // kv)
            > _PAGED_VMEM_BUDGET_BYTES or (ring_pages or g) % g):
        g //= 2
    return g


def page_walk_shape(*, num_heads: int, head_dim: int, page_size: int,
                    pages_per_req: int, dtype: Any = jnp.float32,
                    num_kv_heads: Optional[int] = None) -> tuple:
    """``(tokens one fold covers, folds a whole table row takes)`` for a
    geometry the kernel admits: what `page_groups_walked` counts in."""
    g = pick_pages_per_step(num_heads=num_heads, head_dim=head_dim,
                            page_size=page_size, pages_per_req=pages_per_req,
                            dtype=dtype, num_kv_heads=num_kv_heads)
    return g * page_size, -(-pages_per_req // g)


def fold_shape(*, ring_pages: Optional[int] = None, **geometry) -> tuple:
    """``(pages a fold takes, copies a pool that fetch them)`` for a
    geometry the kernel admits (`pick_pages_per_step`'s arguments): a copy
    a page through a block table, one for a ring's run of pages."""
    g = pick_pages_per_step(ring_pages=ring_pages, **geometry)
    return g, g if ring_pages is None else 1


def paged_attention_refusal(*, num_heads: int, head_dim: int,
                            page_size: int, pages_per_req: int,
                            dtype: Any = jnp.float32,
                            num_kv_heads: Optional[int] = None,
                            batch: Optional[int] = None) -> str:
    """The bound that keeps the in-kernel page walk from this engine
    geometry, in words, or "" when the kernel applies.

    Consulted ONCE per engine (``serving/decode.py:make_step_fns``, the
    families of ``serving/registry.py``) — shapes it rejects take the
    dense gather path, never silence: the caller logs the reason.
    ``num_heads`` is what ONE device holds (the kernel runs per shard).
    Bounds are alignment (sublane-friendly ``head_dim``, a head block the
    flat ``heads·head_dim`` minor dim and the dtype's tile can address)
    and the VMEM tile budget. The shipped geometry — 16 heads × 64, page
    16, bf16 — compiles and decodes right on the v5e (PERF.md).
    ``num_kv_heads`` (fewer key-value heads than query heads): the query
    rows of a head block are then ``group`` to a key-value head, so they
    have to fill whole sublane tiles or be all the heads (7 query heads to
    each of 4 key-value heads: one block of all 28 rows), and a head's
    lanes have to be whole lane tiles (``head_dim`` a multiple of 128) or
    HALF of one (``head_dim`` 64: 4 query heads to each of 8 key-value
    heads is one block of 32 rows over 512 lanes); a grouped head of any
    other width is refused — nothing here has compiled one.
    ``batch`` (a caller that knows its rows): the block tables are a scalar
    prefetch operand, ``batch · pages_per_req`` 4-byte entries that have to
    fit the core's scalar memory — 256 rows of 18,432 tokens in 16-token
    pages are 1.18 MB and the compiler refuses them (v5e compile, PR 51).
    """
    if batch is not None and batch * pages_per_req * 4 > _TABLE_SMEM_BYTES:
        return f"block tables of {batch} rows x {pages_per_req} pages do " \
               f"not fit the {_TABLE_SMEM_BYTES >> 10} KB of scalar " \
               f"memory a scalar-prefetch operand may take"
    if num_heads < 1 or pages_per_req < 1 or page_size < 1:
        return "no heads, pages or page rows"
    kv = num_kv_heads or num_heads
    if kv < 1 or num_heads % kv:
        return f"{num_heads} query heads are no multiple of {kv} " \
               f"key-value heads"
    if kv != num_heads:
        hb = pick_head_block(kv, head_dim, dtype)
        rows = hb * (num_heads // kv)
        sublanes = 8 * 4 // jnp.dtype(dtype).itemsize
        if head_dim % 128 and head_dim != 64:
            return f"head_dim {head_dim} is neither whole 128-lane tiles " \
                   f"nor half of one (grouped queries)"
        if hb == 0 or (hb != kv and rows % sublanes):
            return f"no block of the {kv} key-value heads gives query " \
                   f"rows in whole {sublanes}-row sublane tiles"
    if head_dim < 8 or head_dim % 8 or head_dim > 256:
        return f"head_dim {head_dim} is not a multiple of 8 in 8..256"
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16)):
        return f"dtype {jnp.dtype(dtype).name} is neither float32 nor " \
               f"bfloat16"
    if pick_pages_per_step(
            num_heads=num_heads, head_dim=head_dim, page_size=page_size,
            pages_per_req=pages_per_req, dtype=dtype, num_kv_heads=kv) <= 0:
        return f"no page fits the {_PAGED_VMEM_BUDGET_BYTES >> 20} MiB " \
               f"VMEM budget of a fold (or no head block addresses " \
               f"{kv} x {head_dim} lanes)"
    return ""


def paged_attention_supported(**geometry) -> bool:
    """True when the in-kernel page walk applies to this engine geometry
    (`paged_attention_refusal` names the bound when it does not)."""
    return not paged_attention_refusal(**geometry)


def paged_sharded_supported(mesh: Any, *, num_heads: int,
                            num_pages: int) -> bool:
    """True when the per-device ``shard_map`` wrapping applies: the pool's
    page dim splits evenly over ``fsdp`` and its head dim over ``tensor``
    (the ``serving_kv`` placement), and decode is not running under
    sequence or pipeline parallelism."""
    if mesh is None:
        return False
    shape = dict(mesh.shape)
    if shape.get("seq", 1) != 1 or shape.get("pipe", 1) != 1:
        return False
    return num_pages % shape.get("fsdp", 1) == 0 and \
        num_heads % shape.get("tensor", 1) == 0


def first_group_walked(lens: Any, group_tokens: int, window: int):
    """The page group a windowed row's walk starts at: the one that holds
    position ``lens − window + 1`` (the oldest key the query sees)."""
    xp = np if isinstance(lens, np.ndarray) else jnp
    return xp.maximum(lens - (window - 1), 0) // group_tokens


def page_groups_walked(lens: Any, group_tokens: int,
                       table_groups: Optional[int],
                       window: Optional[int] = None):
    """Page groups the kernel folds for query positions ``lens``: the
    groups up to and including the one that holds the query (from the
    group `first_group_walked` names, with a ``window``), none for an
    inactive row (``lens < 0``), never more than the table has (a ring has
    no end: ``table_groups`` None). The kernel's trip count (a traced
    scalar) and the engine's ``serving_page_walk_share`` gauge (its host
    copy of the lengths, a NumPy array) both come from here."""
    xp = np if isinstance(lens, np.ndarray) else jnp
    upto = lens // group_tokens + 1
    if table_groups is not None:
        upto = xp.minimum(upto, table_groups)
    if window is not None:
        upto = upto - xp.minimum(
            first_group_walked(lens, group_tokens, window), upto)
    return xp.where(lens < 0, 0, upto)


def _decode_kernel(tables_ref, lens_ref, layer_ref, q_ref, *refs,
                   pages: int, page_size: int, head_dim: int, scale: float,
                   group: int = 1, window: Optional[int] = None,
                   ring_pages: Optional[int] = None):
    """One (request, head-block) grid step: the online-softmax walk over
    the page groups this request's context reaches, ``pages`` pages a
    fold, and no further (nor, with a ``window``, further back than the
    group that holds position ``lens − window + 1``).

    ``tables_ref``/``lens_ref``/``layer_ref`` are the scalar-prefetch
    operands (SMEM). ``k_hbm``/``v_hbm`` are the whole pools, left where
    they are (``pl.ANY``): the kernel copies page ``(layer, table[b, c])``
    into one of two VMEM slots a pool (``k_buf``/``v_buf`` ``[2,
    pages·page_size, hb·hd]``, one DMA semaphore a pool and slot in
    ``sems``) and starts the next group's copies before it waits for this
    group's; a row's last fold starts the first group of the next row
    that has a context, and says so in ``ahead_ref`` (SMEM: whether the
    step's first group is in flight, and in which slot), which is why the
    grid runs in order. The walk is ``page_groups_walked(lens[b])`` folds
    long. A table entry < 0 marks an invalid page — null, beyond the
    request's lazy allocation, or owned by another shard: it and every
    page that starts past the query (or ends before the window) are not
    counted, their positions are masked and their copies read local page
    0 in their place; a group with no counted page is not folded.

    ``ring_pages`` (with a ``window``): a row's pages are a RING the
    caller laid out itself, ``ring_pages`` consecutive pages of the buffer
    from page ``tables_ref[b]`` (``[B]``: no table), logical page *j* at
    ``j mod ring_pages``. ``pages`` divides the ring, so group ``grp`` is
    the run of ``pages`` pages from ``(grp · pages) mod ring_pages`` and
    is fetched in ONE copy a pool (slots ``[2, pages, page_size, hb·hd]``,
    read as the same ``[pages·page_size, hb·hd]`` tile). No table entry is
    read and no page flagged: every page of a ring is a page of the ring,
    so the positions' mask (``q_pos − window < p ≤ q_pos``) is the whole
    mask. What a masked key holds is READ here, and has to be finite.

    ``group == 1``: ``q_ref`` is the head block's queries side by side
    ``[1, 1, hb·hd]``; outputs the f32 numerator ``[1, 1, hb·hd]``, m and
    l ``[1, hb, 1]``. ``group > 1``: ``hb`` counts KEY-VALUE heads,
    ``q_ref`` is ``[1, hb·group, hd]`` (a row a query head), a further
    input is the 0/1 mask of the lanes each row owns ``[hb·group,
    hb·hd]``, and the numerator comes out ``[1, hb·group, hd]``. The
    remaining scratch is the block-diagonal query, the ``[rows, hb·hd]``
    accumulator, m and l (f32). ``Q · Kᵀ`` and ``P · V`` are one MXU pass
    each in a bf16 pool's dtype, the multi-pass product in an f32 pool's.
    """
    spread_ref = None
    if group > 1:
        own_ref, refs = refs[0], refs[1:]
        if head_dim % 128:
            spread_ref, refs = refs[0], refs[1:]
    (k_hbm, v_hbm, acc_out_ref, m_out_ref, l_out_ref,
     k_buf, v_buf, sems, ahead_ref, qd_ref, acc_ref, m_ref, l_ref) = refs
    b = pl.program_id(0)
    h = pl.program_id(1)
    rows = pl.num_programs(0)
    head_blocks = pl.num_programs(1)
    hb, width = acc_ref.shape       # group > 1: hb is the block's QUERY rows
    span = pages * page_size
    q_pos = lens_ref[b]
    layer = layer_ref[0]
    ring = ring_pages is not None
    table_groups = None if ring else tables_ref.shape[1] // pages

    def first_group(row):
        return first_group_walked(lens_ref[row], span, window)

    def folds(row):
        return page_groups_walked(lens_ref[row], span, table_groups, window)

    n_groups = folds(b)
    g0 = first_group(b) if window is not None else 0

    def own_lanes():
        # row h of a [hb, hb·hd] block owns lanes [h·hd, (h+1)·hd)
        lane = jax.lax.broadcasted_iota(jnp.int32, (hb, width), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (hb, width), 0)
        return (lane >= row * head_dim) & (lane < (row + 1) * head_dim)

    def group_pages(row, grp):
        # per page of a group: does it count — valid, and starting at or
        # before the query, so one counted page ⇒ one unmasked score —
        # and the pool page its copies read (local page 0 if it does not).
        # (lax by name: a traced operator costs several times a bind, and
        # this runs for every page at every place that starts a group)
        first = grp * pages
        reach = (lens_ref[row] - grp * span) // page_size  # last page ≤ query
        ids = [tables_ref[row, first + j] for j in range(pages)]
        ok = [jax.lax.bitwise_and(jax.lax.ge(ids[j], 0), jax.lax.ge(reach, j))
              for j in range(pages)]
        if window is not None:
            # ... and ending inside the window: its last key is seen
            oldest = lens_ref[row] - (window - 1) - grp * span
            ok = [jax.lax.bitwise_and(
                ok[j], jax.lax.ge((j + 1) * page_size - 1, oldest))
                for j in range(pages)]
        zero = jax.lax.full_like(reach, 0)
        return ok, [jax.lax.select(ok[j], ids[j], zero) for j in range(pages)]

    def page_copies(slot, head_block, page_ids):
        # the 2 · pages copies that fill ``slot``: pool page
        # ``page_ids[j]`` lands in rows [j·ps, (j+1)·ps) — or, of a ring,
        # the two that do: the run of ``pages`` pages from ``page_ids[0]``
        lanes = pl.ds(pl.multiple_of(head_block * width, width), width)
        pools = tuple(enumerate(((k_hbm, k_buf), (v_hbm, v_buf))))
        if ring:
            return [pltpu.make_async_copy(
                pool.at[layer, pl.ds(page_ids[0], pages), :, lanes],
                buf.at[slot], sems.at[i, slot]) for i, (pool, buf) in pools]
        return [pltpu.make_async_copy(
            pool.at[layer, page_ids[j], :, lanes],
            buf.at[slot, pl.ds(j * page_size, page_size)], sems.at[i, slot])
            for j in range(pages) for i, (pool, buf) in pools]

    def start(row, head_block, grp, slot):
        if ring:
            ids = [tables_ref[row] + jax.lax.rem(grp * pages, ring_pages)]
        else:
            ids = group_pages(row, grp)[1]
        for c in page_copies(slot, head_block, ids):
            c.start()

    @pl.when((b == 0) & (h == 0))
    def _nothing_ahead():
        ahead_ref[0] = 0

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(n_groups > 0)
    def _walk():
        in_flight = ahead_ref[0] == 1
        first_slot = jnp.where(in_flight, ahead_ref[1], 0)

        @pl.when(jnp.logical_not(in_flight))
        def _first_group():
            start(b, h, g0, 0)

        # the grid step after this one that has a context — this row's
        # next head block, else the next active row — gets its first
        # group started by this step's last fold
        same = h + 1 < head_blocks
        next_row = jnp.where(same, b, jax.lax.while_loop(
            lambda r: (r < rows) & (folds(jnp.minimum(r, rows - 1)) == 0),
            lambda r: r + 1, b + 1))
        next_head = jnp.where(same, h + 1, 0)
        ahead_ref[0] = (next_row < rows).astype(jnp.int32)
        ahead_ref[1] = (first_slot + n_groups) % 2
        next_first = 0 if window is None else \
            first_group(jnp.minimum(next_row, rows - 1))

        if group == 1:
            # (select in f32: Mosaic has no relayout for a 2-byte select
            # against the broadcast row)
            q = jnp.broadcast_to(q_ref[0].astype(jnp.float32), (hb, width))
            qd_ref[...] = jnp.where(own_lanes(), q, 0.0).astype(qd_ref.dtype)
        else:
            # a row's query repeated under every key-value head's lanes,
            # kept where the row owns them
            if spread_ref is None:
                q = q_ref[0].astype(jnp.float32)           # [rows, hd]
                q = jnp.concatenate([q] * (width // head_dim), axis=1)
            else:
                # a head narrower than a lane tile: no concatenation at
                # half-tile offsets, the MXU repeats the row instead (a
                # product with 0 / 1: each value comes through as it is)
                q = jax.lax.dot_general(
                    q_ref[0], spread_ref[...].astype(q_ref.dtype),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST
                    if q_ref.dtype == jnp.float32 else None)
            qd_ref[...] = (q * own_ref[...]).astype(qd_ref.dtype)

        def fold(i, _):
            grp = g0 + i
            slot = (first_slot + i) % 2
            last = i + 1 == n_groups

            def tile(buf):
                # a slot as the MXU reads it; a ring's copy landed as the
                # pages it read, [g, ps, hb·hd]
                return buf[slot].reshape(span, width) if ring else buf[slot]

            @pl.when(jnp.logical_not(last) | (next_row < rows))
            def _next_group():
                start(jnp.where(last, next_row, b),
                      jnp.where(last, next_head, h),
                      jnp.where(last, next_first, grp + 1), 1 - slot)

            for c in page_copies(slot, 0, [0] * pages):  # a wait reads sizes
                c.wait()
            # (a ring's walk runs from the window's first group to the
            # query's: each holds a key the query sees)
            ok = [True] if ring else group_pages(b, grp)[0]

            @pl.when(functools.reduce(jnp.logical_or, ok))
            def _compute():
                # Heads share the lanes of a K/V row, so the MXU separates
                # them: row h of the block-diagonal query is zero outside
                # head h's lanes, and contracting it against a key row's
                # lanes is head h's dot product alone. Both products
                # follow the pool's dtype: f32 pools take the multi-pass one
                precision = jax.lax.Precision.HIGHEST \
                    if k_buf.dtype == jnp.float32 else None
                k = tile(k_buf)                            # [g·ps, hb·hd]
                s = jax.lax.dot_general(
                    qd_ref[...], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32, precision=precision
                ) * scale
                col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                if ring:
                    seen = grp * span + col <= q_pos
                else:
                    page_ok = ok[0].astype(jnp.int32)  # per key: its page counts
                    for j in range(1, pages):
                        page_ok = jnp.where(col >= j * page_size,
                                            ok[j].astype(jnp.int32), page_ok)
                    seen = (page_ok > 0) & (grp * span + col <= q_pos)
                if window is not None:
                    seen = seen & (grp * span + col > q_pos - window)
                s = jnp.where(seen, s, _NEG_INF)           # [hb, g·ps]
                m_prev = m_ref[...]                        # [hb, 1]
                m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                pexp = jnp.exp(s - m_new)
                l_ref[...] = l_ref[...] * alpha + pexp.sum(axis=1,
                                                           keepdims=True)
                m_ref[...] = m_new
                # the probabilities in the values' dtype, the tile as it
                # landed: one pass of the MXU at any head count (a bf16 V
                # has no low parts for a second to multiply), f32 exact
                pv = jax.lax.dot_general(
                    pexp.astype(k.dtype), tile(v_buf),     # [g·ps, hb·hd]
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32, precision=precision)
                acc_ref[...] = acc_ref[...] * alpha + pv

        jax.lax.fori_loop(0, n_groups, fold, None)

    # keep each head's own block of its accumulator row; m/l laid out
    # [B, nh, 1]: a (hb, 1) store satisfies Mosaic's last-two-dims tiling
    # where a 2D (1, hb) block does not — the flash kernel's lse idiom.
    if group == 1:
        acc_out_ref[0] = jnp.where(own_lanes(), acc_ref[...], 0.0).sum(
            axis=0, keepdims=True)
    elif spread_ref is None:
        kept = acc_ref[...] * own_ref[...]
        acc_out_ref[0] = functools.reduce(jnp.add, [
            kept[:, j * head_dim:(j + 1) * head_dim]
            for j in range(width // head_dim)])
    else:
        # ... and folds each row's own lanes out again (exact: one term of
        # every sum is not zero, and HIGHEST keeps all of a float32)
        acc_out_ref[0] = jax.lax.dot_general(
            acc_ref[...] * own_ref[...], spread_ref[...],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)
    m_out_ref[0] = m_ref[...]
    l_out_ref[0] = l_ref[...]


def _paged_call(q: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
                tables: jax.Array, lens: jax.Array, layer: jax.Array,
                window: Optional[int] = None,
                ring_pages: Optional[int] = None,
                scale: Optional[float] = None):
    """Raw kernel invocation on one device's shard.

    ``q`` ``[B, nh, hd]``, pools ``[layers, pages, page_size, kv·hd]``
    (the whole pool; ``kv`` key-value heads, ``nh`` a multiple of it),
    ``tables`` ``[B, pages_per_req]`` int32 with ``-1`` marking invalid
    entries, ``lens`` ``[B]`` int32 absolute query positions (< 0 =
    inactive row), ``layer`` an int32 scalar: which layer of the pool the
    K/V tiles are fetched from; ``window``: a query sees the keys at the
    last ``window`` positions only; ``ring_pages``: ``tables`` is ``[B]``,
    the first page of each row's ring of that many pages; ``scale``: what
    the scores are multiplied by (None: ``1 / sqrt(hd)``). Returns the
    UNnormalized ``(acc [B,nh,hd] f32, m [B,nh], l [B,nh])`` triple so
    sharded callers can run the cross-shard softmax combine before
    dividing.
    """
    B, nh, hd = q.shape
    ps = pool_k.shape[2]
    kv = pool_k.shape[3] // hd
    group = nh // kv
    hb = pick_head_block(kv, hd, pool_k.dtype)
    width, rows = hb * hd, hb * group
    if ring_pages is None:
        span, groups = page_walk_shape(
            num_heads=nh, head_dim=hd, page_size=ps,
            pages_per_req=tables.shape[1], dtype=pool_k.dtype,
            num_kv_heads=kv)
        g = span // ps
        # whole page groups: the padding columns are invalid pages
        tables = jnp.pad(tables, ((0, 0), (0, groups * g - tables.shape[1])),
                         constant_values=-1)
        slots = (2, g * ps, width)
    else:
        g = pick_pages_per_step(
            num_heads=nh, head_dim=hd, page_size=ps, pages_per_req=ring_pages,
            dtype=pool_k.dtype, num_kv_heads=kv, ring_pages=ring_pages)
        slots = (2, g, ps, width)       # a copy lands as the pages it read

    def q_map(b, h, t, l, lay):
        return b, 0, h

    def ml_map(b, h, t, l, lay):
        return b, h, 0

    if group == 1:
        q_in = q.astype(pool_k.dtype).reshape(B, 1, nh * hd)
        q_specs = [pl.BlockSpec((1, 1, width), q_map)]
        acc_spec = pl.BlockSpec((1, 1, width), q_map)
        acc_shape = (B, 1, nh * hd)
        inputs = (q_in,)
    else:
        # the lanes row r of a block owns: those of key-value head r // group
        own = (jnp.arange(rows)[:, None] // group
               == jnp.arange(width)[None, :] // hd).astype(jnp.float32)
        q_specs = [pl.BlockSpec((1, rows, hd), ml_map),
                   pl.BlockSpec((rows, width), lambda b, h, t, l, lay: (0, 0))]
        acc_spec = pl.BlockSpec((1, rows, hd), ml_map)
        acc_shape = (B, nh, hd)
        inputs = (q.astype(pool_k.dtype), own)
        if hd % 128:
            # lane l of a block repeats value l mod hd of a row's query
            spread = (jnp.arange(hd)[:, None]
                      == jnp.arange(width)[None, :] % hd).astype(jnp.float32)
            q_specs.append(pl.BlockSpec((hd, width),
                                        lambda b, h, t, l, lay: (0, 0)))
            inputs += (spread,)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, kv // hb),
        in_specs=q_specs + [pl.BlockSpec(memory_space=pl.ANY),
                            pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[
            acc_spec,
            pl.BlockSpec((1, rows, 1), ml_map),
            pl.BlockSpec((1, rows, 1), ml_map),
        ],
        scratch_shapes=[
            _VMEM(slots, pool_k.dtype),
            _VMEM(slots, pool_v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
            _VMEM((rows, width), pool_k.dtype),
            _VMEM((rows, width), jnp.float32),
            _VMEM((rows, 1), jnp.float32),
            _VMEM((rows, 1), jnp.float32),
        ],
    )
    acc, m, l = pl.pallas_call(
        functools.partial(_decode_kernel, pages=g, page_size=ps,
                          head_dim=hd,
                          scale=1.0 / math.sqrt(hd) if scale is None
                          else float(scale), group=group, window=window,
                          ring_pages=ring_pages),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(acc_shape, jnp.float32),
            jax.ShapeDtypeStruct((B, nh, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, nh, 1), jnp.float32),
        ],
        # in order: a row's last fold starts the next row's first copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=ops.interpret(),
        # a device trace tells the windowed walk from the whole one by name
        name="paged_decode" if window is None else "paged_decode_window",
    )(tables, lens, jnp.reshape(layer, (1,)).astype(jnp.int32),
      *inputs, pool_k, pool_v)
    return acc.reshape(B, nh, hd), m[..., 0], l[..., 0]


def _localize_tables(tables: jax.Array, page_lo, local_pages: int):
    """Rewrite global page ids to shard-local ones; null pages and pages
    owned by another shard become the kernel's ``-1`` skip sentinel."""
    local = tables - page_lo
    ok = (tables != NULL_PAGE) & (local >= 0) & (local < local_pages)
    return jnp.where(ok, local, -1).astype(jnp.int32)


def _normalize(acc: jax.Array, l: jax.Array, dtype) -> jax.Array:
    """Final softmax division; fully-masked rows (inactive slots: every
    page skipped, ``l == 0``) come out exactly zero instead of NaN."""
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return (acc / l_safe[..., None]).astype(dtype)


def paged_attention(q: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
                    block_tables: jax.Array, lens: jax.Array,
                    layer: jax.Array, window: Optional[int] = None,
                    ring_pages: Optional[int] = None,
                    scale: Optional[float] = None) -> jax.Array:
    """Single-shard paged decode attention over layer ``layer`` of the
    pool.

    Semantics match ``serving/decode.py``'s gather path for active rows:
    softmax over key positions ``≤ lens`` with ``1/sqrt(head_dim)``
    scaling, f32 accumulation, output cast back to ``q.dtype``. Inactive
    rows (``lens < 0``) return exact zeros (the gather path returns
    finite null-page garbage there; both are discarded by the host).
    The pool's minor dimension says how many key-value heads there are
    (``q``'s heads a multiple of them); with ``window`` the softmax is
    over the last ``window`` positions ``lens − window + 1 … lens``.

    ``ring_pages`` (with a ``window`` no longer than the ring): the caller
    keeps each row's last ``ring_pages · page_size`` tokens in a ring it
    laid out itself — ``ring_pages`` consecutive pages of the buffer, the
    token at position *p* in page ``(p // page_size) mod ring_pages`` of
    them — and ``block_tables`` is ``[B]``, the first page of each row's
    ring. Same answer as the table ``ring_first[:, None] + j mod
    ring_pages`` gives; a fold is then fetched in one copy a pool, and
    what the ring holds at positions the query does not see is read and
    masked, so it has to be finite (a ring is zeros until a program
    writes it). Through a block table a page that does not count is never
    read.

    ``scale`` (None: ``1 / sqrt(head_dim)``): what the scores are
    multiplied by, for a caller whose ``head_dim`` here is not the width its
    scores are scaled by — ``serving/samba_y.py`` scores 64-wide heads
    through 128-lane key-value pairs, each query zero in the half it does
    not score. The output is in ``q``'s dtype (float32 queries, which the
    products take in the pool's dtype, get the float32 quotient back).
    """
    if ring_pages is None:
        block_tables = _localize_tables(block_tables, 0, pool_k.shape[1])
    else:
        assert window is not None and block_tables.ndim == 1 and \
            window <= ring_pages * pool_k.shape[2], "a ring holds its window"
    acc, _, l = _paged_call(q, pool_k, pool_v, block_tables.astype(jnp.int32),
                            lens, layer, window, ring_pages, scale)
    return _normalize(acc, l, q.dtype)


def paged_attention_sharded(q: jax.Array, pool_k: jax.Array,
                            pool_v: jax.Array, block_tables: jax.Array,
                            lens: jax.Array, layer: jax.Array, *,
                            mesh: Optional[Any] = None,
                            window: Optional[int] = None) -> jax.Array:
    """Mesh-aware paged attention: the pool's pages stay sharded over
    ``fsdp`` and its heads over ``tensor`` (the ``serving_kv`` placement
    from ``parallel/rules.py``, taken as it is; ``layer`` is replicated)
    while each device walks only its own page slice; partial (acc, m, l)
    triples are merged with the standard flash-decoding combine (global
    running max over ``fsdp``, rescaled numerator/denominator psum).
    Callers must have gated on :func:`paged_sharded_supported`; with no
    mesh (or one device) this is the single-shard call.
    """
    from jax.sharding import PartitionSpec as _P

    from fleetx_tpu.parallel.rules import kv_pool_spec

    if mesh is None or mesh.size == 1:
        return paged_attention(q, pool_k, pool_v, block_tables, lens, layer,
                               window)

    # the registry's serving_kv spec as it is — rules.py stays the one
    # source of placement (PartitionSpec drops trailing Nones, hence the
    # re-pad to name the page and head axes)
    pool_spec = kv_pool_spec()
    _, pages_ax, _, heads_ax = (tuple(pool_spec) + (None,) * 4)[:4]
    q_spec = _P(None, heads_ax, None)
    local_pages = pool_k.shape[1] // mesh.shape[pages_ax]

    def body(q, pk, pv, tabs, lens, layer):
        lo = jax.lax.axis_index(pages_ax) * local_pages
        tabs = _localize_tables(tabs, lo, local_pages)
        acc, m, l = _paged_call(q, pk, pv, tabs, lens, layer, window)
        # flash-decoding combine across the page shards: rescale every
        # shard's numerator/denominator to the global running max, sum
        m_g = jax.lax.pmax(m, pages_ax)
        w = jnp.exp(m - m_g)
        num = jax.lax.psum(acc * w[..., None], pages_ax)
        den = jax.lax.psum(l * w, pages_ax)
        return _normalize(num, den, q.dtype)

    # manual over EVERY mesh axis: the only context in which a Mosaic call
    # lowers under a mesh, and decode has no other tensor the remaining
    # axes could stay automatic for
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(q_spec, pool_spec, pool_spec, _P(None, None), _P(None),
                  _P()),
        out_specs=q_spec, check_vma=False)
    return fn(q, pool_k, pool_v, block_tables, lens, layer)
