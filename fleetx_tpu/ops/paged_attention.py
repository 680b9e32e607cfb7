"""Pallas paged-attention decode kernel: block tables walked in-kernel,
the KV pool read by layer index.

The kernel takes the WHOLE pool ``[layers, pages, page_size, heads·head_dim]``
and never a layer of it: the layer index and the per-request page ids
arrive as **scalar prefetch** operands (``pltpu.PrefetchScalarGridSpec``),
and the K/V BlockSpec index maps read them to DMA page ``(layer,
table[b, p])`` of the pool directly. So the serving layer scan can carry
the pool as one buffer (``serving/decode.py``): no layer slice is cut out
in front of the kernel, and no dense page view ``pool[layer, block_tables]
→ [B, pages_per_req·page_size, heads, head_dim]`` materialises either. An
online-softmax accumulator in f32 VMEM scratch (the
``ops/flash_attention.py`` m/l/acc discipline) folds the pages into the
output without ever holding more than ``pages_per_step`` ``[page_size,
head_block·head_dim]`` tiles of K/V live.

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

Grid: ``(batch, head-block, page-group)`` with the page walk innermost so
the accumulators (index-map invariant over the page dim) stay
VMEM-resident across the whole walk and are flushed once. Each grid step
folds ``pages_per_step`` pages: the pool is handed to the call that many
times, each operand's index map reading its own column of the block
table, so a step's DMAs amortise the fixed cost of a grid step. Null
pages (``NULL_PAGE``), pages past a request's allocation (lazy lifecycle:
block-table tails), and key positions beyond the query's ``lens`` are
all masked in-kernel — callers hand the raw block tables over and the
wrapper rewrites invalid entries to ``-1`` (the kernel's skip sentinel).

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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fleetx_tpu import ops

_VMEM = pltpu.VMEM

_NEG_INF = -1e30

#: the reserved filler page — must match ``serving.paged_cache.NULL_PAGE``
#: (pinned by a test; importing it here would cycle ops ← serving ← ops).
NULL_PAGE = 0

#: per-grid-step live VMEM budget for the kernel's K/V page tiles
#: (double-buffered) plus the block-diagonal query and the f32
#: accumulator/m/l scratch. It bounds ``pages_per_step`` for wide models
#: and rejects only pathological page_size × head_dim configs.
_PAGED_VMEM_BUDGET_BYTES = 4 * 1024 * 1024

#: widest head block: the block-diagonal product does head_block× the
#: needed MXU work and its scratch is head_block² · head_dim, so the block
#: stays narrow; decode attention is DMA-bound and the MXU otherwise idle
_MAX_HEAD_BLOCK = 16

#: most pages folded in one grid step: a step costs ~0.35 µs whatever it
#: moves, a 16 × 1024 bf16 page tile 0.04 µs of HBM time. 24 layer calls
#: at the 345M serving geometry, 1 / 2 / 4 / 8 / 16 pages a step: 35.1 /
#: 23.1 / 16.7 / 14.2 / 13.2 ms on the v5e (PERF.md, PR 28)
_MAX_PAGES_PER_STEP = 8


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
                     dtype: Any) -> int:
    """Live VMEM of one grid step folding ``pages`` pages."""
    esize = jnp.dtype(dtype).itemsize
    width = hb * head_dim
    tiles = 2 * 2 * pages * page_size * width * esize   # K+V, two buffers
    scratch = hb * width * (esize + 4) + 2 * hb * 128 * 4  # (hb, 1) pads
    return tiles + scratch


def pick_pages_per_step(*, num_heads: int, head_dim: int, page_size: int,
                        pages_per_req: int,
                        dtype: Any = jnp.float32) -> int:
    """Pages one grid step folds: the most (a power of two ≤
    `_MAX_PAGES_PER_STEP`, no more than a request has) whose tiles fit
    the VMEM budget; 0 when not even one page does."""
    hb = pick_head_block(num_heads, head_dim, dtype)
    if hb == 0:
        return 0
    g = _MAX_PAGES_PER_STEP
    while g and (g > pages_per_req or _step_vmem_bytes(
            g, page_size, hb, head_dim, dtype) > _PAGED_VMEM_BUDGET_BYTES):
        g //= 2
    return g


def paged_attention_supported(*, num_heads: int, head_dim: int,
                              page_size: int, pages_per_req: int,
                              dtype: Any = jnp.float32) -> bool:
    """True when the in-kernel page walk applies to this engine geometry.

    Consulted ONCE per engine (``serving/decode.py:make_step_fns``) —
    shapes it rejects take the dense gather path, never silence.
    ``num_heads`` is what ONE device holds (the kernel runs per shard).
    Bounds are alignment (sublane-friendly ``head_dim``, a head block the
    flat ``heads·head_dim`` minor dim and the dtype's tile can address)
    and the VMEM tile budget. The shipped geometry — 16 heads × 64, page
    16, bf16 — compiles and decodes right on the v5e (PERF.md).
    """
    if num_heads < 1 or pages_per_req < 1 or page_size < 1:
        return False
    if head_dim < 8 or head_dim % 8 or head_dim > 256:
        return False
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16)):
        return False
    return pick_pages_per_step(
        num_heads=num_heads, head_dim=head_dim, page_size=page_size,
        pages_per_req=pages_per_req, dtype=dtype) > 0


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


def _decode_kernel(tables_ref, lens_ref, layer_ref, q_ref, *refs,
                   pages: int, page_size: int, head_dim: int, scale: float):
    """One (request, head-block, page-group) step of the online-softmax
    walk over ``pages`` pages.

    ``tables_ref``/``lens_ref``/``layer_ref`` are the scalar-prefetch
    operands (SMEM); the layer is consumed by the K/V index maps alone, so
    the ``pages`` K refs and ``pages`` V refs in ``refs`` already hold
    that layer's pages ``[1, page_size, hb·hd]``. A table entry < 0 marks
    an invalid page — null, beyond the request's lazy allocation, or
    owned by another shard: its positions are masked, and a step whose
    pages are all invalid or past the query is skipped entirely (the
    DMAs still land, on local page 0, but are never folded in). After
    them come the outputs (the f32 numerator ``[1, 1, hb·hd]``, m and l
    ``[1, hb, 1]``) and the scratch (the block-diagonal query, the
    ``[hb, hb·hd]`` accumulator, m, l).
    """
    k_refs, v_refs = refs[:pages], refs[pages:2 * pages]
    (acc_out_ref, m_out_ref, l_out_ref,
     qd_ref, acc_ref, m_ref, l_ref) = refs[2 * pages:]
    b = pl.program_id(0)
    p = pl.program_id(2)
    np_ = pl.num_programs(2)
    hb, width = acc_ref.shape

    def own_lanes():
        # row h of a [hb, hb·hd] block owns lanes [h·hd, (h+1)·hd)
        lane = jax.lax.broadcasted_iota(jnp.int32, (hb, width), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (hb, width), 0)
        return (lane >= row * head_dim) & (lane < (row + 1) * head_dim)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        # (select in f32: Mosaic has no relayout for a 2-byte select
        # against the broadcast row)
        q = jnp.broadcast_to(q_ref[0].astype(jnp.float32), (hb, width))
        qd_ref[...] = jnp.where(own_lanes(), q, 0.0).astype(qd_ref.dtype)

    q_pos = lens_ref[b]
    base = p * pages * page_size
    # page j of this step counts when it is valid and starts at or before
    # the query; at least one counted page ⇒ at least one unmasked score
    counted = [(tables_ref[b, p * pages + j] >= 0)
               & (base + j * page_size <= q_pos) for j in range(pages)]
    run = functools.reduce(jnp.logical_or, counted) & (q_pos >= 0)

    @pl.when(run)
    def _compute():
        # Heads share the lanes of a K/V row, so the MXU separates them:
        # row h of the block-diagonal query is zero outside head h's
        # lanes, and contracting it against a key row's lanes is head h's
        # dot product alone. f32 pools take the multi-pass product.
        exact = jax.lax.Precision.HIGHEST
        k = jnp.concatenate([r[0] for r in k_refs], axis=0)  # [g·ps, hb·hd]
        s = jax.lax.dot_general(
            qd_ref[...], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=exact if k.dtype == jnp.float32 else None) * scale
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        page_ok = counted[0].astype(jnp.int32)     # per key: its page counts
        for j in range(1, pages):
            page_ok = jnp.where(col >= j * page_size,
                                counted[j].astype(jnp.int32), page_ok)
        s = jnp.where((page_ok > 0) & (base + col <= q_pos), s,
                      _NEG_INF)                            # [hb, g·ps]
        m_prev = m_ref[...]                                # [hb, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + pexp.sum(axis=1, keepdims=True)
        m_ref[...] = m_new
        v = jnp.concatenate([r[0] for r in v_refs],
                            axis=0).astype(jnp.float32)    # [g·ps, hb·hd]
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            pexp, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=exact)

    @pl.when(p == np_ - 1)
    def _finish():
        # keep each head's own block of its accumulator row; m/l laid out
        # [B, nh, 1]: a (hb, 1) store satisfies Mosaic's last-two-dims
        # tiling where a 2D (1, hb) block does not — the flash kernel's
        # lse idiom.
        acc_out_ref[0] = jnp.where(own_lanes(), acc_ref[...], 0.0).sum(
            axis=0, keepdims=True)
        m_out_ref[0] = m_ref[...]
        l_out_ref[0] = l_ref[...]


def _paged_call(q: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
                tables: jax.Array, lens: jax.Array, layer: jax.Array):
    """Raw kernel invocation on one device's shard.

    ``q`` ``[B, nh, hd]``, pools ``[layers, pages, page_size, nh·hd]``
    (the whole pool), ``tables`` ``[B, pages_per_req]`` int32 with ``-1``
    marking invalid entries, ``lens`` ``[B]`` int32 absolute query
    positions (< 0 = inactive row), ``layer`` an int32 scalar: which
    layer of the pool the K/V tiles are fetched from. Returns the
    UNnormalized ``(acc [B,nh,hd] f32, m [B,nh], l [B,nh])`` triple so
    sharded callers can run the cross-shard softmax combine before
    dividing.
    """
    B, nh, hd = q.shape
    ps = pool_k.shape[2]
    hb = pick_head_block(nh, hd, pool_k.dtype)
    g = pick_pages_per_step(num_heads=nh, head_dim=hd, page_size=ps,
                            pages_per_req=tables.shape[1],
                            dtype=pool_k.dtype)
    # whole page groups: the padding columns are invalid pages
    tables = jnp.pad(tables, ((0, 0), (0, -tables.shape[1] % g)),
                     constant_values=-1)
    width = hb * hd

    def q_map(b, h, p, t, l, lay):
        return b, 0, h

    def ml_map(b, h, p, t, l, lay):
        return b, h, 0

    def kv_spec(j):
        # the layer dim is squeezed: the body sees [1, ps, hb·hd]
        return pl.BlockSpec(
            (None, 1, ps, width),
            lambda b, h, p, t, l, lay: (
                lay[0], jnp.maximum(t[b, p * g + j], 0), 0, h))

    kv_specs = [kv_spec(j) for j in range(g)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, nh // hb, tables.shape[1] // g),
        in_specs=[pl.BlockSpec((1, 1, width), q_map)] + kv_specs + kv_specs,
        out_specs=[
            pl.BlockSpec((1, 1, width), q_map),
            pl.BlockSpec((1, hb, 1), ml_map),
            pl.BlockSpec((1, hb, 1), ml_map),
        ],
        scratch_shapes=[
            _VMEM((hb, width), pool_k.dtype),
            _VMEM((hb, width), jnp.float32),
            _VMEM((hb, 1), jnp.float32),
            _VMEM((hb, 1), jnp.float32),
        ],
    )
    acc, m, l = pl.pallas_call(
        functools.partial(_decode_kernel, pages=g, page_size=ps,
                          head_dim=hd, scale=1.0 / math.sqrt(hd)),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, nh * hd), jnp.float32),
            jax.ShapeDtypeStruct((B, nh, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, nh, 1), jnp.float32),
        ],
        interpret=ops.interpret(),
        name="paged_decode",
    )(tables, lens, jnp.reshape(layer, (1,)).astype(jnp.int32),
      q.astype(pool_k.dtype).reshape(B, 1, nh * hd),
      *([pool_k] * g), *([pool_v] * g))
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
                    layer: jax.Array) -> jax.Array:
    """Single-shard paged decode attention over layer ``layer`` of the
    pool.

    Semantics match ``serving/decode.py``'s gather path for active rows:
    softmax over key positions ``≤ lens`` with ``1/sqrt(head_dim)``
    scaling, f32 accumulation, output cast back to ``q.dtype``. Inactive
    rows (``lens < 0``) return exact zeros (the gather path returns
    finite null-page garbage there; both are discarded by the host).
    """
    tables = _localize_tables(block_tables, 0, pool_k.shape[1])
    acc, _, l = _paged_call(q, pool_k, pool_v, tables, lens, layer)
    return _normalize(acc, l, q.dtype)


def paged_attention_sharded(q: jax.Array, pool_k: jax.Array,
                            pool_v: jax.Array, block_tables: jax.Array,
                            lens: jax.Array, layer: jax.Array, *,
                            mesh: Optional[Any] = None) -> jax.Array:
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
        return paged_attention(q, pool_k, pool_v, block_tables, lens, layer)

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
        acc, m, l = _paged_call(q, pk, pv, tabs, lens, layer)
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
