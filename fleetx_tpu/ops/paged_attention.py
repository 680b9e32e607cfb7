"""Pallas paged-attention decode kernel: block tables walked in-kernel.

The serving decode step previously materialised the dense page view
``pool[block_tables] → [B, pages_per_req·page_size, heads, head_dim]``
per layer per token — ``B·pages_per_req·page_size·heads·head_dim`` bytes
of HBM gather traffic for keys that are mostly masked tail. This kernel
removes the materialisation: per-request page ids arrive as **scalar
prefetch** operands (``pltpu.PrefetchScalarGridSpec``), the BlockSpec
index maps read them to DMA each page of the pool directly, and an
online-softmax accumulator in f32 VMEM scratch (the
``ops/flash_attention.py`` m/l/acc discipline) folds every page into the
output without ever holding more than one ``[page_size, head_block,
head_dim]`` tile of K/V live.

Grid: ``(batch, head-block, page-block)`` with the page walk innermost so
the accumulator output block (index-map invariant over the page dim)
stays VMEM-resident across the whole walk and is flushed once. Null
pages (``NULL_PAGE``), pages past a request's allocation (lazy lifecycle:
block-table tails), and key positions beyond the query's ``lens`` are
all masked in-kernel — callers hand the raw block tables over and the
wrapper rewrites invalid entries to ``-1`` (the kernel's skip sentinel).

Contract mirrors ``ops/flash_attention.py`` exactly:

- ``paged_attention_supported(...)`` gates the path; rejected shapes keep
  today's gather — degrade, never break (``serving/decode.py`` makes the
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

#: per-grid-step live VMEM budget for the kernel's K/V page tiles plus the
#: f32 accumulator/m/l scratch, double-buffered. Decode tiles are tiny
#: (one page × one head block), so this bound only rejects pathological
#: page_size × head_dim configs rather than anything a serving YAML ships.
_PAGED_VMEM_BUDGET_BYTES = 2 * 1024 * 1024

#: widest head block: decode attention is DMA-bound, so a wider block only
#: grows the K/V tile — but each grid step costs a fixed overhead, so the
#: block is as wide as this cap allows
_MAX_HEAD_BLOCK = 16


def pick_head_block(num_heads: int, dtype: Any = jnp.float32) -> int:
    """Widest head block ≤ `_MAX_HEAD_BLOCK` dividing ``num_heads`` that
    Mosaic can address: heads are the second-minor dim of the K/V tile
    ``[page_size, heads, head_dim]``, so the block is a multiple of the
    dtype's sublane tile (8 rows of 4 bytes, 16 of 2) or all the heads.
    0 when no block qualifies."""
    sublanes = 8 * 4 // jnp.dtype(dtype).itemsize
    for hb in range(min(num_heads, _MAX_HEAD_BLOCK), 0, -1):
        if num_heads % hb == 0 and (hb % sublanes == 0 or hb == num_heads):
            return hb
    return 0


def paged_attention_supported(*, num_heads: int, head_dim: int,
                              page_size: int, pages_per_req: int,
                              dtype: Any = jnp.float32) -> bool:
    """True when the in-kernel page walk applies to this engine geometry.

    Consulted ONCE per engine (``serving/decode.py:make_step_fns``) —
    shapes it rejects take the dense gather path, today's behavior, never
    silence. ``num_heads`` is what ONE device holds (the kernel runs per
    shard). Bounds are alignment (sublane-friendly ``head_dim``, a head
    block the dtype's tile can address) and the VMEM tile budget. The
    shipped geometry — 16 and 8 heads × 64, page 16 — compiles and decodes
    right on the v5e in bf16 and f32 (PERF.md).
    """
    if num_heads < 1 or pages_per_req < 1 or page_size < 1:
        return False
    if head_dim < 8 or head_dim % 8 or head_dim > 256:
        return False
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16)):
        return False
    hb = pick_head_block(num_heads, dtype)
    if hb == 0:
        return False
    esize = jnp.dtype(dtype).itemsize
    # double-buffered K+V page tiles + f32 acc/m/l scratch
    tile = 2 * 2 * page_size * hb * head_dim * esize
    scratch = hb * head_dim * 4 + 2 * hb * 128 * 4  # (hb, 1) pads to lanes
    return tile + scratch <= _PAGED_VMEM_BUDGET_BYTES


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


def _decode_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref,
                   acc_ref, m_out_ref, l_out_ref, m_ref, l_ref, *,
                   page_size: int, scale: float):
    """One (request, head-block, page) step of the online-softmax walk.

    ``tables_ref``/``lens_ref`` are the scalar-prefetch operands (SMEM);
    a table entry < 0 marks an invalid page — null, beyond the request's
    lazy allocation, or owned by another shard — and skips the step
    entirely (the page's DMA still lands, on local page 0, but its
    contribution is never folded in). ``acc_ref`` is the f32 output block
    itself: its index map is invariant over the page dim, so it stays
    VMEM-resident across the walk and accumulates in place.
    """
    b = pl.program_id(0)
    p = pl.program_id(2)
    np_ = pl.num_programs(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[0] = jnp.zeros_like(acc_ref[0])
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    page = tables_ref[b, p]
    q_pos = lens_ref[b]
    base = p * page_size
    run = (page >= 0) & (q_pos >= 0) & (base <= q_pos)

    @pl.when(run)
    def _compute():
        # One query row per head: a matrix-vector product, so the VPU does
        # it in the pool's own layout. The tile keeps pages leading and
        # (heads, head_dim) on (sublanes, lanes) throughout — scores reduce
        # over lanes with the dim kept, the page reduction adds whole
        # vregs — where an MXU ``dot_general`` would want the head batch
        # dim leading on K/V, which the page-major pool does not give.
        q = q_ref[0].astype(jnp.float32)                  # [hb, hd]
        k = k_ref[0].astype(jnp.float32)                  # [ps, hb, hd]
        s = (k * q[None]).sum(axis=-1, keepdims=True) * scale  # [ps, hb, 1]
        pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = jnp.where(pos <= q_pos, s, _NEG_INF)
        m_prev = m_ref[...]                                # [hb, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=0))
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.exp(s - m_new[None])                   # [ps, hb, 1]
        l_ref[...] = l_ref[...] * alpha + pexp.sum(axis=0)
        m_ref[...] = m_new
        v = v_ref[0].astype(jnp.float32)                  # [ps, hb, hd]
        acc_ref[0] = acc_ref[0] * alpha + (pexp * v).sum(axis=0)

    @pl.when(p == np_ - 1)
    def _finish():
        # m/l laid out [B, nh, 1]: a (hb, 1) store satisfies Mosaic's
        # last-two-dims tiling where a 2D (1, hb) block does not — the
        # flash kernel's lse idiom.
        m_out_ref[0] = m_ref[...]
        l_out_ref[0] = l_ref[...]


def _paged_call(q: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
                tables: jax.Array, lens: jax.Array):
    """Raw kernel invocation on one device's shard.

    ``q`` ``[B, nh, hd]``, pools ``[pages, page_size, nh, hd]``,
    ``tables`` ``[B, pages_per_req]`` int32 with ``-1`` marking invalid
    entries, ``lens`` ``[B]`` int32 absolute query positions (< 0 =
    inactive row). Returns the UNnormalized ``(acc [B,nh,hd] f32,
    m [B,nh], l [B,nh])`` triple so sharded callers can run the
    cross-shard softmax combine before dividing.
    """
    B, nh, hd = q.shape
    ps = pool_k.shape[1]
    pages_per_req = tables.shape[1]
    hb = pick_head_block(nh, pool_k.dtype)
    scale = 1.0 / math.sqrt(hd)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nh // hb, pages_per_req),
        in_specs=[
            pl.BlockSpec((1, hb, hd), lambda b, h, p, t, l: (b, h, 0)),
            pl.BlockSpec(
                (1, ps, hb, hd),
                lambda b, h, p, t, l: (jnp.maximum(t[b, p], 0), 0, h, 0)),
            pl.BlockSpec(
                (1, ps, hb, hd),
                lambda b, h, p, t, l: (jnp.maximum(t[b, p], 0), 0, h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, hd), lambda b, h, p, t, l: (b, h, 0)),
            pl.BlockSpec((1, hb, 1), lambda b, h, p, t, l: (b, h, 0)),
            pl.BlockSpec((1, hb, 1), lambda b, h, p, t, l: (b, h, 0)),
        ],
        scratch_shapes=[
            _VMEM((hb, 1), jnp.float32),
            _VMEM((hb, 1), jnp.float32),
        ],
    )
    acc, m, l = pl.pallas_call(
        functools.partial(_decode_kernel, page_size=ps, scale=scale),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, nh, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, nh, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, nh, 1), jnp.float32),
        ],
        interpret=ops.interpret(),
        name="paged_decode",
    )(tables, lens, q, pool_k, pool_v)
    return acc, m[..., 0], l[..., 0]


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
                    block_tables: jax.Array, lens: jax.Array) -> jax.Array:
    """Single-shard paged decode attention.

    Semantics match ``serving/decode.py``'s gather path for active rows:
    softmax over key positions ``≤ lens`` with ``1/sqrt(head_dim)``
    scaling, f32 accumulation, output cast back to ``q.dtype``. Inactive
    rows (``lens < 0``) return exact zeros (the gather path returns
    finite null-page garbage there; both are discarded by the host).
    """
    tables = _localize_tables(block_tables, 0, pool_k.shape[0])
    acc, _, l = _paged_call(q, pool_k, pool_v, tables, lens)
    return _normalize(acc, l, q.dtype)


def paged_attention_sharded(q: jax.Array, pool_k: jax.Array,
                            pool_v: jax.Array, block_tables: jax.Array,
                            lens: jax.Array, *,
                            mesh: Optional[Any] = None) -> jax.Array:
    """Mesh-aware paged attention: pool pages stay sharded over ``fsdp``
    and heads over ``tensor`` (the ``serving_kv`` placement from
    ``parallel/rules.py``) while each device walks only its own page
    slice; partial (acc, m, l) triples are merged with the standard
    flash-decoding combine (global running max over ``fsdp``, rescaled
    numerator/denominator psum). Callers must have gated on
    :func:`paged_sharded_supported`; with no mesh (or one device) this
    is the single-shard call.
    """
    from jax.sharding import PartitionSpec as _P

    from fleetx_tpu.parallel.rules import kv_pool_spec

    if mesh is None or mesh.size == 1:
        return paged_attention(q, pool_k, pool_v, block_tables, lens)

    # per-layer pool spec = the registry's 5D serving_kv spec minus the
    # scanned layer dim — rules.py stays the one source of placement
    # (PartitionSpec drops trailing Nones, hence the re-pad to 4 dims)
    entries = (tuple(kv_pool_spec())[1:] + (None, None, None, None))[:4]
    pages_ax, _, heads_ax, _ = entries
    pool_spec = _P(pages_ax, None, heads_ax, None)
    q_spec = _P(None, heads_ax, None)
    local_pages = pool_k.shape[0] // mesh.shape[pages_ax]

    def body(q, pk, pv, tabs, lens):
        lo = jax.lax.axis_index(pages_ax) * local_pages
        tabs = _localize_tables(tabs, lo, local_pages)
        acc, m, l = _paged_call(q, pk, pv, tabs, lens)
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
        in_specs=(q_spec, pool_spec, pool_spec, _P(None, None), _P(None)),
        out_specs=q_spec, check_vma=False)
    return fn(q, pool_k, pool_v, block_tables, lens)
