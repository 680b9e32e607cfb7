"""Pallas paged decode over a pool of LATENTS: many query heads against one
key-value head whose value is the leading part of its key.

Latent attention (DeepSeek-V3's) caches, a token, the normed latent
``c_kv`` and the one rotary key ``k_r`` all heads share: no heads axis. In
the absorbed form a decode step never builds a head's keys or values: head
*h*'s query is taken into the latent's space (``q_n,h W_uk,h``, beside its
rotary part), scored against the cached row itself, and the probabilities
weigh the row's first ``value_width`` values (``c_kv``), which the caller
takes out through ``W_uv,h``. So the kernel is multi-query attention with
key width ``lanes`` and value = ``key[:value_width]``: the pool is read
ONCE for both products.

Pool ``[layers, pages, page_size, lanes]``; ``lanes`` is the latent's
width padded to whole 128-lane tiles (512 + 64 -> 640: a TPU buffer pads
its minor dimension to 128 anyway; the padding lanes are zero in pool and
query). The walk is ``ops/paged_attention.py``'s: one grid step a row, a
loop as long as the row's own context, ``pages`` pages a fold copied page
by page through the row's block table into one of two VMEM slots, the next
fold's copies started before this one's are waited for, online softmax in
float32. The block tables stay in HBM (a row's table — 2,592 entries at
the recipe's ``max_seq_len`` — is copied to SMEM when its step starts: 96
of them do not fit SMEM as scalar prefetch). Trace name
``mla_paged_decode``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fleetx_tpu import ops

_NEG_INF = -1e30
#: what one fold moves: 16 pages of [16, 640] bfloat16 (PERF.md section 6,
#: PR 30: a fold's fixed cost is the core's own, so the pages follow the
#: bytes)
_FOLD_BYTES = 320 * 1024


def lanes_of(width: int) -> int:
    """The pool's minor dimension for a latent ``width`` values wide."""
    return -(-int(width) // 128) * 128


def fold_pages(page_size: int, lanes: int, pages_per_req: int,
               dtype=jnp.bfloat16) -> int:
    """Pages one fold takes: the power of two that moves `_FOLD_BYTES`, no
    more than a request has."""
    page_bytes = page_size * lanes * jnp.dtype(dtype).itemsize
    most = max(_FOLD_BYTES // page_bytes, 1)
    g = 1 << (most.bit_length() - 1)
    while g > max(pages_per_req, 1):
        g //= 2
    return g


def refusal(*, num_heads: int, lanes: int, value_width: int, page_size: int,
            dtype=jnp.bfloat16) -> str:
    """The bound that keeps the kernel from this geometry, in words, or ""
    when it applies (on the CPU, interpreted, any geometry runs)."""
    sublanes = 8 * 4 // jnp.dtype(dtype).itemsize
    if lanes % 128 or value_width % 128 or value_width > lanes:
        return f"latent lanes {lanes} / value width {value_width} are not " \
               f"whole 128-lane tiles"
    if page_size % sublanes:
        return f"a page of {page_size} rows is not whole {sublanes}-row tiles"
    if num_heads % 8:
        return f"{num_heads} query heads are not whole sublane tiles"
    return ""


def _kernel(lens_ref, layer_ref, q_ref, tables_hbm, pool_hbm, o_ref,
            table, tile, sems, table_sem, acc_ref, m_ref, l_ref, *,
            pages: int, page_size: int, value_width: int, scale: float):
    """One row: ``q_ref`` [1, H, lanes], ``tables_hbm`` [B, 1, P] and
    ``pool_hbm`` the whole pool (both left where they are), ``o_ref`` [1, H,
    value_width] float32, normalised. ``table`` (SMEM) the row's own block
    table, ``tile`` [2, pages · page_size, lanes] the two slots."""
    b = pl.program_id(0)
    q_pos = lens_ref[b]
    layer = layer_ref[0]
    span = pages * page_size
    n_folds = jnp.where(q_pos < 0, 0, q_pos // span + 1)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def copies(grp, slot):
        # a page past the row's allocation is the null page: read, masked
        return [pltpu.make_async_copy(
            pool_hbm.at[layer, jnp.maximum(table[0, grp * pages + j], 0)],
            tile.at[slot, pl.ds(j * page_size, page_size)], sems.at[slot])
            for j in range(pages)]

    @pl.when(n_folds > 0)
    def _walk():
        row = pltpu.make_async_copy(tables_hbm.at[b], table, table_sem)
        row.start()
        row.wait()
        for c in copies(0, 0):
            c.start()

        def fold(i, _):
            slot = i % 2

            @pl.when(i + 1 < n_folds)
            def _next():
                for c in copies(i + 1, 1 - slot):
                    c.start()

            for c in copies(0, slot):       # a wait reads sizes
                c.wait()
            k = tile[slot]                                  # [span, lanes]
            s = jax.lax.dot_general(
                q_ref[0], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(i * span + col <= q_pos, s, _NEG_INF)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
            m_ref[...] = m_new
            # the value is the key's leading part: the tile as it landed
            pv = jax.lax.dot_general(
                p.astype(k.dtype), k[:, :value_width],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            acc_ref[...] = acc_ref[...] * alpha + pv

        jax.lax.fori_loop(0, n_folds, fold, None)

    l = l_ref[...]
    o_ref[0] = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)


def mla_paged_decode(q: jax.Array, pool: jax.Array, block_tables: jax.Array,
                     lens: jax.Array, layer: jax.Array, *, value_width: int,
                     scale: float) -> jax.Array:
    """``q`` [B, H, lanes] (absorbed queries, the pool's dtype and lanes),
    ``pool`` [layers, pages, page_size, lanes], ``block_tables`` [B, P],
    ``lens`` [B] the query positions (< 0: an inactive row, which gets
    zeros), ``layer`` which layer of the pool -> float32 ``[B, H,
    value_width]``: softmax over the keys at positions ``≤ lens`` of ``q ·
    key · scale``, times the keys' first ``value_width`` values."""
    B, H, lanes = q.shape
    ps = pool.shape[2]
    g = fold_pages(ps, lanes, block_tables.shape[1], pool.dtype)
    # whole folds, and whole 128-word tiles of the row's copy to SMEM; the
    # padding columns are null pages past every query
    cols = -(-block_tables.shape[1] // max(g, 128)) * max(g, 128)
    tables = jnp.pad(block_tables.astype(jnp.int32),
                     ((0, 0), (0, cols - block_tables.shape[1])))
    return pl.pallas_call(
        functools.partial(_kernel, pages=g, page_size=ps,
                          value_width=value_width, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, H, lanes), lambda b, l, lay: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, H, value_width),
                                   lambda b, l, lay: (b, 0, 0)),
            scratch_shapes=[
                pltpu.SMEM((1, cols), jnp.int32),
                pltpu.VMEM((2, g * ps, lanes), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA(()),
                pltpu.VMEM((H, value_width), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, value_width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=ops.interpret(),
        name="mla_paged_decode",
    )(lens.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
      q.astype(pool.dtype), tables[:, None, :], pool)


def gathered_decode(q: jax.Array, pool: jax.Array, block_tables: jax.Array,
                    lens: jax.Array, layer: jax.Array, *, value_width: int,
                    scale: float) -> jax.Array:
    """The same answer from a gathered view of each row's pages (XLA): what
    runs where the kernel does not admit the geometry."""
    B = q.shape[0]
    k = pool[layer, block_tables].reshape(B, -1, pool.shape[-1])
    s = jnp.einsum("bhw,btw->bht", q.astype(pool.dtype), k,
                   preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(k.shape[1], dtype=jnp.int32)[None, None, :]
    s = jnp.where(pos <= lens[:, None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bht,btw->bhw", p.astype(pool.dtype),
                   k[..., :value_width], preferred_element_type=jnp.float32)
    return jnp.where((lens >= 0)[:, None, None], o, 0.0)
