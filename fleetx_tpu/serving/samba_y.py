"""The serving programs of the decoder-hybrid-decoder family
(``models/samba_y``: selective-scan layers alternating with differential
attention over a window, ONE full attention layer whose keys and values
every later attention layer reads, gated memory units): what
``serving/decode.py`` is to the GPT block.

Two jitted programs with static shapes, ``prefill`` (one chunk of one
request) and ``decode`` (one token for every slot), built once an engine
and called by the same scheduler as every family's
(``serving/registry.py``).

**Four kinds of cache under one engine**, six buffers, all riding the
carry of every layer loop and all donated — each stays one buffer from a
program's input to its output:

- *the pool* ``[1, pages, page_size, kv_heads · head_dim]``, K and V: ONE
  layer's (the full layer, published ``N/2 + 1``). That layer WRITES it;
  it and the ``N/2 − 2`` cross layers after it READ it — with 32 layers,
  eight walks of the same pages a decode step. Paged: addressed through the
  request's block table, grown and freed by the engine's ``PageAllocator``
  exactly as GPT's pool is. Page 0 is the null page.
- *the rings* ``[window layers, 1 + slots · ring_pages, page_size, kv_heads
  · head_dim]``, K and V: a window layer's last ``sliding_window`` + one
  chunk of tokens a slot (``serving/programs.py`` has the ring's helpers);
  bytes that do not depend on ``max_seq_len``, nothing allocated.
- *the states* ``[scan layers, slots, d_state, inner]`` float32 and *the
  tails* ``[scan layers, d_conv − 1, slots, inner]``: a scan layer's whole
  memory of a sequence whatever its length.

A slot's ring, state and tail are never "allocated": they are whatever the
last request left there until a request's FIRST chunk (``start == 0``)
reads zeros in place of the state and the tail (a ring's stale keys lie at
positions no query of the new request sees). A chunk carries them to the
next (a ragged chunk's rows past its end carry ``Δ = 0`` and write the
null page; the tail keeps the last REAL tokens' inputs), and a preempted
request — prefilled again from its first token, like every family's —
rebuilds all of them; the host does nothing for them. A decode step moves
the state and the tail of the live rows only.

**The last scan layer's output** ``m`` is an activation, not a cache: a
decode step carries ``[slots, inner]`` from layer ``N/2`` to the last gated
memory unit.

**Prefill runs the upper half on ONE row.** Layers ``0 … N/2 + 1`` take the
chunk (the full layer writes the pool). The gated memory units and the
cross layers above keep no cache of their own, so no earlier token's pass
through them is ever read again: they run on the chunk's last valid row
alone — its hidden state, its ``m``, its queries against the pool — on
every chunk (a request's first token needs it on the last; the 14 layers'
weights read once a chunk, PERF.md section 6 has the cost), and the
program returns that row (``programs.step_fns(last_row=True)``).

**Attention.** The two score maps of a head pair reach every path as
2 · head_dim-wide heads (``models/samba_y/model.py:diff_queries``: four
zero-half queries to each key-value pair's lanes), scaled by ``1 /
sqrt(head_dim)``, outputs in float32 so that the difference of the two maps
is taken before anything is rounded. Decode: ``ops/paged_attention.py``
(``scale=``) over the pool through the block tables, over the rings with
``window=`` / ``ring_pages=``; where the kernel does not admit the geometry
(toy widths) the gathered view, and the engine's build says so once.
Prefill: ``programs.prefill_blocked_attention`` over the request's pages or
the slot's ring as a table; the one row above reads the pool as a decode
row does.

**Parameters**: bfloat16, but every norm's weight and bias, the scan's own
vectors and the λ vectors in float32; ``programs.serving_params`` makes that
tree once and the programs refuse any other.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from fleetx_tpu.models.samba_y import model as M
from fleetx_tpu.models.samba_y.config import (CROSS, FULL, GMU, SCAN, WINDOW,
                                              SambaYConfig)
from fleetx_tpu.observability.trace import device_scope
from fleetx_tpu.ops import paged_attention as PA
from fleetx_tpu.ops import selective_scan as SS
from fleetx_tpu.serving import programs
from fleetx_tpu.serving.programs import SamplingParams, ring_pages

CACHES = 6      # pool K, V; ring K, V; states; tails


# -------------------------------------------------------------------- caches
def cache_shapes(cfg: SambaYConfig, *, num_pages: int, page_size: int,
                 max_batch: int, prefill_chunk: int) -> tuple:
    """``(pool, ring, state, tail)`` shapes; pool and ring exist twice, K
    and V."""
    rp = ring_pages(cfg, page_size, prefill_chunk)
    scans = cfg.layers_of(SCAN)
    return ((1, int(num_pages), int(page_size), cfg.kv_lanes),
            (cfg.layers_of(WINDOW), 1 + int(max_batch) * rp, int(page_size),
             cfg.kv_lanes),
            (scans, int(max_batch), cfg.d_state, cfg.d_inner),
            (scans, cfg.d_conv - 1, int(max_batch), cfg.d_inner))


def init_cache(cfg: SambaYConfig, **geometry) -> tuple:
    """``(pool_k, pool_v, ring_k, ring_v, state, tail)``, zeros; the state
    float32, the rest ``cfg.dtype``. ``num_pages`` INCLUDES the null page:
    the usable capacity is ``(num_pages − 1) · page_size`` token slots of
    the one paged layer — what admission, growth and preemption count."""
    pool, ring, state, tail = cache_shapes(cfg, **geometry)
    z = lambda shape: jnp.zeros(shape, cfg.dtype)  # noqa: E731
    return (z(pool), z(pool), z(ring), z(ring),
            jnp.zeros(state, jnp.float32), z(tail))


def describe(cfg: SambaYConfig, serving: Any, cache: list) -> str:
    """The caches of one engine, in words (its start-up line)."""
    return "1 layer paged (%d lanes a token) that %d layers read, %d window " \
        "layers a ring of %d pages a slot, %d scan layers a state of %d x " \
        "%d and a tail of %d rows a slot" % (
            cache[0].shape[3], 1 + cfg.layers_of(CROSS),
            cfg.layers_of(WINDOW),
            ring_pages(cfg, serving.page_size, serving.prefill_chunk),
            cfg.layers_of(SCAN), cfg.d_state, cfg.d_inner,
            cache[5].shape[1])


def kernel_geometry(cfg: SambaYConfig, *, page_size: int,
                    pages_per_req: int) -> dict:
    """What ``ops/paged_attention.py`` is asked: every query head against
    key-value PAIRS of 2 · head_dim lanes."""
    return dict(num_heads=cfg.num_attention_heads, head_dim=2 * cfg.head_dim,
                page_size=page_size, pages_per_req=pages_per_req,
                dtype=cfg.dtype, num_kv_heads=cfg.num_key_value_heads // 2)


def kernel_refusal(cfg: SambaYConfig, *, page_size: int, pages_per_req: int,
                   prefill_chunk: int) -> str:
    """Why the kernels do not admit this geometry — ``"attention: <bound>;
    scan: <bound>"`` — or "" when they serve every layer. One refusal puts
    both programs on their plain paths (one path a program)."""
    why = {"attention": PA.paged_attention_refusal(**kernel_geometry(
        cfg, page_size=page_size, pages_per_req=pages_per_req)),
           "scan": SS.scan_refusal(channels=cfg.d_inner, states=cfg.d_state,
                                   chunk=prefill_chunk)}
    return "; ".join(f"{what}: {bound}" for what, bound in why.items()
                     if bound)


def kernel_walk(cfg: SambaYConfig, *, page_size: int, pages_per_req: int,
                prefill_chunk: int) -> tuple:
    """``(walk shape, folds by cache kind)`` of a geometry the kernel
    admits: the pool's fold takes a copy a page through the block table, a
    ring's one copy for its run of pages."""
    paged = kernel_geometry(cfg, page_size=page_size,
                            pages_per_req=pages_per_req)
    ring = ring_pages(cfg, page_size, prefill_chunk)
    return PA.page_walk_shape(**paged), {
        "full": PA.fold_shape(**paged),
        "window": PA.fold_shape(**dict(paged, pages_per_req=ring),
                                ring_pages=ring)}


# ------------------------------------------------------------------- forward
def _forward(params: Any, cfg: SambaYConfig, tokens, positions, cache,
             block_tables, slot, start, n_valid, *, rp: int, decode: bool,
             kernels: bool):
    """``tokens`` [rows] at absolute ``positions`` [rows] (< 0: no token)
    through every layer in the published order. Decode: a row a slot, one
    token each; returns ``hidden [rows, h]``. Prefill: the rows are one
    chunk of the request in slot ``slot``, ``n_valid`` of them real, from
    position ``start``; the layers above the full layer run on the last
    valid row alone and that row ``[1, h]`` comes back. ``cache`` is
    ``(pool_k, pool_v, ring_k, ring_v, state, tail)``; ``block_tables`` [B,
    pages_per_req] the rows' pages in the pool; ``rp`` the pages of one
    slot's ring; ``kernels``: the Pallas kernels (else the plain paths).
    Returns ``(hidden, cache, {})``."""
    programs.refuse_unserved(params, cfg, M.served_dtype)
    (rows,) = tokens.shape
    dt, eps, hd = cfg.dtype, cfg.layer_norm_eps, cfg.head_dim
    pairs, wide = cfg.num_key_value_heads // 2, 2 * cfg.head_dim
    ps, P = cache[0].shape[2], block_tables.shape[1]
    window = cfg.sliding_window
    scale = 1.0 / math.sqrt(hd)
    # keys a block of the prefill's attention scores at once: as many as
    # the chunk has queries, in whole pages
    key_block = -(-rows // ps) * ps

    with device_scope("embed"):
        x = params["embed"]["tokens"][jnp.maximum(tokens, 0)]
    valid, q_pos, offs, pages = programs.row_targets(positions, block_tables,
                                                     ps)
    if decode:
        slots = jnp.arange(rows, dtype=jnp.int32)
        ring_first, ring_at = programs.ring_targets(positions[:, None],
                                                    slots, rp, ps)
        ring_at = ring_at[:, 0]
        if not kernels:
            view_pages, view_pos = programs.ring_view(ring_first, positions,
                                                      rp, ps)
    else:
        ring_first, ring_at = programs.ring_targets(
            positions[None], jnp.reshape(slot, (1,)).astype(jnp.int32), rp,
            ps)
        ring_at = ring_at[0]
        ring_tbl = programs.ring_table(ring_first, P, rp)
        first = start == 0                  # the request's first chunk
        n_keys = start + n_valid

    def at_layer(stack, i):
        return jax.tree.map(lambda w: w[i], stack)

    def close(x, mixed, lp):
        """The mixer's residual add, then the layer's MLP."""
        with device_scope("norm"):
            x = x + mixed.astype(dt)
            f = M.layer_norm(x, lp["norm2"], eps, dt)
        with device_scope("mlp"):
            return x + M.gated_mlp(f, lp["mlp"])

    # ---------------------------------------------------------- scan layers
    def scan_layer(i, x, cache):
        """Layer ``i`` of the scan stack -> ``(x, cache, y)``; ``y`` the
        scan's output with the skip, float32."""
        pool_k, pool_v, ring_k, ring_v, state, tail = cache
        lp = at_layer(params[SCAN], i)
        sp = lp["ssm"]
        with device_scope("norm"):
            u = M.layer_norm(x, lp["norm1"], eps, dt)
        mixed, y, state, tail = programs.scan_mixer(
            u, sp, cfg, state, tail, i, decode=decode, kernels=kernels,
            valid=valid, slot=slot, first=None if decode else first,
            n_valid=n_valid)
        return close(x, mixed, lp), \
            (pool_k, pool_v, ring_k, ring_v, state, tail), y

    # ----------------------------------------------------- attention layers
    def walk(q, buf_k, buf_v, layer, one_row=False):
        """``q`` [rows, heads, 2 hd] against layer ``layer`` of the POOL,
        as a decode row reads it (``one_row``: the chunk's last valid row,
        its request's pages) -> float32."""
        lens = jnp.reshape(start + n_valid - 1, (1,)).astype(jnp.int32) \
            if one_row else positions
        if kernels:
            return PA.paged_attention(q.astype(jnp.float32), buf_k, buf_v,
                                      block_tables, lens, layer, scale=scale)
        if one_row:
            return programs.prefill_blocked_attention(
                q[None], buf_k, buf_v, layer, block_tables, lens[None], n_keys,
                key_block, dt, scale=scale, out_dtype=jnp.float32)[0]
        kd = buf_k[layer, block_tables].reshape(rows, -1, pairs, wide)
        vd = buf_v[layer, block_tables].reshape(rows, -1, pairs, wide)
        kp = jnp.broadcast_to(jnp.arange(P * ps, dtype=jnp.int32),
                              (rows, P * ps))
        return programs.gathered_attention(
            q[:, None], kd, vd, kp, q_pos[:, None], None, dt, scale=scale,
            out_dtype=jnp.float32)[:, 0]

    def read_ring(q, ring_k, ring_v, i):
        """``q`` against window layer ``i``'s ring: a chunk's fold, a decode
        row's walk or its gathered view -> float32."""
        if not decode:
            return programs.prefill_blocked_attention(
                q[None], ring_k, ring_v, i, ring_tbl, q_pos[None], n_keys,
                key_block, dt, window=window, scale=scale,
                out_dtype=jnp.float32)[0]
        if kernels:
            return PA.paged_attention(
                q.astype(jnp.float32), ring_k, ring_v, ring_first, positions,
                i, window=window, ring_pages=rp, scale=scale)
        kd = ring_k[i, view_pages].reshape(rows, -1, pairs, wide)
        vd = ring_v[i, view_pages].reshape(rows, -1, pairs, wide)
        return programs.gathered_attention(
            q[:, None], kd, vd, view_pos, q_pos[:, None], window, dt,
            scale=scale, out_dtype=jnp.float32)[:, 0]

    def attention_layer(kind, i, x, cache):
        """Layer ``i`` of the ``window`` or ``full`` stack: keys and values
        written to its cache, then read."""
        pool_k, pool_v, ring_k, ring_v, state, tail = cache
        lp = at_layer(params[kind], i)
        ap = lp["attn"]
        with device_scope("norm"):
            u = M.layer_norm(x, lp["norm1"], eps, dt)
        with device_scope("attn.proj"):
            q, k, v = M.attention_project(u, ap, cfg)
            q = M.diff_queries(q)
        with device_scope("attn.cache"):
            if kind == FULL:
                pool_k = pool_k.at[0, pages, offs].set(k)
                pool_v = pool_v.at[0, pages, offs].set(v)
            else:
                ring_k = ring_k.at[i, ring_at, offs].set(k)
                ring_v = ring_v.at[i, ring_at, offs].set(v)
        if kind == FULL and decode:     # the pool as the cross layers read it
            with device_scope("attn.cross"):
                o = walk(q, pool_k, pool_v, 0)
        else:
            with device_scope("attn.core"):
                o = programs.prefill_blocked_attention(
                    q[None], pool_k, pool_v, 0, block_tables, q_pos[None],
                    n_keys, key_block, dt, scale=scale,
                    out_dtype=jnp.float32)[0] if kind == FULL \
                    else read_ring(q, ring_k, ring_v, i)
        with device_scope("attn.proj"):
            lam0 = M.lambda_inits(cfg, kind)[i]
            mixed = M.attention_out(
                M.diff_combine(o, ap, lam0, eps, dt), ap)
        return close(x, mixed, lp), \
            (pool_k, pool_v, ring_k, ring_v, state, tail)

    def cross_layer(i, x, pool_k, pool_v, one_row):
        """Layer ``i`` of the cross stack: its own queries, the pool's K
        and V; it writes nothing."""
        lp = at_layer(params[CROSS], i)
        ap = lp["attn"]
        with device_scope("norm"):
            u = M.layer_norm(x, lp["norm1"], eps, dt)
        with device_scope("attn.proj"):
            q = M.diff_queries(M.attention_project(u, ap, cfg)[0])
        with device_scope("attn.cross"):
            o = walk(q, pool_k, pool_v, 0, one_row)
        with device_scope("attn.proj"):
            lam0 = M.lambda_inits(cfg, CROSS)[i]
            mixed = M.attention_out(
                M.diff_combine(o, ap, lam0, eps, dt), ap)
        return close(x, mixed, lp)

    def memory_layer(i, x, m):
        lp = at_layer(params[GMU], i)
        with device_scope("norm"):
            u = M.layer_norm(x, lp["norm1"], eps, dt)
        with device_scope("gmu"):
            mixed = M.memory_unit(u, m, lp["gmu"])
        return close(x, mixed, lp)

    # ------------------------------------------------ the published order
    def lower_pair(i, carry):
        x, cache = carry
        x, cache, _ = scan_layer(i, x, cache)
        return attention_layer(WINDOW, i, x, cache)

    cache = tuple(cache)
    with device_scope("stack"):
        x, cache = jax.lax.fori_loop(0, cfg.layers_of(WINDOW), lower_pair,
                                     (x, cache))
        x, cache, y = scan_layer(cfg.layers_of(SCAN) - 1, x, cache)
        x, cache = attention_layer(FULL, 0, x, cache)
        m = y.astype(dt)
        if not decode:      # the upper half sees the chunk's last valid row
            x = programs.last_valid_row(x, n_valid)
            m = programs.last_valid_row(m, n_valid)
        pool_k, pool_v = cache[:2]

        def upper_pair(i, x):
            x = memory_layer(i, x, m)
            return cross_layer(i, x, pool_k, pool_v, not decode)

        x = jax.lax.fori_loop(0, cfg.layers_of(CROSS), upper_pair, x)
    with device_scope("head"):
        x = M.layer_norm(x, params["final_norm"], eps, dt)
    return x, cache, {}


_logits = device_scope("head")(M.logits)


def make_step_fns(cfg: SambaYConfig, *, prefill_chunk: int, page_size: int,
                  sampling: SamplingParams, kernels: bool = False) -> dict:
    """The two jitted programs of one engine, ``{"prefill", "decode"}``:
    ``serving/programs.py:step_fns`` around ``_forward`` over ``(pool_k,
    pool_v, ring_k, ring_v, state, tail)``. ``prefill`` takes the slot
    whose ring, state and tail the request owns after the draw count, and
    returns the chunk's last valid row's token and logits from ONE row
    through the upper half. ``kernels``: the paged and scan kernels (else
    the gathered view and the ``lax.scan``)."""
    rp = ring_pages(cfg, page_size, prefill_chunk)

    def prefill(params, cache, tokens, positions, block_table, start,
                n_valid, slot):
        return _forward(params, cfg, tokens[0], positions, cache, block_table,
                        slot, start, n_valid, rp=rp, decode=False,
                        kernels=kernels)

    def decode(params, cache, tokens, positions, block_tables, lens):
        return _forward(params, cfg, tokens, positions, cache, block_tables,
                        None, None, None, rp=rp, decode=True,
                        kernels=kernels)

    return programs.step_fns(prefill, decode, _logits, caches=CACHES,
                             prefill_chunk=prefill_chunk, sampling=sampling,
                             last_row=True)
