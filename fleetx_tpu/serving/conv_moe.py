"""The serving programs of the short-convolution family
(``models/conv_moe``: gated convolution layers beside grouped-query
attention over sparse experts): what ``serving/decode.py`` is to the GPT
block, ``serving/swa_moe.py`` to the windowed family and
``serving/gdn_mla.py`` to the linear-attention one.

Two jitted programs with static shapes, ``prefill`` (one chunk of one
request) and ``decode`` (one token for every slot), built once an engine
and called by the same scheduler as every family's
(``serving/registry.py``).

**Two caches under one engine.**

- *the key-value pool* ``[attention layers, pages, page_size, kv_heads ·
  head_dim]``, K and V: the attention layers ONLY — three layers of four
  keep no keys. Paged: addressed through the request's block table, grown
  and freed by the engine's ``PageAllocator`` exactly as GPT's pool is. Page
  0 is the null page. Keys are stored normed and rotated.
- *the convolution's tail* ``[conv layers, taps − 1, slots, hidden]``: the
  last ``taps − 1`` values of ``z = B ⊙ x`` a slot, a convolution layer's
  whole memory of a sequence whatever its length (slots before channels:
  whole sublane tiles, where ``taps − 1 = 2`` rows would be padded and
  relaid out by every program).

The tail cannot be dropped page by page and is never "allocated": a slot's
is whatever the last request left there until a request's FIRST chunk
(``start == 0``) reads zeros in its place. So a reused slot starts from a
zero tail, a chunk carries it to the next (a ragged chunk writes the last
two REAL tokens' ``z``; tokens past its end change nothing), and a preempted
request — prefilled again from its first token, like every family's —
rebuilds it whole; the host does nothing for it. A decode step shifts the
tails of the live rows only (a row in prefill keeps its tail through the
decode steps between its chunks) — the contract ``serving/gdn_mla.py``
states for its tail.

All three buffers ride the carry of every layer loop and are donated: each
stays one buffer from a program's input to its output.

**Attention.** A chunk writes K and V to the slot's pages and folds the
request's pages a key block at a time up to the chunk's end
(``serving/programs.py:prefill_blocked_attention``); decode calls
``ops/paged_attention.py`` with 4 query heads to each key-value head of 64 —
half a lane tile — or, where the kernel does not admit the geometry, reads
the gathered view (the engine's build says so once).

**Parameters**: bfloat16, but every norm's weight, the router and its
selection bias in float32; ``programs.serving_params`` makes that tree once
(``Family.serving_params``, ``serving/registry.py``) and the programs refuse
any other.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from fleetx_tpu.models.conv_moe import model as M
from fleetx_tpu.models.conv_moe.config import CONV, FULL, ConvMoEConfig
from fleetx_tpu.models.swa_moe import model as shared
from fleetx_tpu.observability.trace import device_scope
from fleetx_tpu.ops import paged_attention as PA
from fleetx_tpu.serving import programs
from fleetx_tpu.serving.programs import SamplingParams


# -------------------------------------------------------------------- caches
def cache_shapes(cfg: ConvMoEConfig, *, num_pages: int, page_size: int,
                 max_batch: int) -> tuple:
    """``(pool, tail)`` shapes; the pool exists twice, K and V. A kind of
    layer the config lacks keeps one (unused) layer, so every program has
    three buffers."""
    return ((max(cfg.layers_of(FULL), 1), int(num_pages), int(page_size),
             cfg.num_key_value_heads * cfg.head_dim),
            (max(cfg.layers_of(CONV), 1), cfg.conv_L_cache - 1,
             int(max_batch), cfg.hidden_size))


def init_cache(cfg: ConvMoEConfig, **geometry) -> tuple:
    """``(pool_k, pool_v, tail)``, zeros in ``cfg.dtype``. ``num_pages``
    INCLUDES the null page: the usable capacity is ``(num_pages − 1) ·
    page_size`` token slots an attention layer — what admission, growth
    and preemption count."""
    pool, tail = cache_shapes(cfg, **geometry)
    return (jnp.zeros(pool, cfg.dtype), jnp.zeros(pool, cfg.dtype),
            jnp.zeros(tail, cfg.dtype))


def describe(cfg: ConvMoEConfig, serving: Any, cache: list) -> str:
    """The caches of one engine, in words (its start-up line)."""
    return "%d attention layers paged (%d lanes a token), %d convolution " \
        "layers a tail of %d rows a slot" % (
            cfg.layers_of(FULL), cache[0].shape[3], cfg.layers_of(CONV),
            cache[2].shape[1])


def kernel_geometry(cfg: ConvMoEConfig, *, page_size: int,
                    pages_per_req: int) -> dict:
    """What ``ops/paged_attention.py`` is asked about the attention layers."""
    return dict(num_heads=cfg.num_attention_heads, head_dim=cfg.head_dim,
                page_size=page_size, pages_per_req=pages_per_req,
                dtype=cfg.dtype, num_kv_heads=cfg.num_key_value_heads)


def kernel_refusal(cfg: ConvMoEConfig, **geometry) -> str:
    """Why ``ops/paged_attention.py`` does not admit the attention layers'
    decode at this geometry (``page_size``, ``pages_per_req``), or ""."""
    return PA.paged_attention_refusal(**kernel_geometry(cfg, **geometry))


# ------------------------------------------------------------------- forward
def _forward(params: Any, cfg: ConvMoEConfig, tokens, positions, cache,
             block_tables, slot, start, n_valid, *, decode: bool,
             paged_kernel: bool, moe_kernel: str):
    """``tokens`` [rows] at absolute ``positions`` [rows] (< 0: no token)
    through every layer in the published order. Decode: a row a slot, one
    token each. Prefill: the rows are one chunk of the request in slot
    ``slot``, ``n_valid`` of them real, from position ``start``. ``cache``
    is ``(pool_k, pool_v, tail)``; ``block_tables`` [B, pages_per_req] the
    rows' pages in the pool. Returns ``(hidden [rows, h], cache, stats)`` —
    the stats are ``programs.walk_runs``'s."""
    programs.refuse_unserved(params, cfg, M.served_dtype)
    (rows,) = tokens.shape
    dt = cfg.dtype
    hd, kv = cfg.head_dim, cfg.num_key_value_heads
    ps, P = cache[0].shape[2], block_tables.shape[1]
    taps = cfg.conv_L_cache
    moe_pass_rows = shared.pass_rows(cfg, rows)
    key_block = -(-rows // ps) * ps

    with device_scope("embed"):
        x = params["embed"]["tokens"][jnp.maximum(tokens, 0)]
    valid, q_pos, offs, pages = programs.row_targets(positions, block_tables,
                                                     ps)
    with device_scope("attn.proj"):
        cos, sin = M.rotary_tables(cfg, q_pos)
    first = None if decode else start == 0      # the request's first chunk

    def conv_operator(u, lp, cache, at):
        pool_k, pool_v, tail = cache
        with device_scope("conv.proj"):
            bcx = M.conv_project(u, lp)
        with device_scope("conv.mix"):
            z, gate = M.conv_gates(bcx, cfg)
            if decode:
                old = tail[at]                              # [K-1, B, h]
                ext = jnp.concatenate([old, z[None]], axis=0)
                c = M.conv_taps(ext, lp["taps"][:, None, :], axis=0)
                tail = tail.at[at].set(jnp.where(
                    valid[None, :, None], ext[1:], old))
            else:
                old = jnp.where(first, jnp.zeros_like(tail[at, :, slot]),
                                tail[at, :, slot])          # [K-1, h]
                ext = jnp.concatenate([old, z], axis=0)
                c = M.conv_sequence(ext, lp["taps"])
                # the last values of the chunk's REAL tokens
                tail = tail.at[at, :, slot].set(jax.lax.dynamic_slice(
                    ext, (n_valid, 0), (taps - 1, ext.shape[1])))
            y = (gate * c).astype(dt)
        with device_scope("conv.proj"):
            return jnp.einsum("sc,ch->sh", y, lp["out"]), \
                (pool_k, pool_v, tail)

    def attention_operator(u, lp, cache, at):
        pool_k, pool_v, tail = cache
        with device_scope("attn.proj"):
            q, k, v = M.attention_project(u, lp, cfg, cos, sin)
        with device_scope("attn.cache"):
            pool_k = pool_k.at[at, pages, offs].set(k.reshape(rows, kv * hd))
            pool_v = pool_v.at[at, pages, offs].set(v)
        with device_scope("attn.core"):
            if decode and paged_kernel:
                o = PA.paged_attention(q, pool_k, pool_v, block_tables,
                                       positions, at)
            elif decode:
                kd = pool_k[at, block_tables].reshape(rows, -1, kv, hd)
                vd = pool_v[at, block_tables].reshape(rows, -1, kv, hd)
                kp = jnp.broadcast_to(jnp.arange(P * ps, dtype=jnp.int32),
                                      (rows, P * ps))
                o = programs.gathered_attention(
                    q[:, None], kd, vd, kp, q_pos[:, None], None, dt)[:, 0]
            else:
                o = programs.prefill_blocked_attention(
                    q[None], pool_k, pool_v, at, block_tables, q_pos[None],
                    start + n_valid, key_block, dt)[0]
        with device_scope("attn.proj"):
            return jnp.einsum("snd,ndh->sh", o, lp["out"]), \
                (pool_k, pool_v, tail)

    def layer_of(kind, lo, cache_lo):
        stack = params[kind]
        op, mlp = kind.split("_")
        dense = mlp == "dense"
        per_layer = programs.per_layer_leaves(stack)

        def layer(i, carry):
            x, cache, counters = carry
            lp = jax.tree.map(lambda w: w[i], per_layer)
            with device_scope("norm"):
                u = M.rms_norm(x, lp["operator_norm"]["scale"], cfg.norm_eps,
                               dt)
            if op == CONV:
                y, cache = conv_operator(u, lp["conv"], cache,
                                         cache_lo + (i - lo))
            else:
                y, cache = attention_operator(u, lp["attn"], cache,
                                              cache_lo + (i - lo))
            with device_scope("norm"):
                x = x + y
                f = M.rms_norm(x, lp["ffn_norm"]["scale"], cfg.norm_eps, dt)
            if dense:
                with device_scope("mlp"):
                    y = M.gated_mlp(f, lp["mlp"]["gate"], lp["mlp"]["up"],
                                    lp["mlp"]["down"])
            else:
                ids, weights = M.route(f, lp["moe"], cfg)
                with device_scope("moe.route"):
                    ids = jnp.where(valid[:, None], ids, -1)
                y, held_rows, turns = shared.held_experts(
                    f, ids, weights, stack["moe"], i, cfg, moe_pass_rows,
                    moe_kernel)
                counters = programs.count_held(counters, held_rows, turns)
            with device_scope("mlp"):
                return x + y.astype(dt), cache, counters

        return layer

    x, cache, stats = programs.walk_runs(cfg, x, cache, layer_of)
    with device_scope("head"):
        x = M.rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps, dt)
    return x, cache, stats


_logits = device_scope("head")(M.logits)


def make_step_fns(cfg: ConvMoEConfig, *, prefill_chunk: int,
                  sampling: SamplingParams,
                  paged_kernel: bool = False) -> dict:
    """The two jitted programs of one engine, ``{"prefill", "decode"}``:
    ``serving/programs.py:step_fns`` around ``_forward`` over ``(pool_k,
    pool_v, tail)``. ``prefill`` takes the slot whose tail the request owns
    after the draw count; ``decode`` returns the step's expert counters
    after its logits. ``paged_kernel``: the decode kernel (else the gathered
    view)."""
    def prefill(params, cache, tokens, positions, block_table, start,
                n_valid, slot):
        return _forward(
            params, cfg, tokens[0], positions, cache, block_table, slot,
            start, n_valid, decode=False, paged_kernel=False,
            moe_kernel="moe_gmm_prefill")

    def decode(params, cache, tokens, positions, block_tables, lens):
        return _forward(
            params, cfg, tokens, positions, cache, block_tables, None, None,
            None, decode=True, paged_kernel=paged_kernel,
            moe_kernel="moe_gmm_decode")

    return programs.step_fns(prefill, decode, _logits, caches=3,
                             prefill_chunk=prefill_chunk, sampling=sampling)
