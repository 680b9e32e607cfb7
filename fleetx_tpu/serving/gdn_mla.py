"""The serving programs of the hybrid family (``models/gdn_mla``:
gated-delta-rule layers beside latent attention over sparse experts): what
``serving/decode.py`` is to the GPT block and ``serving/swa_moe.py`` to the
windowed one.

Two jitted programs with static shapes, ``prefill`` (one chunk of one
request) and ``decode`` (one token for every slot), built once an engine
and called by the same scheduler as every family's
(``serving/registry.py``).

**Three caches under one engine**, none with a heads axis of keys:

- *the latent pool* ``[latent layers, pages, page_size, lanes]``: what a
  latent-attention layer keeps a token is ``(c_kv, k_r)`` — 512 + 64 values,
  padded to 640 lanes (a TPU buffer pads its minor dimension to 128
  anyway). Paged: addressed through the request's block table, grown and
  freed by the engine's ``PageAllocator`` exactly as GPT's pool is. Page 0
  is the null page.
- *the recurrent state* ``[linear layers, slots, value heads, dk, dv]``
  float32: a linear-attention layer's whole memory of a sequence, constant
  in its length, a decode slot's for as long as the request holds the slot.
  Key-major (``ops/gated_delta.py``).
- *the convolution's tail* ``[linear layers, taps − 1, slots, channels]``:
  the last inputs of the causal convolution over ``[q; k; v]`` (slots
  before channels: whole sublane tiles, where ``taps − 1 = 3`` rows would be
  padded and relaid out by every program).

The state and the tail cannot be dropped page by page and are never
"allocated": a slot's are whatever the last request left there until a
request's FIRST chunk (``start == 0``) reads zeros in their place. So a
reused slot starts from a zero state and a zero tail, and a preempted
request — prefilled again from its first token, like every family's —
rebuilds both whole; the host does nothing for either. A decode step
touches the live rows' states only (a row in prefill keeps its partial
state through the decode steps between its chunks).

All three buffers ride the carry of every layer loop and are donated: each
stays one buffer from a program's input to its output.

**The latent layer has two paths.** Decode absorbs ``W_uk`` into the query
and ``W_uv`` into the output (algebra, not an approximation): 64 queries of
576 against the cached row itself, values its first 512
(``ops/mla_paged_attention.py``; the gathered view where the kernel does not
admit the geometry). Prefill folds the request's pages a block of keys at a
time and up-projects each block's latents to the heads' keys and values
(unabsorbed: at a chunk's 512 queries that is the cheaper form).

**The linear layer**: a chunk runs the chunked rule from the slot's state
(``ops/gated_delta.py:chunk_rule``), a decode step the one-token rule over
all slots, in place (``gdn_decode``). Tokens past a ragged chunk's end
carry ``g = 0, β = 0`` and change nothing.

**Parameters**: bfloat16, but every norm's weight, the router with its
selection bias and the decay's vectors in float32;
``programs.serving_params`` makes that tree once (``Family.serving_params``,
``serving/registry.py``) and the programs refuse any other.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from fleetx_tpu.models.gdn_mla import model as M
from fleetx_tpu.models.gdn_mla.config import LATENT, LINEAR, GDNMLAConfig
from fleetx_tpu.models.mla_moe import moe as held_share
from fleetx_tpu.models.swa_moe import model as shared
from fleetx_tpu.observability.trace import device_scope
from fleetx_tpu.ops import gated_delta as GD
from fleetx_tpu.ops import mla_paged_attention as LA
from fleetx_tpu.serving import programs
from fleetx_tpu.serving.programs import SamplingParams

_NEG = -1e30


# -------------------------------------------------------------------- caches
def cache_shapes(cfg: GDNMLAConfig, *, num_pages: int, page_size: int,
                 max_batch: int) -> tuple:
    """``(latent pool, state, tail)`` shapes. A kind of layer the config
    lacks keeps one (unused) layer, so every program has three buffers."""
    lin, lat = max(cfg.layers_of(LINEAR), 1), max(cfg.layers_of(LATENT), 1)
    return ((lat, int(num_pages), int(page_size),
             LA.lanes_of(cfg.latent_width)),
            (lin, int(max_batch), cfg.linear_num_value_heads,
             cfg.linear_key_head_dim, cfg.linear_value_head_dim),
            (lin, cfg.linear_conv_kernel_dim - 1, int(max_batch),
             cfg.conv_channels))


def init_cache(cfg: GDNMLAConfig, **geometry) -> tuple:
    """``(latent pool in cfg.dtype, state float32, tail in cfg.dtype)``,
    zeros. ``num_pages`` INCLUDES the null page: the usable capacity is
    ``(num_pages − 1) · page_size`` token slots a latent layer — what
    admission, growth and preemption count."""
    pool, state, tail = cache_shapes(cfg, **geometry)
    return (jnp.zeros(pool, cfg.dtype), jnp.zeros(state, jnp.float32),
            jnp.zeros(tail, cfg.dtype))


def describe(cfg: GDNMLAConfig, serving: Any, cache: list) -> str:
    """The caches of one engine, in words (its start-up line)."""
    return "%d latent layers paged (%d lanes a token), %d linear layers a " \
        "state and a convolution tail a slot" % (
            cfg.layers_of(LATENT), cache[0].shape[3], cfg.layers_of(LINEAR))


def kernel_refusal(cfg: GDNMLAConfig, *, page_size: int) -> str:
    """Why the latent decode kernel does not admit this geometry, or ""."""
    return LA.refusal(num_heads=cfg.num_attention_heads,
                      lanes=LA.lanes_of(cfg.latent_width),
                      value_width=cfg.kv_lora_rank, page_size=page_size,
                      dtype=cfg.dtype)


# ----------------------------------------------------------------- attention
def _prefill_latent_attention(q_n, q_r, pool, layer, table, q_pos, n_keys,
                              key_block: int, lp: dict, cfg: GDNMLAConfig):
    """One chunk's queries ``q_n`` [C, H, dn] / ``q_r`` [C, H, dr] against
    the latents the pages ``table`` [1, P] name in layer ``layer`` of the
    pool, ``key_block`` keys at a time up to key ``n_keys``: each block's
    latents are up-projected to the heads' keys and values (UNABSORBED),
    online softmax in float32. Returns ``[C, H, dv]``."""
    C, H, _ = q_n.shape
    ps, r, dr = pool.shape[2], cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dv, dt = cfg.v_head_dim, q_n.dtype
    per = key_block // ps
    cols = -(-table.shape[1] // per) * per
    row = jnp.pad(table[0], (0, cols - table.shape[1]))     # null pages
    scale = M.softmax_scale(cfg)
    qp = q_pos[None, :, None]

    def body(j, state):
        m, l, acc = state
        pages = jax.lax.dynamic_slice(row, (j * per,), (per,))
        blk = pool[layer, pages].reshape(key_block, -1)
        ckv, kr = blk[:, :r], blk[:, r:r + dr]
        kn = jnp.einsum("tr,rnd->tnd", ckv, lp["k_b"])
        v = jnp.einsum("tr,rnd->tnd", ckv, lp["v_b"])
        s = (jnp.einsum("snd,tnd->nst", q_n, kn,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("snd,td->nst", q_r, kr,
                          preferred_element_type=jnp.float32)) * scale
        kp = (j * key_block + jnp.arange(key_block, dtype=jnp.int32)
              )[None, None, :]
        s = jnp.where(kp <= qp, s, _NEG)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        acc = acc * alpha[..., None] + jnp.einsum(
            "nst,tnd->nsd", p.astype(dt), v,
            preferred_element_type=jnp.float32)
        return m_new, l * alpha + p.sum(-1), acc

    m, l, acc = jax.lax.fori_loop(
        0, (n_keys + key_block - 1) // key_block, body,
        (jnp.full((H, C), _NEG, jnp.float32), jnp.zeros((H, C), jnp.float32),
         jnp.zeros((H, C, dv), jnp.float32)))
    o = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
    return jnp.transpose(o, (1, 0, 2)).astype(dt)


# ------------------------------------------------------------------- forward
def _forward(params: Any, cfg: GDNMLAConfig, tokens, positions, cache,
             block_tables, slot, start, n_valid, *, decode: bool,
             kernels: bool, latent_kernel: bool, moe_kernel: str):
    """``tokens`` [rows] at absolute ``positions`` [rows] (< 0: no token)
    through every layer in the published order. Decode: a row a slot, one
    token each. Prefill: the rows are one chunk of the request in slot
    ``slot``, ``n_valid`` of them real, from position ``start``. ``cache``
    is ``(latent pool, state, tail)``; ``block_tables`` [B, pages_per_req]
    the rows' pages in the pool. Returns ``(hidden [rows, h], cache,
    stats)`` — the stats are ``programs.walk_runs``'s."""
    programs.refuse_unserved(params, cfg, M.served_dtype)
    (rows,) = tokens.shape
    dt = cfg.dtype
    pool = cache[0]
    ps = pool.shape[2]
    lanes, width = pool.shape[3], cfg.latent_width
    taps = cfg.linear_conv_kernel_dim
    moe_pass_rows = shared.pass_rows(cfg, rows)
    key_block = -(-rows // ps) * ps
    combine = M.glu(cfg)

    with device_scope("embed"):
        x = params["embed"]["tokens"][jnp.maximum(tokens, 0)]
    valid, q_pos, offs, pages = programs.row_targets(positions, block_tables,
                                                     ps)
    first = None if decode else start == 0      # the request's first chunk

    def linear_mixer(u, lp, cache, at):
        pool, state, tail = cache
        with device_scope("gdn.proj"):
            qkv, z, g, beta = M.linear_project(u, lp, cfg)
        with device_scope("gdn.conv"):
            if decode:
                old = tail[at]                              # [K-1, B, C]
                ext = jnp.concatenate([old, qkv[None]], axis=0)
                y = M.conv_taps(ext, lp["conv"][:, None, :], axis=0)
                tail = tail.at[at].set(jnp.where(
                    valid[None, :, None], ext[1:], old))
            else:
                old = jnp.where(first, jnp.zeros_like(tail[at, :, slot]),
                                tail[at, :, slot])          # [K-1, C]
                ext = jnp.concatenate([old, qkv], axis=0)
                y = M.conv_taps(jnp.stack(
                    [ext[j:j + rows] for j in range(taps)], axis=1),
                    lp["conv"])
                # the last inputs of the chunk's REAL tokens
                tail = tail.at[at, :, slot].set(jax.lax.dynamic_slice(
                    ext, (n_valid, 0), (taps - 1, ext.shape[1])))
        with device_scope("gdn.core"):
            q, k, v = M.split_qkv(y, cfg)
            if decode:
                o, state = GD.gdn_decode(state, at, q, k, v, jnp.exp(g),
                                         beta, valid, kernel=kernels)
            else:
                s_in = jnp.where(first, jnp.zeros_like(state[at, slot]),
                                 state[at, slot])
                o, s_out = GD.chunk_rule(
                    q, k, v, jnp.where(valid[:, None], g, 0.0),
                    jnp.where(valid[:, None], beta, 0.0), s_in,
                    kernel=kernels)
                state = state.at[at, slot].set(s_out)
        with device_scope("gdn.proj"):
            return M.linear_output(o, z, lp, cfg, dt), (pool, state, tail)

    def latent_mixer(u, lp, cache, at):
        pool, state, tail = cache
        with device_scope("attn.proj"):
            q_n, q_r, row = M.latent_project(u, lp, cfg, q_pos)
            row = jnp.pad(row, ((0, 0), (0, lanes - width)))
        with device_scope("attn.cache"):
            pool = pool.at[at, pages, offs].set(row)
        if decode:
            with device_scope("attn.proj"):     # W_uk into the query
                q = jnp.concatenate([
                    jnp.einsum("bnd,rnd->bnr", q_n, lp["k_b"]), q_r,
                    jnp.zeros(q_r.shape[:2] + (lanes - width,), dt)], axis=-1)
            with device_scope("attn.core"):
                attend = LA.mla_paged_decode if latent_kernel \
                    else LA.gathered_decode
                o = attend(q, pool, block_tables, positions, at,
                           value_width=cfg.kv_lora_rank,
                           scale=M.softmax_scale(cfg)).astype(dt)
            with device_scope("attn.proj"):     # W_uv out of the output
                o = jnp.einsum("bnr,rnd->bnd", o, lp["v_b"])
        else:
            with device_scope("attn.core"):
                o = _prefill_latent_attention(
                    q_n, q_r, pool, at, block_tables, q_pos,
                    start + n_valid, key_block, lp, cfg)
        with device_scope("attn.proj"):
            return M.latent_output(o, u, lp, cfg), (pool, state, tail)

    def layer_of(kind, lo, cache_lo):
        stack = params[kind]
        mixer, mlp = kind.split("_")
        dense = mlp == "dense"
        per_layer = programs.per_layer_leaves(stack)

        def layer(i, carry):
            x, cache, counters = carry
            lp = jax.tree.map(lambda w: w[i], per_layer)
            with device_scope("norm"):
                u = M.norm(x, lp["attn_norm"]["w"], cfg, dt)
            y, cache = (latent_mixer if mixer == LATENT else linear_mixer)(
                u, lp["mixer"], cache, cache_lo + (i - lo))
            with device_scope("norm"):
                x = x + M.norm(y, lp["attn_post_norm"]["w"], cfg, dt)
                u = M.norm(x, lp["mlp_norm"]["w"], cfg, dt)
            if dense:
                with device_scope("mlp"):
                    y = M.gated_mlp(u, lp["mlp"]["gate"], lp["mlp"]["up"],
                                    lp["mlp"]["down"], combine)
            else:
                moe = lp["moe"]
                ids, weights, _ = held_share.route(
                    u, moe["router"], moe["selection_bias"],
                    cfg.num_experts_per_tok, cfg.routed_scaling_factor,
                    cfg.norm_topk_prob)
                with device_scope("moe.route"):
                    ids = jnp.where(valid[:, None], ids, -1)
                y, held_rows, turns = shared.held_experts(
                    u, ids, weights, stack["moe"], i, cfg, moe_pass_rows,
                    moe_kernel, glu=combine)
                with device_scope("mlp"):
                    if cfg.n_shared_experts:
                        y = y + M.gated_mlp(u, moe["shared_gate"],
                                            moe["shared_up"],
                                            moe["shared_down"], combine)
                counters = programs.count_held(counters, held_rows, turns)
            with device_scope("norm"):
                x = x + M.norm(y, lp["mlp_post_norm"]["w"], cfg, dt)
            return x, cache, counters

        return layer

    x, cache, stats = programs.walk_runs(cfg, x, cache, layer_of)
    with device_scope("head"):
        x = M.norm(x, params["final_norm"]["w"], cfg, dt)
    return x, cache, stats


def make_step_fns(cfg: GDNMLAConfig, *, prefill_chunk: int,
                  sampling: SamplingParams, kernels: bool = False,
                  latent_kernel: bool = False) -> dict:
    """The two jitted programs of one engine, ``{"prefill", "decode"}``:
    ``serving/programs.py:step_fns`` around ``_forward`` over ``(latent
    pool, state, tail)``. ``prefill`` takes the slot whose state the request
    owns after the draw count; ``decode`` returns the step's expert counters
    after its logits. ``kernels``: the two Pallas kernels of the rule (else
    their XLA paths); ``latent_kernel``: the latent decode kernel (else the
    gathered view)."""
    def prefill(params, cache, tokens, positions, block_table, start,
                n_valid, slot):
        return _forward(
            params, cfg, tokens[0], positions, cache, block_table, slot,
            start, n_valid, decode=False, kernels=kernels,
            latent_kernel=False, moe_kernel="moe_gmm_prefill")

    def decode(params, cache, tokens, positions, block_tables, lens):
        return _forward(
            params, cfg, tokens, positions, cache, block_tables, None, None,
            None, decode=True, kernels=kernels, latent_kernel=latent_kernel,
            moe_kernel="moe_gmm_decode")

    return programs.step_fns(prefill, decode, programs.untied_logits, caches=3,
                             prefill_chunk=prefill_chunk, sampling=sampling)
