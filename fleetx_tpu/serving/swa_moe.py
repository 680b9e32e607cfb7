"""The serving programs of the windowed-attention sparse-expert family
(``models/swa_moe``): what ``serving/decode.py`` is to the GPT block.

Two jitted programs with static shapes, ``prefill`` (one chunk of one
request) and ``decode`` (one token for every slot), built once an engine
and called by the same scheduler as GPT's (``serving/registry.py``).

**Two caches under one engine.** Full-attention layers keep every token: a
paged pool ``[full layers, pages, page_size, kv_heads · head_dim]``, K and
V, addressed through the request's block table, grown and freed by the
engine's ``PageAllocator`` exactly as GPT's pool is. Window layers never
need more than the window: a RING per decode slot, ``[window layers, 1 +
slots · ring_pages, page_size, kv_heads · head_dim]`` with ``ring_pages =
ceil((window + prefill_chunk) / page_size)``; the token at position *p*
lives in the slot's ring at ``p mod ring_tokens``, so a slot's ring bytes
do not depend on ``max_seq_len``, nothing is allocated or released as a
request grows, and the host does no work for it. Page 0 of either buffer is
the null page (masked rows and the tail of a ragged chunk write there). A
chunk's keys are written before its queries read, and the ``prefill_chunk``
tokens they overwrite are older than the window of every query in the
chunk: that is what the extra chunk of ring is for. A preempted request is
prefilled again from its first token, which rebuilds its ring.

All four buffers ride the carry of every layer loop and are donated: each
stays one buffer from a program's input to its output (``decode.py`` has
the reasons; ``tests/test_tpu_lowering.py`` pins it for these programs).

**Layers in the published order.** The layers of one shape are stacked;
the order (``SWAMoEConfig.runs``) is walked run by run, each run a loop
over its slice of its stack, the stack indexed inside the loop as a layer
scan indexes its operand. The experts' stacks are never indexed by layer at
all: ``moe_gmm`` reads tile *t*'s matrix at ``layer · held + expert(t)`` of
the stack seen flat.

**Attention.** Decode: ``ops/paged_attention.py`` with fewer key-value
heads than query heads (a whole number of query heads to each: 6 or 9 over
8 in one member, 7 over 4 in the other) — the full layers over the
request's pages, the window layers over the slot's ring with ``window=``
set, so a row's walk starts at the group of pages that holds ``len − window
+ 1``. The kernel is told what the ring is (``ring_pages=``, the first page
of each row's ring in the block table's place): logical page *j* is ring
page ``j mod ring_pages``, an aligned group of pages is one run of the
buffer, and a fold fetches it in one copy a cache buffer.
Where the kernel does not admit the geometry (toy widths), the gathered
view, and the engine's build says so once (``gather_fallbacks``). Prefill:
one fold for both caches (``_prefill_blocked_attention``: a block of keys as
long as the chunk at a time, online softmax) — a full layer over the
request's pages up to the chunk's end (a loop as long as the context); a
window layer over its slot's ring as a block table, from the block that
holds the first query's oldest key (a loop as long as window + chunk: two
blocks at a 512-token window under 512-token chunks, nine or ten at 4,096).

**The router's input.** With ``router_input: pre_attention`` the experts'
ids and weights are computed from the attention norm's output and carried
over the attention call to the experts, which act on the normed state
after attention; otherwise both read that state. A layer type whose
``rope_parameters`` group is None is not rotated.

**Parameters**: bfloat16, but the norms' scales and the router in
float32; ``serving_params`` makes that tree once and the programs refuse
any other.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from fleetx_tpu.models.swa_moe import model as M
from fleetx_tpu.models.swa_moe.config import FULL, WINDOW, SWAMoEConfig
from fleetx_tpu.observability.trace import device_scope
from fleetx_tpu.ops import paged_attention as PA
from fleetx_tpu.serving.decode import (SamplingParams, _sample,
                                       merge_fresh)

_NEG = -1e30


# -------------------------------------------------------------------- caches
def ring_pages(cfg: SWAMoEConfig, page_size: int, prefill_chunk: int) -> int:
    """Pages of one slot's ring: the window plus one prefill chunk."""
    return -(-(cfg.sliding_window + int(prefill_chunk)) // int(page_size))


def cache_shapes(cfg: SWAMoEConfig, *, num_pages: int, page_size: int,
                 max_batch: int, prefill_chunk: int) -> tuple:
    """``(full pool shape, ring shape)``; each exists twice, K and V."""
    width = cfg.num_key_value_heads * cfg.head_dim
    rp = ring_pages(cfg, page_size, prefill_chunk)
    return ((cfg.layers_of("full"), int(num_pages), int(page_size), width),
            (cfg.layers_of("window"), 1 + int(max_batch) * rp,
             int(page_size), width))


def init_cache(cfg: SWAMoEConfig, *, num_pages: int, page_size: int,
               max_batch: int, prefill_chunk: int) -> tuple:
    """``(full_k, full_v, ring_k, ring_v)``, zeros in ``cfg.dtype``.

    Full layers: ``num_pages`` INCLUDES the null page, so the usable
    capacity is ``(num_pages - 1) * page_size`` token slots a full layer —
    what admission, growth and preemption count. Window layers: ``slots ·
    ring_pages · page_size`` token slots a window layer whatever
    ``max_seq_len`` is (+ the null page)."""
    full, ring = cache_shapes(cfg, num_pages=num_pages, page_size=page_size,
                              max_batch=max_batch,
                              prefill_chunk=prefill_chunk)
    z = lambda shape: jnp.zeros(shape, cfg.dtype)  # noqa: E731
    return z(full), z(full), z(ring), z(ring)


def gather_fallbacks(cfg: SWAMoEConfig, *, page_size: int,
                     pages_per_req: int) -> list:
    """The layer kinds whose decode attention ``ops/paged_attention.py``
    does not admit at this geometry, each with the bound that refused it:
    ``[(kind, reason), ...]``, empty when the kernel serves every layer.
    One refused kind puts the whole decode program on the gathered view
    (one attention path a program), so the engine logs these when it is
    built."""
    out = []
    for kind in cfg.kinds():
        why = PA.paged_attention_refusal(
            num_heads=cfg.heads_of(kind), head_dim=cfg.head_dim,
            page_size=page_size, pages_per_req=pages_per_req,
            dtype=cfg.dtype, num_kv_heads=cfg.num_key_value_heads)
        if why:
            out.append((kind, why))
    return out


def paged_kernel_enabled(cfg: SWAMoEConfig, *, page_size: int,
                         pages_per_req: int) -> bool:
    """Whether ``ops/paged_attention.py`` admits every layer's geometry."""
    return not gather_fallbacks(cfg, page_size=page_size,
                                pages_per_req=pages_per_req)


# ---------------------------------------------------------------- parameters
def _unserved(params: Any, cfg: Any, served_dtype=M.served_dtype) -> list:
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    return [i for i, (path, leaf) in enumerate(flat)
            if leaf.dtype != served_dtype(path, cfg)]


def serving_params(params: Any, cfg: Any, served_dtype=M.served_dtype) -> Any:
    """The tree both programs take: every leaf in ``cfg.dtype`` but the
    norms' scales and the router (float32) — ``served_dtype(path, cfg)``
    says which (another family passes its own). One jitted cast of the
    leaves that need it; a leaf already served comes back as the object it
    was."""
    todo = _unserved(params, cfg, served_dtype)
    if not todo:
        return params
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    want = [served_dtype(flat[i][0], cfg) for i in todo]
    cast = jax.jit(lambda xs: [x.astype(d) for x, d in zip(xs, want)])(
        [flat[i][1] for i in todo])
    leaves = [leaf for _, leaf in flat]
    for i, leaf in zip(todo, cast):
        leaves[i] = leaf
    return treedef.unflatten(leaves)


# ----------------------------------------------------------------- attention
def _gathered_attention(q, k, v, key_pos, q_pos, window, dtype):
    """``q`` [B, S, H, hd] against gathered keys ``k``/``v`` [B, K, kv, hd]
    that hold the tokens at absolute positions ``key_pos`` [B, K] (< 0: no
    token): softmax over the keys at ``q_pos − window < p ≤ q_pos``."""
    B, S, H, hd = q.shape
    kv = k.shape[2]
    qg = q.reshape(B, S, kv, H // kv, hd)
    s = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    kp, qp = key_pos[:, None, :], q_pos[:, :, None]
    seen = (kp >= 0) & (kp <= qp)
    if window is not None:
        seen = seen & (kp > qp - window)
    s = jnp.where(seen[:, None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1).astype(dtype)
    o = jnp.einsum("bkgst,btkd->bskgd", p, v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, S, H, hd).astype(dtype)


def _prefill_blocked_attention(q, pool_k, pool_v, layer, table, q_pos,
                               n_keys, key_block: int, dtype, window=None):
    """One chunk's queries ``q`` [1, C, H, hd] against the pages ``table``
    [1, P] names in layer ``layer`` of a cache (a request's pages in the
    full pool, or a slot's ring as a table: logical page *j* → ring page
    ``j mod ring_pages``), ``key_block`` keys at a time, up to key
    ``n_keys`` (online softmax in float32). Without a ``window`` the loop
    is as long as the context; with one it starts at the block that holds
    the first query's oldest key, ``q_pos[0, 0] − window + 1``, so it is as
    long as window + chunk whatever the context, and the keys of that
    block the ring has since overwritten lie before every query's window
    and are masked."""
    _, C, H, hd = q.shape
    ps, width = pool_k.shape[2], pool_k.shape[3]
    kv = width // hd
    per = key_block // ps
    cols = -(-table.shape[1] // per) * per
    row = jnp.pad(table[0], (0, cols - table.shape[1]))    # null pages
    qg = q[0].reshape(C, kv, H // kv, hd)
    qp = q_pos[0][None, None, :, None]

    def body(j, state):
        m, l, acc = state
        pages = jax.lax.dynamic_slice(row, (j * per,), (per,))
        k = pool_k[layer, pages].reshape(key_block, kv, hd)
        v = pool_v[layer, pages].reshape(key_block, kv, hd)
        s = jnp.einsum("ckgd,tkd->kgct", qg, k,
                       preferred_element_type=jnp.float32) / math.sqrt(hd)
        kp = (j * key_block + jnp.arange(key_block, dtype=jnp.int32)
              )[None, None, None, :]
        seen = kp <= qp
        if window is not None:
            seen = seen & (kp > qp - window)
        s = jnp.where(seen, s, _NEG)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        acc = acc * alpha[..., None] + jnp.einsum(
            "kgct,tkd->kgcd", p.astype(dtype), v,
            preferred_element_type=jnp.float32)
        return m_new, l * alpha + p.sum(-1), acc

    shape = (kv, H // kv, C)
    first = 0 if window is None else \
        jnp.maximum(q_pos[0, 0] - (window - 1), 0) // key_block
    m, l, acc = jax.lax.fori_loop(
        first, (n_keys + key_block - 1) // key_block, body,
        (jnp.full(shape, _NEG, jnp.float32), jnp.zeros(shape, jnp.float32),
         jnp.zeros(shape + (hd,), jnp.float32)))
    o = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
    return jnp.transpose(o, (2, 0, 1, 3)).reshape(1, C, H, hd).astype(dtype)


# ------------------------------------------------------------------- forward
def _forward(params: Any, cfg: SWAMoEConfig, tokens, positions, cache,
             block_tables, slots, last, *, rp: int, decode: bool,
             paged_kernel: bool, moe_kernel: str):
    """``tokens`` [B, S] at absolute ``positions`` [B, S] (< 0: no token)
    through every layer in the published order. ``cache`` is ``(full_k,
    full_v, ring_k, ring_v)``; ``block_tables`` [B, pages_per_req] the
    rows' pages in the full pool; ``slots`` [B] whose ring each row is;
    ``last`` [B] the last position each row holds after this call; ``rp``
    the pages of one slot's ring (window + chunk). Returns
    ``(hidden [B, S, h], cache, stats)``; ``stats``: held experts hit,
    summed over the expert layers, (token, expert) pairs on held experts,
    the rows of the fullest held expert over the mean (worst layer) and the
    passes the held experts' loops took (all layers)."""
    unserved = _unserved(params, cfg)
    if unserved:
        raise TypeError(
            "the serving programs take the tree serving_params() makes: "
            f"{len(unserved)} leaves are not in their served dtype")
    B, S = tokens.shape
    dt = cfg.dtype
    hd, kv = cfg.head_dim, cfg.num_key_value_heads
    full_k = cache[0]
    ps, P = full_k.shape[2], block_tables.shape[1]
    window = cfg.sliding_window
    moe_pass_rows = M.pass_rows(cfg, B * S)
    # keys a block of the prefill's attention scores at once: as many as
    # the chunk has queries, in whole pages
    key_block = -(-S // ps) * ps

    with device_scope("embed"):
        x = params["embed"]["tokens"][jnp.maximum(tokens, 0)]
    with device_scope("attn.cache"):    # where the rows go, for every layer
        valid = positions >= 0
        q_pos = jnp.maximum(positions, 0)
        offs = jnp.clip(positions % ps, 0, ps - 1)
        # where each (row, slot) is written: the request's page, the slot's
        # ring
        page_slot = jnp.clip(positions // ps, 0, P - 1)
        full_pages = jnp.where(
            valid, jnp.take_along_axis(block_tables, page_slot, axis=1), 0)
        ring_first = 1 + slots * rp                               # [B]
        ring_at = jnp.where(
            valid, ring_first[:, None] + (q_pos // ps) % rp, 0)
        valid_tok = valid.reshape(B * S)
    with device_scope("attn.proj"):
        tables = {t: M.rotary_tables(cfg, t, q_pos) for t in (FULL, WINDOW)}
    act = M.activation(cfg)
    router_first = cfg.router_input == "pre_attention"
    with device_scope("attn.cache"):    # how a window layer reads its ring
        if not decode:
            # folded as a block table: logical page j -> ring page j mod rp
            ring_table = ring_first[:, None] + \
                jnp.arange(P, dtype=jnp.int32)[None, :] % rp
        elif not paged_kernel:
            # the gathered view: the ring's ``rp`` logical pages that end at
            # the page of ``last``, in order, and the position of each key
            # in them
            view_first = (jnp.maximum(last, 0) // ps - (rp - 1))[:, None] \
                + jnp.arange(rp, dtype=jnp.int32)[None, :]        # [B, rp]
            view_pages = ring_first[:, None] + view_first % rp
            view_pos = (view_first[:, :, None] * ps + jnp.arange(
                ps, dtype=jnp.int32)[None, None, :]).reshape(B, rp * ps)

    def attention(kind_type, u, lp, cache, at):
        with device_scope("attn.proj"):
            q = jnp.einsum("bsh,ndh->bsnd", u, lp["q"])
            k = jnp.einsum("bsh,ndh->bsnd", u, lp["k"])
            v = jnp.einsum("bsh,hn->bsn", u, lp["v"])
            if tables[kind_type] is not None:   # else: no position signal
                cos, sin = tables[kind_type]
                q, k = M.apply_rotary(q, cos, sin), \
                    M.apply_rotary(k, cos, sin)
            k_rows = k.reshape(B, S, kv * hd)
        full_k, full_v, ring_k, ring_v = cache
        with device_scope("attn.cache"):
            if kind_type == FULL:
                full_k = full_k.at[at, full_pages, offs].set(k_rows)
                full_v = full_v.at[at, full_pages, offs].set(v)
            else:
                ring_k = ring_k.at[at, ring_at, offs].set(k_rows)
                ring_v = ring_v.at[at, ring_at, offs].set(v)
        with device_scope("attn.core"):
            o = scores(kind_type, q, full_k, full_v, ring_k, ring_v, at)
        with device_scope("attn.proj"):
            if cfg.gating == "per-head":
                gate = jax.nn.sigmoid(jnp.einsum(
                    "bsh,hn->bsn", u, lp["gate"],
                    preferred_element_type=jnp.float32))
                o = (o.astype(jnp.float32) * gate[..., None]).astype(dt)
            y = jnp.einsum("bsnd,ndh->bsh", o, lp["out"])
        return y, (full_k, full_v, ring_k, ring_v)

    def scores(kind_type, q, full_k, full_v, ring_k, ring_v, at):
        """The layer's attention over the cache it has just written."""
        if kind_type == FULL and decode and paged_kernel:
            return PA.paged_attention(q[:, 0], full_k, full_v, block_tables,
                                      positions[:, 0], at)[:, None]
        if kind_type == FULL and decode:
            kd = full_k[at, block_tables].reshape(B, -1, kv, hd)
            vd = full_v[at, block_tables].reshape(B, -1, kv, hd)
            kp = jnp.broadcast_to(jnp.arange(P * ps, dtype=jnp.int32),
                                  (B, P * ps))
            return _gathered_attention(q, kd, vd, kp, q_pos, None, dt)
        if kind_type == FULL:
            return _prefill_blocked_attention(
                q, full_k, full_v, at, block_tables, q_pos, last[0] + 1,
                key_block, dt)
        if decode and paged_kernel:
            # the kernel is told what this is: a ring of ``rp`` pages from
            # ``ring_first``, so a fold is one run of the buffer
            return PA.paged_attention(q[:, 0], ring_k, ring_v, ring_first,
                                      positions[:, 0], at, window=window,
                                      ring_pages=rp)[:, None]
        if not decode:
            return _prefill_blocked_attention(
                q, ring_k, ring_v, at, ring_table, q_pos, last[0] + 1,
                key_block, dt, window=window)
        kd = ring_k[at, view_pages].reshape(B, -1, kv, hd)
        vd = ring_v[at, view_pages].reshape(B, -1, kv, hd)
        return _gathered_attention(q, kd, vd, view_pos, q_pos, window, dt)

    def run(kind, lo, n, cache_lo, carry):
        stack = params[kind]
        kind_type = WINDOW if kind.startswith("window") else FULL
        dense = kind.endswith("dense")
        per_layer = {k: v for k, v in stack.items() if k != "moe"}
        if not dense:
            per_layer["moe"] = {k: v for k, v in stack["moe"].items()
                                if not k.startswith("experts_")}

        def layer(i, carry):
            x, cache, hit, pairs, load, passes = carry
            lp = jax.tree.map(lambda w: w[i], per_layer)
            with device_scope("norm"):
                u = M.rms_norm(x, lp["attn_norm"]["scale"], cfg.rms_norm_eps,
                               dt)
            if router_first and not dense:
                # chosen from the layer's normed input, applied after
                # attention to the normed state the experts act on
                routing = M.route(u.reshape(B * S, -1),
                                  lp["moe"]["router"], cfg)
            y, cache = attention(kind_type, u, lp["attn"], cache,
                                 cache_lo + (i - lo))
            with device_scope("norm"):
                x = x + y
                u = M.rms_norm(x, lp["mlp_norm"]["scale"], cfg.rms_norm_eps,
                               dt)
            if dense:
                with device_scope("mlp"):
                    y = M.gated_mlp(u, lp["mlp"]["gate"], lp["mlp"]["up"],
                                    lp["mlp"]["down"], act).astype(dt)
            else:
                u2d = u.reshape(B * S, -1)
                ids, weights = routing if router_first else \
                    M.route(u2d, lp["moe"]["router"], cfg)
                with device_scope("moe.route"):
                    ids = jnp.where(valid_tok[:, None], ids, -1)
                y, rows, turns = M.held_experts(
                    u2d, ids, weights, stack["moe"], i, cfg, moe_pass_rows,
                    moe_kernel)
                with device_scope("mlp"):
                    if cfg.shared_expert_intermediate_size:
                        y = y + M.gated_mlp(u2d, lp["moe"]["shared_gate"],
                                            lp["moe"]["shared_up"],
                                            lp["moe"]["shared_down"], act)
                    y = y.astype(dt).reshape(B, S, -1)
                with device_scope("moe.route"):     # the step's counters
                    hit = hit + (rows > 0).sum().astype(jnp.float32)
                    pairs = pairs + rows.sum().astype(jnp.int32)
                    held = rows.astype(jnp.float32)
                    load = jnp.maximum(
                        load, held.max() / jnp.maximum(held.mean(), 1e-9))
                    passes = passes + turns.astype(jnp.int32)
            with device_scope("mlp"):
                return x + y, cache, hit, pairs, load, passes

        with device_scope("stack"):
            if n == 1:  # a static index: the layer is a view of its stack
                return layer(lo, carry)
            return jax.lax.fori_loop(lo, lo + n, layer, carry)

    carry = (x, tuple(cache), jnp.float32(0.0), jnp.int32(0),
             jnp.float32(0.0), jnp.int32(0))
    for kind, lo, n, cache_lo in cfg.runs():
        carry = run(kind, lo, n, cache_lo, carry)
    x, cache, hit, pairs, load, passes = carry
    with device_scope("head"):
        x = M.rms_norm(x, params["final_norm"]["scale"], cfg.rms_norm_eps,
                       dt)
    return x, cache, {"hit": hit, "pairs_held": pairs,
                      "load_max_over_mean": load, "passes": passes}


@device_scope("head")
def _logits(params: Any, x_last: jax.Array) -> jax.Array:
    """The (untied) head on the selected positions -> float32 ``[B, V]``."""
    return jnp.einsum("bh,hv->bv", x_last, params["head"]["kernel"],
                      preferred_element_type=jnp.float32)


def make_step_fns(cfg: SWAMoEConfig, *, prefill_chunk: int, page_size: int,
                  sampling: SamplingParams,
                  paged_kernel: bool = False) -> dict:
    """The two jitted programs of one engine, ``{"prefill", "decode"}``.

    Both take ``(params, full_k, full_v, ring_k, ring_v, ...)``, donate the
    four cache buffers and return them first. ``prefill`` then takes what
    GPT's takes and the slot whose ring the request owns; ``decode`` what
    GPT's takes (the last tokens as the device holds them, the one fresh
    row, tables, lengths, the base key and the draw count). After the
    caches come the sampled token(s), the float32
    logits and, from ``decode``, the step's expert counters (held experts
    hit, summed over the expert layers; pairs on held experts; the fullest
    held expert's rows over the mean, worst layer; the passes the held
    experts' loops took, all layers): they ride to the host with the
    tokens. Shapes are static (``max_batch`` /
    ``pages_per_req`` / ``prefill_chunk`` arrive with the arrays), so each
    jit cache holds one entry for the engine's lifetime."""
    rp = ring_pages(cfg, page_size, prefill_chunk)

    def prefill(params, full_k, full_v, ring_k, ring_v, tokens, block_table,
                start, n_valid, rng, draw, slot):
        """One prompt chunk of the request in slot ``slot``: ``tokens``
        ``[1, C]`` with ``n_valid`` real entries from position ``start``."""
        idx = jnp.arange(prefill_chunk, dtype=jnp.int32)[None, :]
        positions = jnp.where(idx < n_valid, start + idx, -1)
        last = jnp.reshape(start + n_valid - 1, (1,)).astype(jnp.int32)
        x, cache, _ = _forward(
            params, cfg, tokens, positions, (full_k, full_v, ring_k, ring_v),
            block_table, jnp.reshape(slot, (1,)).astype(jnp.int32), last,
            rp=rp, decode=False, paged_kernel=False,
            moe_kernel="moe_gmm_prefill")
        with device_scope("head"):
            at = jnp.clip(n_valid - 1, 0, prefill_chunk - 1)
            x_last = jax.lax.dynamic_index_in_dim(x[0], at, axis=0,
                                                  keepdims=False)[None]
        logits = _logits(params, x_last)
        return (*cache, _sample(logits, rng, draw, sampling), logits)

    def decode(params, full_k, full_v, ring_k, ring_v, tokens, fresh_slot,
               fresh_tok, block_tables, lens, rng, draw):
        """One token for every slot: ``tokens`` / ``lens`` ``[max_batch]``
        (an empty slot carries ``lens < 0`` and a null-page table);
        ``tokens`` is the previous call's sampled tokens, ``merge_fresh``
        puts the one request that left prefill this tick in its slot."""
        tokens = merge_fresh(tokens, fresh_slot, fresh_tok)
        positions = jnp.where(lens >= 0, lens, -1)[:, None]
        slots = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        x, cache, stats = _forward(
            params, cfg, tokens[:, None], positions,
            (full_k, full_v, ring_k, ring_v), block_tables, slots,
            jnp.maximum(lens, -1).astype(jnp.int32), rp=rp, decode=True,
            paged_kernel=paged_kernel, moe_kernel="moe_gmm_decode")
        logits = _logits(params, x[:, 0])
        with device_scope("moe.route"):     # rides with the counters
            stats["rows"] = (lens >= 0).sum().astype(jnp.int32)
        return (*cache, _sample(logits, rng, draw, sampling), logits,
                stats)

    donate = (1, 2, 3, 4)
    return {"prefill": jax.jit(prefill, donate_argnums=donate),
            "decode": jax.jit(decode, donate_argnums=donate)}
