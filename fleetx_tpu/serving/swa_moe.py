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
view, and the engine's build says so once (``kernel_refusal``). Prefill:
one fold for both caches (``programs.prefill_blocked_attention``: a block
of keys as long as the chunk at a time, online softmax) — a full layer over
the request's pages up to the chunk's end (a loop as long as the context); a
window layer over its slot's ring as a block table, from the block that
holds the first query's oldest key (a loop as long as window + chunk: two
blocks at a 512-token window under 512-token chunks, nine or ten at 4,096).

**The router's input.** With ``router_input: pre_attention`` the experts'
ids and weights are computed from the attention norm's output and carried
over the attention call to the experts, which act on the normed state
after attention; otherwise both read that state. A layer type whose
``rope_parameters`` group is None is not rotated.

**Parameters**: bfloat16, but the norms' scales and the router in
float32; ``programs.serving_params`` makes that tree once
(``Family.serving_params``, ``serving/registry.py``) and the programs refuse
any other.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from fleetx_tpu.models.swa_moe import model as M
from fleetx_tpu.models.swa_moe.config import FULL, WINDOW, SWAMoEConfig
from fleetx_tpu.observability.trace import device_scope
from fleetx_tpu.ops import paged_attention as PA
from fleetx_tpu.serving import programs
from fleetx_tpu.serving.programs import SamplingParams, ring_pages


# -------------------------------------------------------------------- caches
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


def describe(cfg: SWAMoEConfig, serving: Any, cache: list) -> str:
    """The caches of one engine, in words (its start-up line)."""
    return "%d full layers paged, %d window layers a ring of %d pages a " \
        "slot" % (cfg.layers_of("full"), cfg.layers_of("window"),
                  ring_pages(cfg, serving.page_size, serving.prefill_chunk))


def kernel_refusal(cfg: SWAMoEConfig, *, page_size: int,
                   pages_per_req: int) -> str:
    """Why ``ops/paged_attention.py`` does not admit the decode attention
    of this geometry — every refused layer kind with the bound that refused
    it, ``"<kind> layers: <bound>; …"`` — or "" when the kernel serves all
    layer. One refused kind puts the whole decode program on the gathered
    view (one attention path a program), so the engine's build logs it."""
    why = {kind: PA.paged_attention_refusal(
        num_heads=cfg.heads_of(kind), head_dim=cfg.head_dim,
        page_size=page_size, pages_per_req=pages_per_req, dtype=cfg.dtype,
        num_kv_heads=cfg.num_key_value_heads) for kind in cfg.kinds()}
    return "; ".join(f"{kind} layers: {bound}"
                     for kind, bound in why.items() if bound)


def kernel_walk(cfg: SWAMoEConfig, *, page_size: int, pages_per_req: int,
                prefill_chunk: int) -> tuple:
    """``(walk shape, folds by cache kind)`` of a geometry the kernel
    admits (``ops/paged_attention.py:page_walk_shape`` / ``fold_shape``):
    the full layers' fold takes a copy a page through the block table, the
    window layers' one copy for a ring's run of pages."""
    ring = ring_pages(cfg, page_size, prefill_chunk)
    geometry = dict(num_heads=max(cfg.num_attention_heads_per_layer),
                    head_dim=cfg.head_dim, page_size=page_size,
                    dtype=cfg.dtype, num_kv_heads=cfg.num_key_value_heads)
    paged = dict(geometry, pages_per_req=pages_per_req)
    return PA.page_walk_shape(**paged), {
        "full": PA.fold_shape(**paged),
        "window": PA.fold_shape(pages_per_req=ring, ring_pages=ring,
                                **geometry)}


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
    programs.refuse_unserved(params, cfg, M.served_dtype)
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
        valid_tok = valid.reshape(B * S)
    ring_first, ring_at = programs.ring_targets(positions, slots, rp, ps)
    with device_scope("attn.proj"):
        tables = {t: M.rotary_tables(cfg, t, q_pos) for t in (FULL, WINDOW)}
    act = M.activation(cfg)
    router_first = cfg.router_input == "pre_attention"
    # how a window layer reads its ring: folded as a block table (a
    # chunk), or the gathered view (a decode step without the kernel)
    if not decode:
        ring_table = programs.ring_table(ring_first, P, rp)
    elif not paged_kernel:
        view_pages, view_pos = programs.ring_view(ring_first, last, rp, ps)

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
            return programs.gathered_attention(q, kd, vd, kp, q_pos, None, dt)
        if kind_type == FULL:
            return programs.prefill_blocked_attention(
                q, full_k, full_v, at, block_tables, q_pos, last[0] + 1,
                key_block, dt)
        if decode and paged_kernel:
            # the kernel is told what this is: a ring of ``rp`` pages from
            # ``ring_first``, so a fold is one run of the buffer
            return PA.paged_attention(q[:, 0], ring_k, ring_v, ring_first,
                                      positions[:, 0], at, window=window,
                                      ring_pages=rp)[:, None]
        if not decode:
            return programs.prefill_blocked_attention(
                q, ring_k, ring_v, at, ring_table, q_pos, last[0] + 1,
                key_block, dt, window=window)
        kd = ring_k[at, view_pages].reshape(B, -1, kv, hd)
        vd = ring_v[at, view_pages].reshape(B, -1, kv, hd)
        return programs.gathered_attention(q, kd, vd, view_pos, q_pos, window, dt)

    def layer_of(kind, lo, cache_lo):
        stack = params[kind]
        kind_type = WINDOW if kind.startswith("window") else FULL
        dense = kind.endswith("dense")
        per_layer = programs.per_layer_leaves(stack)

        def layer(i, carry):
            x, cache, counters = carry
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
                counters = programs.count_held(counters, rows, turns)
            with device_scope("mlp"):
                return x + y, cache, counters

        return layer

    x, cache, stats = programs.walk_runs(cfg, x, cache, layer_of)
    with device_scope("head"):
        x = M.rms_norm(x, params["final_norm"]["scale"], cfg.rms_norm_eps,
                       dt)
    return x, cache, stats


def make_step_fns(cfg: SWAMoEConfig, *, prefill_chunk: int, page_size: int,
                  sampling: SamplingParams,
                  paged_kernel: bool = False) -> dict:
    """The two jitted programs of one engine, ``{"prefill", "decode"}``:
    ``serving/programs.py:step_fns`` around ``_forward`` over ``(full_k,
    full_v, ring_k, ring_v)``. ``prefill`` takes the slot whose ring the
    request owns after the draw count; ``decode`` returns the step's expert
    counters after its logits (held experts hit, summed over the expert
    layers; pairs on held experts; the fullest held expert's rows over the
    mean, worst layer; the passes the held experts' loops took, all
    layers). ``paged_kernel``: the decode kernel (else the gathered
    view)."""
    rp = ring_pages(cfg, page_size, prefill_chunk)

    def prefill(params, cache, tokens, positions, block_table, start,
                n_valid, slot):
        last = jnp.reshape(start + n_valid - 1, (1,)).astype(jnp.int32)
        return _forward(
            params, cfg, tokens, positions, cache, block_table,
            jnp.reshape(slot, (1,)).astype(jnp.int32), last, rp=rp,
            decode=False, paged_kernel=False, moe_kernel="moe_gmm_prefill")

    def decode(params, cache, tokens, positions, block_tables, lens):
        slots = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        return _forward(
            params, cfg, tokens[:, None], positions, cache, block_tables,
            slots, jnp.maximum(lens, -1).astype(jnp.int32), rp=rp,
            decode=True, paged_kernel=paged_kernel,
            moe_kernel="moe_gmm_decode")

    return programs.step_fns(prefill, decode, programs.untied_logits, caches=4,
                             prefill_chunk=prefill_chunk, sampling=sampling,
                             blocks=True)
