"""The serving programs of the scan / multi-query family
(``models/ssm_mqa``: selective-scan layers whose step, ``B`` and ``C`` are
normed, beside a few multi-query attention layers): what
``serving/decode.py`` is to the GPT block.

Two jitted programs with static shapes, ``prefill`` (one chunk of one
request) and ``decode`` (one token for every slot), built once an engine
and called by the same scheduler as every family's
(``serving/registry.py``).

**Three kinds of cache under one engine**, four buffers, all riding the
carry of every layer loop and all donated — each stays one buffer from a
program's input to its output:

- *the pool* ``[attention layers, pages, page_size, kv_heads · head_dim]``,
  K and V: the attention layers ONLY. Paged: addressed through the
  request's block table, grown and freed by the engine's ``PageAllocator``
  exactly as GPT's pool is. Page 0 is the null page. With ONE key-value
  head of 128 the pool is one lane tile wide: a page of 16 tokens is 4 KB,
  and the recipe takes pages of 128 tokens so that a page carries what the
  decode kernel's fold was sized on (``docs/ssm_mqa.md`` "The page").
- *the states* ``[scan layers, slots, d_state, inner]`` float32 and *the
  tails* ``[scan layers, d_conv − 1, slots, inner]``: a scan layer's whole
  memory of a sequence whatever its length — at 256 slots the largest
  thing a decode step reads after the weights.

A slot's state and tail are never "allocated": they are whatever the last
request left there until a request's FIRST chunk (``start == 0``) reads
zeros in their place. A chunk carries them to the next (a ragged chunk's
rows past its end carry ``Δ = 0`` and write the null page; the tail keeps
the last REAL tokens' inputs), and a preempted request — prefilled again
from its first token, like every family's — rebuilds both whole; the host
does nothing for them. A decode step moves the state and the tail of the
live rows only. That contract, and the layer itself, are
``serving/programs.py:scan_mixer``'s — one definition, shared with
``serving/samba_y.py``.

**The layers are walked in runs** (``programs.walk_runs`` over
``cfg.runs()``): 26 scan layers in runs of 7, 13 and 6 between the two
attention layers, each run ONE loop over its slice of the scan stack, so a
program holds the scan layer's body three times and not 26.

**Attention.** Every query head against the one key-value head, scaled by
``1 / sqrt(head_dim)``, nothing rotated. Decode: ``ops/paged_attention.py``
(one block of all the query heads) over the pool through the block tables;
where the kernel does not admit the geometry (toy widths) the gathered
view, and the engine's build says so once. Prefill:
``programs.prefill_blocked_attention`` over the request's pages.

**Parameters**: bfloat16, but every norm's weight (the scan's inner three
among them) and the scan's own vectors in float32;
``programs.serving_params`` makes that tree once and the programs refuse
any other.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from fleetx_tpu.models.ssm_mqa import model as M
from fleetx_tpu.models.ssm_mqa.config import FULL, SCAN, SSMMQAConfig
from fleetx_tpu.observability.trace import device_scope
from fleetx_tpu.ops import paged_attention as PA
from fleetx_tpu.ops import selective_scan as SS
from fleetx_tpu.serving import programs
from fleetx_tpu.serving.programs import SamplingParams

CACHES = 4      # pool K, V; states; tails


# -------------------------------------------------------------------- caches
def cache_shapes(cfg: SSMMQAConfig, *, num_pages: int, page_size: int,
                 max_batch: int) -> tuple:
    """``(pool, state, tail)`` shapes; the pool exists twice, K and V."""
    scans = cfg.layers_of(SCAN)
    return ((cfg.layers_of(FULL), int(num_pages), int(page_size),
             cfg.kv_lanes),
            (scans, int(max_batch), cfg.d_state, cfg.d_inner),
            (scans, cfg.d_conv - 1, int(max_batch), cfg.d_inner))


def init_cache(cfg: SSMMQAConfig, **geometry) -> tuple:
    """``(pool_k, pool_v, state, tail)``, zeros; the state float32, the
    rest ``cfg.dtype``. ``num_pages`` INCLUDES the null page: the usable
    capacity is ``(num_pages − 1) · page_size`` token slots an attention
    layer — what admission, growth and preemption count."""
    pool, state, tail = cache_shapes(cfg, **geometry)
    return (jnp.zeros(pool, cfg.dtype), jnp.zeros(pool, cfg.dtype),
            jnp.zeros(state, jnp.float32), jnp.zeros(tail, cfg.dtype))


def describe(cfg: SSMMQAConfig, serving: Any, cache: list) -> str:
    """The caches of one engine, in words (its start-up line)."""
    return "%d attention layers paged (%d lanes a token, pages of %d), %d " \
        "scan layers a state of %d x %d and a tail of %d rows a slot" % (
            cfg.layers_of(FULL), cache[0].shape[3], cache[0].shape[2],
            cfg.layers_of(SCAN), cfg.d_state, cfg.d_inner,
            cache[3].shape[1])


def kernel_geometry(cfg: SSMMQAConfig, *, page_size: int,
                    pages_per_req: int) -> dict:
    """What ``ops/paged_attention.py`` is asked about the attention
    layers: every query head over the key-value heads."""
    return dict(num_heads=cfg.num_attention_heads, head_dim=cfg.head_dim,
                page_size=page_size, pages_per_req=pages_per_req,
                dtype=cfg.dtype, num_kv_heads=cfg.num_key_value_heads)


def kernel_refusal(cfg: SSMMQAConfig, *, page_size: int, pages_per_req: int,
                   prefill_chunk: int, max_batch: int) -> str:
    """Why the kernels do not admit this geometry — ``"attention: <bound>;
    scan: <bound>"`` — or "" when they serve every layer. One refusal puts
    both programs on their plain paths (one path a program). ``max_batch``:
    with pages as small as the one-tile pool invites, 256 slots' block
    tables outgrow the kernel's scalar memory."""
    why = {"attention": PA.paged_attention_refusal(
        batch=max_batch, **kernel_geometry(
            cfg, page_size=page_size, pages_per_req=pages_per_req)),
           "scan": SS.scan_refusal(channels=cfg.d_inner, states=cfg.d_state,
                                   chunk=prefill_chunk)}
    return "; ".join(f"{what}: {bound}" for what, bound in why.items()
                     if bound)


# ------------------------------------------------------------------- forward
def _forward(params: Any, cfg: SSMMQAConfig, tokens, positions, cache,
             block_tables, slot, start, n_valid, *, decode: bool,
             kernels: bool):
    """``tokens`` [rows] at absolute ``positions`` [rows] (< 0: no token)
    through every layer in the published order. Decode: a row a slot, one
    token each. Prefill: the rows are one chunk of the request in slot
    ``slot``, ``n_valid`` of them real, from position ``start``. ``cache``
    is ``(pool_k, pool_v, state, tail)``; ``block_tables`` [B,
    pages_per_req] the rows' pages in the pool; ``kernels``: the Pallas
    kernels (else the plain paths). Returns ``(hidden [rows, h], cache,
    stats)`` — the stats are ``programs.walk_runs``'s, all zero here."""
    programs.refuse_unserved(params, cfg, M.served_dtype)
    (rows,) = tokens.shape
    dt, eps, hd = cfg.dtype, cfg.rms_norm_eps, cfg.head_dim
    kv = cfg.num_key_value_heads
    ps, P = cache[0].shape[2], block_tables.shape[1]
    key_block = -(-rows // ps) * ps

    with device_scope("embed"):
        x = params["embed"]["tokens"][jnp.maximum(tokens, 0)]
    valid, q_pos, offs, pages = programs.row_targets(positions, block_tables,
                                                     ps)
    first = None if decode else start == 0      # the request's first chunk

    def scan_operator(u, sp, cache, at):
        pool_k, pool_v, state, tail = cache
        mixed, _, state, tail = programs.scan_mixer(
            u, sp, cfg, state, tail, at, decode=decode, kernels=kernels,
            valid=valid, slot=slot, first=first, n_valid=n_valid, eps=eps)
        return mixed, (pool_k, pool_v, state, tail)

    def attention_operator(u, ap, cache, at):
        pool_k, pool_v, state, tail = cache
        with device_scope("attn.proj"):
            q, k, v = M.attention_project(u, ap, cfg)
        with device_scope("attn.cache"):
            pool_k = pool_k.at[at, pages, offs].set(k)
            pool_v = pool_v.at[at, pages, offs].set(v)
        with device_scope("attn.core"):
            if decode and kernels:
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
            return M.attention_out(o, ap), (pool_k, pool_v, state, tail)

    def layer_of(kind, lo, cache_lo):
        stack = params[kind]

        def layer(i, carry):
            x, cache, counters = carry
            lp = jax.tree.map(lambda w: w[i], stack)
            with device_scope("norm"):
                u = M.rms_norm(x, lp["norm1"]["scale"], eps, dt)
            if kind == SCAN:
                mixed, cache = scan_operator(u, lp["ssm"], cache,
                                             cache_lo + (i - lo))
            else:
                mixed, cache = attention_operator(u, lp["attn"], cache,
                                                  cache_lo + (i - lo))
            with device_scope("norm"):
                x = x + mixed.astype(dt)
                f = M.rms_norm(x, lp["norm2"]["scale"], eps, dt)
            with device_scope("mlp"):
                y = M.gated_mlp(f, lp["mlp"]["gate"], lp["mlp"]["up"],
                                lp["mlp"]["down"])
                return x + y.astype(dt), cache, counters

        return layer

    x, cache, stats = programs.walk_runs(cfg, x, cache, layer_of)
    with device_scope("head"):
        x = M.rms_norm(x, params["final_norm"]["scale"], eps, dt)
    return x, cache, stats


_logits = device_scope("head")(M.logits)


def make_step_fns(cfg: SSMMQAConfig, *, prefill_chunk: int,
                  sampling: SamplingParams, kernels: bool = False) -> dict:
    """The two jitted programs of one engine, ``{"prefill", "decode"}``:
    ``serving/programs.py:step_fns`` around ``_forward`` over ``(pool_k,
    pool_v, state, tail)``. ``prefill`` takes the slot whose state and tail
    the request owns after the draw count. ``kernels``: the paged and scan
    kernels (else the gathered view and the ``lax.scan``)."""
    def prefill(params, cache, tokens, positions, block_table, start,
                n_valid, slot):
        return _forward(params, cfg, tokens[0], positions, cache, block_table,
                        slot, start, n_valid, decode=False, kernels=kernels)

    def decode(params, cache, tokens, positions, block_tables, lens):
        return _forward(params, cfg, tokens, positions, cache, block_tables,
                        None, None, None, decode=True, kernels=kernels)

    return programs.step_fns(prefill, decode, _logits, caches=CACHES,
                             prefill_chunk=prefill_chunk, sampling=sampling)
