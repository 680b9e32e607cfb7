"""What the served families' device programs share, owned by none of them.

``serving/decode.py`` (GPT), ``serving/swa_moe.py``, ``serving/gdn_mla.py``,
``serving/conv_moe.py`` and ``serving/samba_y.py`` each hold ONE family's
caches and forward; what more than one of them needs is here, under public
names, so that no family imports another: the tail of every program
(``SamplingParams``, ``sample``, ``merge_fresh``: all five); and for the
four families after GPT the one-jit cast (``serving_params``, told the
family's ``served_dtype``; GPT's rule reads the leaf it is given and stays
in ``decode.py``), attention over gathered keys and a chunk's fold over a
cache's pages, a slot's ring of window keys (``ring_pages``, where a row is
written — ``ring_targets`` — and how a ring is read: ``ring_table`` folded
as a block table, ``ring_view`` gathered; two families), the walk over a
config's layer runs with the step's expert counters, and ``step_fns``: the
``prefill`` / ``decode`` pair around a family's forward. GPT keeps its own
pair (``decode.py:make_step_fns``): its forward returns no counters and its
outputs are constrained to a mesh, and the shared pair would branch on both.

**The programs' contract** (``serving/engine.py`` calls them; docs/serving.md
"The tick"): ``prefill(params, *cache, tokens [1, C], block_table [1, P],
start, n_valid, rng, draw, *extra)`` and ``decode(params, *cache, tokens
[B], fresh_slot, fresh_tok [1], block_tables [B, P], lens [B], rng,
draw)``; both donate the cache buffers and return them first, then the
sampled token(s) and the float32 logits, and ``decode`` last the step's
counters. A change to that contract is made here, once.

**What a prefill forward hands back** (``step_fns``): the chunk's hidden
rows, of which the shell picks the last valid one — or, told so
(``last_row=True``), that ONE row, already picked: a family whose upper
layers keep no cache of their own runs them on the chunk's last valid row
alone (no earlier token's pass through them is ever read again) and has no
chunk of hidden states to return.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp

from fleetx_tpu.models import scan_mixer as SM
from fleetx_tpu.models.gpt import generation as G
from fleetx_tpu.observability.trace import device_scope
from fleetx_tpu.ops import selective_scan as SS

_NEG = -1e30


# ------------------------------------------------------------------ sampling
@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Engine-wide sampling knobs (static: baked into the two programs)."""

    do_sample: bool = False
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 0.0


@device_scope("sample")
def sample(logits: jax.Array, rng: jax.Array, draw: jax.Array,
           sp: SamplingParams) -> jax.Array:
    """Greedy argmax or the sampling-transform chain shared with
    ``generation.generate`` (temperature → top-k → top-p → categorical).

    ``rng`` is the engine's one base key and ``draw`` the host's count of
    the programs it has dispatched: the call's key is folded HERE, inside
    the program, so no key is ever split by a dispatch of its own. Greedy
    reads neither (and ``jit`` then drops both from the executable)."""
    if not sp.do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    l = G.apply_temperature(logits, sp.temperature)
    l = G.apply_top_k(l, sp.top_k)
    l = G.apply_top_p(l, sp.top_p)
    return jax.random.categorical(jax.random.fold_in(rng, draw), l,
                                  axis=-1).astype(jnp.int32)


@device_scope("sample")
def merge_fresh(tokens: jax.Array, fresh_slot: jax.Array,
                fresh_tok: jax.Array) -> jax.Array:
    """The decode batch's input tokens: the previous step's output, which
    never left the device, with the first token of the request that left
    prefill in this tick (``fresh_tok`` ``[1]``, the last chunk's sampled
    token, unfetched) put in its slot; ``fresh_slot < 0``: none did."""
    rows = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    return jnp.where(rows == fresh_slot, fresh_tok[0], tokens)


# ---------------------------------------------------------------- parameters
def unserved(params: Any, cfg: Any, served_dtype: Callable) -> list:
    """Flattening-order indices of the leaves a forward could not take as
    they are: those not in ``served_dtype(path, cfg)``."""
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    return [i for i, (path, leaf) in enumerate(flat)
            if leaf.dtype != served_dtype(path, cfg)]


def refuse_unserved(params: Any, cfg: Any, served_dtype: Callable) -> None:
    """What a forward says, while tracing, of a tree that would need a
    cast: the weights are cast once, when the engine is built."""
    todo = unserved(params, cfg, served_dtype)
    if todo:
        raise TypeError(
            "the serving programs take the tree serving_params() makes: "
            f"{len(todo)} leaves are not in their served dtype")


def serving_params(params: Any, cfg: Any, served_dtype: Callable) -> Any:
    """The tree both programs of a family take: every leaf in
    ``served_dtype(path, cfg)`` — ``cfg.dtype`` but what the family's model
    keeps in float32 (norms, routers). One jitted cast of the leaves that
    need it; a leaf already served comes back as the object it was."""
    todo = unserved(params, cfg, served_dtype)
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
def gathered_attention(q, k, v, key_pos, q_pos, window, dtype, scale=None,
                       out_dtype=None):
    """``q`` [B, S, H, hd] against gathered keys ``k``/``v`` [B, K, kv, hd]
    that hold the tokens at absolute positions ``key_pos`` [B, K] (< 0: no
    token): softmax over the keys at ``q_pos − window < p ≤ q_pos`` of the
    scores times ``scale`` (None: ``1 / sqrt(hd)``); the probabilities
    enter the value product in ``dtype``, the result comes out in
    ``out_dtype`` (None: ``dtype``)."""
    B, S, H, hd = q.shape
    kv = k.shape[2]
    qg = q.reshape(B, S, kv, H // kv, hd)
    s = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                   preferred_element_type=jnp.float32)
    s = s / math.sqrt(hd) if scale is None else s * scale
    kp, qp = key_pos[:, None, :], q_pos[:, :, None]
    seen = (kp >= 0) & (kp <= qp)
    if window is not None:
        seen = seen & (kp > qp - window)
    s = jnp.where(seen[:, None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1).astype(dtype)
    o = jnp.einsum("bkgst,btkd->bskgd", p, v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, S, H, hd).astype(out_dtype or dtype)


def prefill_blocked_attention(q, pool_k, pool_v, layer, table, q_pos,
                              n_keys, key_block: int, dtype, window=None,
                              scale=None, out_dtype=None):
    """One chunk's queries ``q`` [1, C, H, hd] against the pages ``table``
    [1, P] names in layer ``layer`` of a cache (a request's pages in the
    full pool, or a slot's ring as a table: logical page *j* → ring page
    ``j mod ring_pages``), ``key_block`` keys at a time, up to key
    ``n_keys`` (online softmax in float32). Without a ``window`` the loop
    is as long as the context; with one it starts at the block that holds
    the first query's oldest key, ``q_pos[0, 0] − window + 1``, so it is as
    long as window + chunk whatever the context, and the keys of that
    block the ring has since overwritten lie before every query's window
    and are masked. ``scale`` / ``out_dtype``: as ``gathered_attention``."""
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
                       preferred_element_type=jnp.float32)
        s = s / math.sqrt(hd) if scale is None else s * scale
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
    return jnp.transpose(o, (2, 0, 1, 3)).reshape(1, C, H, hd).astype(
        out_dtype or dtype)


@device_scope("attn.cache")
def row_targets(positions: jax.Array, block_tables: jax.Array,
                page_size: int) -> tuple:
    """Where each row's token goes in a paged pool, for every layer:
    ``positions`` [rows] (< 0: no token, the null page) through
    ``block_tables`` [rows, P] (decode: a row a slot) or [1, P] (a chunk:
    every row the request's) -> ``(valid, q_pos, offsets, pages)``."""
    rows, P = positions.shape[0], block_tables.shape[1]
    valid = positions >= 0
    q_pos = jnp.maximum(positions, 0)
    offs = jnp.clip(positions % page_size, 0, page_size - 1)
    page_slot = jnp.clip(positions // page_size, 0, P - 1)
    tables = block_tables if block_tables.shape[0] == rows else \
        jnp.broadcast_to(block_tables, (rows, P))
    pages = jnp.where(valid, jnp.take_along_axis(
        tables, page_slot[:, None], axis=1)[:, 0], 0)
    return valid, q_pos, offs, pages


# ------------------------------------------------------------ a slot's ring
def ring_pages(cfg: Any, page_size: int, prefill_chunk: int) -> int:
    """Pages of one slot's ring: the window (``cfg.sliding_window``) plus
    one prefill chunk. A window layer's buffer is ``[layers, 1 + slots ·
    ring_pages, page_size, lanes]``: page 0 the null page, slot *s*'s ring
    the ``ring_pages`` pages from ``1 + s · ring_pages``, the token at
    position *p* in ring page ``(p // page_size) mod ring_pages``. A
    chunk's keys are written before its queries read, and the
    ``prefill_chunk`` tokens they overwrite are older than the window of
    every query in the chunk: that is what the extra chunk is for."""
    return -(-(cfg.sliding_window + int(prefill_chunk)) // int(page_size))


@device_scope("attn.cache")
def ring_targets(positions: jax.Array, slots: jax.Array, rp: int,
                 page_size: int) -> tuple:
    """Where a window layer WRITES: ``positions`` [B, S] (< 0: no token,
    the null page) of rows whose rings are ``slots`` [B] -> ``(ring_first
    [B]`` — the first page of each row's ring —``, ring_at [B, S])``; the
    offset inside the page is the paged pool's (``positions mod
    page_size``)."""
    ring_first = 1 + slots * rp
    ring_at = jnp.where(
        positions >= 0,
        ring_first[:, None] + (jnp.maximum(positions, 0) // page_size) % rp,
        0)
    return ring_first, ring_at


@device_scope("attn.cache")
def ring_table(ring_first: jax.Array, pages_per_req: int, rp: int
               ) -> jax.Array:
    """A ring FOLDED as a block table (``prefill_blocked_attention``): [B,
    pages_per_req], logical page *j* -> ring page ``j mod rp``."""
    return ring_first[:, None] + \
        jnp.arange(pages_per_req, dtype=jnp.int32)[None, :] % rp


@device_scope("attn.cache")
def ring_view(ring_first: jax.Array, last: jax.Array, rp: int,
              page_size: int) -> tuple:
    """A ring GATHERED (``gathered_attention``): the ring's ``rp`` logical
    pages that end at the page of ``last`` [B] (the last position each row
    holds), in order -> ``(pages [B, rp], the position of each key in them
    [B, rp · page_size])``; a position < 0 holds no token."""
    B = last.shape[0]
    view_first = (jnp.maximum(last, 0) // page_size - (rp - 1))[:, None] \
        + jnp.arange(rp, dtype=jnp.int32)[None, :]              # [B, rp]
    view_pos = (view_first[:, :, None] * page_size + jnp.arange(
        page_size, dtype=jnp.int32)[None, None, :]).reshape(B, rp * page_size)
    return ring_first[:, None] + view_first % rp, view_pos


@device_scope("head")
def untied_logits(params: Any, x_last: jax.Array) -> jax.Array:
    """The (untied) head on the selected positions -> float32 ``[B, V]``."""
    return jnp.einsum("bh,hv->bv", x_last, params["head"]["kernel"],
                      preferred_element_type=jnp.float32)


# ------------------------------------------------- a selective-scan layer
def scan_mixer(u: jax.Array, sp: dict, cfg: Any, state: jax.Array,
               tail: jax.Array, i, *, decode: bool, kernels: bool,
               valid: jax.Array, slot=None, first=None, n_valid=None,
               eps: float = 0.0) -> tuple:
    """Layer ``i`` of a family's scan stack through its two caches: ``u``
    [rows, h] the block's normed input, ``sp`` the layer's leaves
    (``models/scan_mixer.py``; a layer that holds the inner norms' weights
    norms its step, ``B`` and ``C`` with ``eps``), ``state`` [scan layers,
    slots, N, inner] float32 and ``tail`` [scan layers, d_conv − 1, slots,
    inner] the WHOLE buffers -> ``(the mixer's output [rows, h], y [rows,
    inner] float32 — the scan's output with the skip —, state, tail)``.

    Decode: a row a slot; the live rows (``valid``) shift their tails and
    step their states (``ssm_decode``, in place), every other row keeps
    both. Prefill: the rows are one chunk of the request in ``slot``,
    ``n_valid`` of them real; a request's ``first`` chunk reads zeros in
    place of the state and the tail, a row past the chunk's end carries
    ``Δ = 0``, and the tail keeps the last REAL tokens' inputs. ``kernels``:
    the Pallas kernels (else the plain forms)."""
    dt = u.dtype
    with device_scope("ssm.proj"):
        xs, z = SM.ssm_in(u, sp)
    with device_scope("ssm.conv"):
        if decode:
            old = tail[i]                                   # [K-1, B, inner]
            ext = jnp.concatenate([old, xs[None]], axis=0)
            c = SM.conv_taps(ext, sp["taps"][:, None, :], axis=0)
            tail = tail.at[i].set(jnp.where(
                valid[None, :, None], ext[1:], old))
        else:
            old = jnp.where(first, jnp.zeros_like(tail[i, :, slot]),
                            tail[i, :, slot])               # [K-1, inner]
            ext = jnp.concatenate([old, xs], axis=0)
            c = SM.conv_sequence(ext, sp["taps"])
            # the last inputs of the chunk's REAL tokens
            tail = tail.at[i, :, slot].set(jax.lax.dynamic_slice(
                ext, (n_valid, 0), (old.shape[0], ext.shape[1])))
        xc = SM.conv_act(c, sp, dt)
    with device_scope("ssm.proj"):
        delta, b, c = SM.ssm_params(xc, sp, cfg, eps)
    with device_scope("ssm.core"):
        a = SM.ssm_decay(sp)
        if decode:
            y, state = SS.scan_step(state, i, xc, delta, a, b, c, sp["D"],
                                    valid, kernel=kernels)
        else:
            # a row past the chunk's end leaves the state as it was
            delta = jnp.where(valid[:, None], delta, 0.0)
            h0 = jnp.where(first, jnp.zeros_like(state[i, slot]),
                           state[i, slot])
            y, h = SS.scan_chunk(xc, delta, a, b, c, sp["D"], h0,
                                 kernel=kernels)
            state = state.at[i, slot].set(h)
    with device_scope("ssm.proj"):
        return SM.ssm_out(y, z, sp), y, state, tail


# ------------------------------------------------------------ the layer walk
def per_layer_leaves(stack: dict) -> dict:
    """What a layer loop indexes by layer: a stack's leaves but the experts'
    matrices, which are never indexed by layer (``moe_gmm`` reads tile
    *t*'s matrix at ``layer · held + expert(t)`` of the stack seen flat)."""
    per_layer = {k: v for k, v in stack.items() if k != "moe"}
    if "moe" in stack:
        per_layer["moe"] = {k: v for k, v in stack["moe"].items()
                            if not k.startswith("experts_")}
    return per_layer


@device_scope("moe.route")
def count_held(counters: tuple, held_rows: jax.Array,
               turns: jax.Array) -> tuple:
    """The step's counters after one more expert layer (``held_rows``: the
    rows each held expert took; ``turns``: the passes its loop made)."""
    hit, pairs, load, passes = counters
    hit = hit + (held_rows > 0).sum().astype(jnp.float32)
    pairs = pairs + held_rows.sum().astype(jnp.int32)
    held = held_rows.astype(jnp.float32)
    load = jnp.maximum(load, held.max() / jnp.maximum(held.mean(), 1e-9))
    return hit, pairs, load, passes + turns.astype(jnp.int32)


def walk_runs(cfg: Any, x: jax.Array, cache: tuple,
              layer_of: Callable) -> tuple:
    """``x`` through every layer in the published order (``cfg.runs()``:
    kind, first layer in its stack, layers, first layer in its cache), each
    run a loop over its slice of its stack with the cache buffers in the
    carry. ``layer_of(kind, lo, cache_lo)`` makes the run's loop body over
    ``(x, cache, counters)``. Returns ``(x, cache, stats)``; ``stats``:
    held experts hit, summed over the expert layers, (token, expert) pairs
    on held experts, the rows of the fullest held expert over the mean
    (worst layer) and the passes the held experts' loops took (all
    layers)."""
    carry = (x, tuple(cache), (jnp.float32(0.0), jnp.int32(0),
                               jnp.float32(0.0), jnp.int32(0)))
    for kind, lo, n, cache_lo in cfg.runs():
        layer = layer_of(kind, lo, cache_lo)
        with device_scope("stack"):
            # one layer: a static index, the layer is a view of its stack
            carry = layer(lo, carry) if n == 1 else \
                jax.lax.fori_loop(lo, lo + n, layer, carry)
    x, cache, (hit, pairs, load, passes) = carry
    return x, cache, {"hit": hit, "pairs_held": pairs,
                      "load_max_over_mean": load, "passes": passes}


# ----------------------------------------------------------------- the shell
def last_valid_row(rows: jax.Array, n_valid: jax.Array) -> jax.Array:
    """Row ``n_valid − 1`` of a chunk's ``rows`` [C, ...] -> [1, ...]."""
    at = jnp.clip(n_valid - 1, 0, rows.shape[0] - 1)
    return jax.lax.dynamic_index_in_dim(rows, at, axis=0, keepdims=True)


def step_fns(prefill_forward: Callable, decode_forward: Callable,
             logits_of: Callable, *, caches: int, prefill_chunk: int,
             sampling: SamplingParams, blocks: bool = False,
             last_row: bool = False) -> dict:
    """The two jitted programs of one engine, ``{"prefill", "decode"}``,
    around a family's forward (the module docstring has their contract).

    ``prefill_forward(params, cache, tokens [1, C], positions, block_table,
    start, n_valid, *extra)`` and ``decode_forward(params, cache, tokens
    [B], positions, block_tables, lens)`` return ``(hidden, cache,
    stats)``; ``logits_of(params, rows [n, h])`` float32 ``[n, V]``;
    ``caches`` how many cache buffers follow ``params`` (all donated).
    ``blocks``: the forward takes positions, and returns hidden states, as
    ``[B, S]`` blocks (``[1, C]`` a chunk, ``[B, 1]`` a decode step) where
    the others take a row a token (``[C]``, ``[B]``). ``positions < 0``: no
    token (a ragged chunk's tail; an empty slot, ``lens < 0``).
    ``last_row``: ``prefill_forward`` returns the chunk's last valid row
    ``[1, h]`` alone, not the chunk, and the shell picks nothing. ``decode``
    adds the live rows to ``stats`` (``walk_runs``'s), which ride to the
    host with the tokens. Shapes are static (``max_batch`` /
    ``pages_per_req`` arrive with the arrays), so each jit cache holds one
    entry for the engine's lifetime."""
    def prefill(params, *args):
        """One prompt chunk of one request: ``n_valid`` real entries from
        position ``start``; the sampled token and logits are the last
        valid row's (meaningful on the request's final chunk)."""
        cache, (tokens, block_table, start, n_valid, rng, draw, *extra) = \
            args[:caches], args[caches:]
        idx = jnp.arange(prefill_chunk, dtype=jnp.int32)
        if blocks:
            idx = idx[None, :]
        positions = jnp.where(idx < n_valid, start + idx, -1)
        x, cache, _ = prefill_forward(params, cache, tokens, positions,
                                      block_table, start, n_valid, *extra)
        if not last_row:
            with device_scope("head"):
                x = last_valid_row(x[0] if blocks else x, n_valid)
        logits = logits_of(params, x)
        return (*cache, sample(logits, rng, draw, sampling), logits)

    def decode(params, *args):
        """One token for every slot: an empty slot, or one still in
        prefill, carries ``lens < 0`` (and a null-page table) and keeps
        what it holds; ``tokens`` is the previous call's sampled tokens,
        ``merge_fresh`` puts the one request that left prefill this tick
        in its slot."""
        cache, (tokens, fresh_slot, fresh_tok, block_tables, lens, rng,
                draw) = args[:caches], args[caches:]
        tokens = merge_fresh(tokens, fresh_slot, fresh_tok)
        positions = jnp.where(lens >= 0, lens, -1)
        if blocks:
            positions = positions[:, None]
        x, cache, stats = decode_forward(params, cache, tokens, positions,
                                         block_tables, lens)
        logits = logits_of(params, x[:, 0] if blocks else x)
        with device_scope("moe.route"):     # rides with the counters
            stats["rows"] = (lens >= 0).sum().astype(jnp.int32)
        return (*cache, sample(logits, rng, draw, sampling), logits, stats)

    donate = tuple(range(1, 1 + caches))
    return {"prefill": jax.jit(prefill, donate_argnums=donate),
            "decode": jax.jit(decode, donate_argnums=donate)}


# ------------------------------------------------------- the step's counters
def expert_stats_recorder(cfg: Any) -> Callable:
    """``record(metrics, counters)``: what ``decode`` returned after its
    logits, on the host (``counters``: a list of at most one ``stats``) ->
    the ``serving_moe_*`` metrics. ``cfg.kinds()`` names the stacks with
    experts ``*moe``; a config without them records nothing."""
    expert_layers = sum(n for kind, n in cfg.kinds().items()
                        if kind.endswith("moe"))
    per_tok = cfg.num_experts_per_tok

    def record(metrics: Any, counters: list) -> None:
        if not expert_layers:
            return
        for stats in counters:
            metrics.histogram("serving_moe_experts_hit").record(
                float(stats["hit"]) / expert_layers)
            metrics.counter("serving_moe_pairs_held_total").inc(
                int(stats["pairs_held"]))
            metrics.counter("serving_moe_pairs_total").inc(
                int(stats["rows"]) * per_tok * expert_layers)
            metrics.histogram("serving_moe_load_max_over_mean").record(
                float(stats["load_max_over_mean"]))
            metrics.counter("serving_moe_passes_total").inc(
                int(stats["passes"]))

    return record


def expert_stats_snapshot(metrics: Any) -> dict:
    """The ``serving_snapshot()`` keys of what rode the decode program's
    outputs to the host with the tokens."""
    return {
        "serving_moe_load_max_over_mean": metrics.histogram(
            "serving_moe_load_max_over_mean").summary().get("mean"),
        "serving_moe_passes_total": int(
            metrics.counter("serving_moe_passes_total").value)}
