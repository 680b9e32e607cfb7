"""Sparse-expert layer held as a share: no drops, sorted rows, grouped
products over the experts held here.

The layer is told which experts it holds (``experts_held`` from
``first_expert_held``). It routes every token over ALL ``n_routed_experts``
(a sigmoid router in float32 whose selection adds a bias that takes no
gradient), computes the part of the result its own experts give, and adds
nothing for the absent ones — what expert parallelism asks of a layer, run
here without its exchange. Nothing stands in for the other chips.

How the held part is computed:

- every (token, expert) pair whose expert is held here is a *row*; rows
  lie in expert order, each expert's run padded to whole tiles of
  ``moe_tile_rows`` so that a tile belongs to one expert. ``plan_rows``
  places them by counting (the order is a stable sort's, yet nothing is
  sorted or searched): a running count down the pairs' one-hot over the
  held experts gives each pair its place in its expert's run, two
  cumulative sums over ``[held]`` give the runs' padded starts, a compare
  of the tiles' first rows with the runs' ends gives each tile its
  expert, and one scatter of the pairs' rows tells the rows their pairs;
- rows are multiplied in passes of ``moe_chunk_rows``: gather the rows'
  tokens, one grouped product into the gate and up widths, one back
  (``ops/grouped_matmul.py``: a tile's weights are read by its expert's
  index, never gathered), weight, add into the tokens' sums. A pass is a turn of a ``while`` loop that
  ends after the last held row, so the cost follows the rows that exist
  and the memory is one pass's, yet the buffer admits every pair: a
  router that sends all tokens to one held expert drops none;
- no ``[tokens, experts, capacity]`` tensor is built.

The backward pass is written by hand (``jax.custom_vjp``): the same loop;
each pass recomputes its products, forms the gated MLP's gradients and adds
the weights' into float32 accumulators in place (``moe_tgmm``).

The selection bias is moved by the load and not by a gradient: the layer
hands the optimizer ``load - mean load`` as that leaf's cotangent
(``optims/optimizer.py`` takes its sign).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from fleetx_tpu.observability.trace import device_scope
from fleetx_tpu.ops import grouped_matmul


# ------------------------------------------------------------------ routing
@device_scope("moe.route")
def route(x2d: jax.Array, router: jax.Array, bias: jax.Array, top_k: int,
          scaling: float, normalise: bool, eps: float = 1e-20):
    """``x2d`` [N, h] -> (expert ids [N, k], weights [N, k] float32, load
    [E] float32). Scores are sigmoids in float32; the k largest of
    ``score + bias`` are chosen; weights are the chosen scores themselves
    (without the bias), over their sum plus ``eps`` (a family's own:
    ``models/conv_moe`` divides by the sum + 1e-6), times ``scaling``."""
    logits = jnp.einsum("nh,he->ne", x2d.astype(jnp.float32),
                        router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(scores + jax.lax.stop_gradient(bias)[None], top_k)
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    if normalise:
        picked = picked / (picked.sum(-1, keepdims=True) + eps)
    n_experts = router.shape[-1]
    load = (ids[..., None] == jnp.arange(n_experts)).sum(
        axis=(0, 1)).astype(jnp.float32)
    return ids, picked * scaling, load


@jax.custom_vjp
def load_as_cotangent(bias: jax.Array, load_excess: jax.Array) -> jax.Array:
    """A zero that carries ``load_excess`` (load - mean load) back to the
    selection bias as its cotangent: add it to the loss."""
    del bias, load_excess
    return jnp.zeros((), jnp.float32)


def _lac_fwd(bias, load_excess):
    return jnp.zeros((), jnp.float32), load_excess


def _lac_bwd(load_excess, g):
    return g * load_excess, jnp.zeros_like(load_excess)


load_as_cotangent.defvjp(_lac_fwd, _lac_bwd)


# --------------------------------------------------------------------- plan
def buffer_rows(n_pairs_max: int, held: int, tile: int, chunk: int) -> int:
    """Rows of the sorted buffer: every pair that can land here plus each
    expert's padding to a whole tile, in whole passes."""
    rows = n_pairs_max + held * (tile - 1)
    return -(-rows // chunk) * chunk


def _seen_so_far(onehot: jax.Array) -> jax.Array:
    """``onehot`` [P, held] -> how many of the rows up to and including each
    have each column set, int32: a cumulative sum down P, made in two
    levels. Inside blocks of 128 rows it is one product with a triangle of
    ones (operands of 0 and 1, float32 sums: exact below 2^24 rows), across
    blocks a cumulative sum of the blocks' totals. The chip runs
    ``jnp.cumsum`` down thousands of rows as a 128-wide window a lane: at
    1,024 x 64 the whole plan is 138 us with it and 35 with this (PERF.md
    section 6, PR 49)."""
    rows, held = onehot.shape
    blocks = jnp.pad(onehot, ((0, -rows % 128), (0, 0))).astype(
        jnp.bfloat16).reshape(-1, 128, held)
    at = jnp.arange(128, dtype=jnp.int32)
    within = jnp.einsum("ij,bjh->bih",
                        (at[:, None] >= at[None]).astype(jnp.bfloat16),
                        blocks, preferred_element_type=jnp.float32)
    before = jnp.cumsum(within[:, -1], axis=0) - within[:, -1]
    return (within + before[:, None]).reshape(-1, held)[:rows].astype(
        jnp.int32)


@device_scope("moe.route")
def plan_rows(ids: jax.Array, first: int, held: int, tile: int,
              chunk: int) -> dict:
    """Where each held (token, expert) pair goes in the sorted buffer: rows
    in the order a stable sort of the pairs by local expert gives, each
    expert's run padded to whole tiles.

    ``row_pair`` [R]: the flat pair index of each row (0 where the row is
    padding, ``row_valid`` false); ``tile_expert`` [R / tile]: the local
    expert of each tile; ``pair_row`` [P]: the row of each pair (0 where
    the pair's expert is not held, ``pair_held`` false); ``n_tiles`` and
    ``n_passes``: the tiles and passes that hold rows.

    Nothing is sorted or searched: a pair's row is its expert's padded
    start plus the pairs of that expert before it (``_seen_so_far`` down
    the one-hot), a tile's expert is the number of padded runs that end at
    or before it, and the rows learn their pairs from one scatter of the
    pairs' rows. No loop, and no lookup in a ``[held]`` table.
    """
    n, k = ids.shape
    pairs = n * k
    rows = buffer_rows(n * min(k, held), held, tile, chunk)
    local = ids.reshape(pairs) - first
    is_held = (local >= 0) & (local < held)
    onehot = local[:, None] == jnp.arange(held, dtype=jnp.int32)[None]
    seen = _seen_so_far(onehot)
    count = seen[-1]
    padded = -(-count // tile) * tile
    a_end = jnp.cumsum(padded)
    # pairs -> rows (a pair that is not held matches no column: row 0)
    pair_row = jnp.where(onehot, (a_end - padded)[None] + seen - 1, 0).sum(-1)
    # rows -> pairs: the inverse, written by the held pairs (pair + 1; the
    # others aim past the buffer and are dropped)
    slot = jnp.zeros((rows,), jnp.int32).at[
        jnp.where(is_held, pair_row, rows)].set(
            jnp.arange(1, pairs + 1, dtype=jnp.int32), mode="drop")
    at = jnp.arange(rows // tile, dtype=jnp.int32) * tile
    tile_expert = jnp.minimum((at[:, None] >= a_end[None]).sum(-1), held - 1)
    return {"row_pair": jnp.maximum(slot - 1, 0), "row_valid": slot > 0,
            "tile_expert": tile_expert, "pair_row": pair_row,
            "pair_held": is_held, "rows_held": count,
            "n_tiles": a_end[-1] // tile, "n_passes": -(-a_end[-1] // chunk)}


# ---------------------------------------------------------- grouped products
@device_scope("moe.route")
def pass_inputs(c, x, w_flat, plan, k, chunk, tile):
    """What pass ``c`` works on: its rows' tokens, weights (zero on padding
    rows), each tile's expert, the tiles that hold rows, the gathered rows."""
    at, tiles = c * chunk, chunk // tile
    pair = jax.lax.dynamic_slice(plan["row_pair"], (at,), (chunk,))
    valid = jax.lax.dynamic_slice(plan["row_valid"], (at,), (chunk,))
    experts = jax.lax.dynamic_slice(plan["tile_expert"], (c * tiles,),
                                    (tiles,))
    n_tiles = jnp.clip(plan["n_tiles"] - c * tiles, 0, tiles)
    tok = pair // k
    return tok, w_flat[pair] * valid, valid, experts, n_tiles, x[tok]


@device_scope("moe.experts")
def _gated(xs, gate_up, down, experts, n_tiles, tile):
    """The pass's gated MLP: ``(gu, a, o)`` with ``gu`` the gate and up
    products side by side, ``a = silu(gate) * up``, ``o = a @ down``."""
    gu = grouped_matmul.moe_gmm(xs, gate_up, experts, n_tiles, tile=tile,
                                out_dtype=jnp.float32)
    g, u = jnp.split(gu, 2, axis=-1)
    a = (jax.nn.silu(g) * u).astype(xs.dtype)
    o = grouped_matmul.moe_gmm(a, down, experts, n_tiles, tile=tile,
                               out_dtype=jnp.float32)
    return gu, a, o


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def grouped_experts(x, w_flat, gate_up, down, plan, k, chunk, tile):
    """``x`` [N, h], pair weights ``w_flat`` [N * k], the held experts'
    ``gate_up`` [held, h, 2 f] and ``down`` [held, f, h] -> the held
    experts' weighted sum for every token, [N, h] float32."""
    return _grouped_fwd(x, w_flat, gate_up, down, plan, k, chunk, tile)[0]


def _grouped_fwd(x, w_flat, gate_up, down, plan, k, chunk, tile):
    def body(state):
        c, y = state
        tok, wt, _, experts, n_tiles, xs = pass_inputs(
            c, x, w_flat, plan, k, chunk, tile)
        o = _gated(xs, gate_up, down, experts, n_tiles, tile)[2]
        with device_scope("moe.route"):
            return c + 1, y.at[tok].add(o * wt[:, None])

    with device_scope("moe.experts"):
        _, y = jax.lax.while_loop(
            lambda s: s[0] < plan["n_passes"], body,
            (jnp.int32(0), jnp.zeros(x.shape, jnp.float32)))
    return y, (x, w_flat, gate_up, down, plan)


def _grouped_bwd(k, chunk, tile, residuals, dy):
    x, w_flat, gate_up, down, plan = residuals
    rows = plan["row_pair"].shape[0]
    dt = x.dtype

    def body(state):
        c, dx, d_row_w, d_gate_up, d_down = state
        tok, wt, valid, experts, n_tiles, xs = pass_inputs(
            c, x, w_flat, plan, k, chunk, tile)
        gu, a, o = _gated(xs, gate_up, down, experts, n_tiles, tile)
        with device_scope("moe.route"):
            dyc = dy[tok]
            d_wt = (o * dyc).sum(-1) * valid
            do = (dyc * wt[:, None]).astype(dt)
        with device_scope("moe.experts"):
            da = grouped_matmul.moe_gmm(do, down, experts, n_tiles,
                                        tile=tile, transpose_rhs=True,
                                        out_dtype=jnp.float32)
            g, u = jnp.split(gu, 2, axis=-1)
            sig = jax.nn.sigmoid(g)
            dgu = jnp.concatenate(
                [da * u * sig * (1.0 + g * (1.0 - sig)), da * g * sig],
                axis=-1).astype(dt)
            dxs = grouped_matmul.moe_gmm(dgu, gate_up, experts, n_tiles,
                                         tile=tile, transpose_rhs=True,
                                         out_dtype=jnp.float32)
            d_gate_up = grouped_matmul.moe_tgmm(xs, dgu, d_gate_up, experts,
                                                n_tiles, tile=tile)
            d_down = grouped_matmul.moe_tgmm(a, do, d_down, experts, n_tiles,
                                             tile=tile)
        with device_scope("moe.route"):
            return (c + 1, dx.at[tok].add(dxs),
                    jax.lax.dynamic_update_slice(d_row_w, d_wt,
                                                 (c * chunk,)),
                    d_gate_up, d_down)

    zeros32 = functools.partial(jnp.zeros_like, dtype=jnp.float32)
    with device_scope("moe.experts"):
        _, dx, d_row_w, d_gate_up, d_down = jax.lax.while_loop(
            lambda s: s[0] < plan["n_passes"], body,
            (jnp.int32(0), zeros32(x), jnp.zeros((rows,), jnp.float32),
             zeros32(gate_up), zeros32(down)))
        d_gate_up = d_gate_up.astype(gate_up.dtype)
        d_down = d_down.astype(down.dtype)
    with device_scope("moe.route"):
        d_w = jnp.where(plan["pair_held"], d_row_w[plan["pair_row"]], 0.0)
        return (dx.astype(dt), d_w.astype(w_flat.dtype), d_gate_up, d_down,
                None)


grouped_experts.defvjp(_grouped_fwd, _grouped_bwd)


# -------------------------------------------------------------------- layer
def gated_mlp(x, gate, up, down):
    """``down(silu(gate x) * up x)`` on ``x`` [..., h]."""
    g = jnp.einsum("...h,hf->...f", x, gate)
    u = jnp.einsum("...h,hf->...f", x, up)
    return jnp.einsum("...f,fh->...h", jax.nn.silu(g) * u, down)


def moe_layer(x: jax.Array, p: dict, cfg) -> tuple:
    """The expert layer on ``x`` [B, S, h]: held routed experts plus the
    shared expert. Returns ``(y, stats)``; ``stats`` holds the zero that
    carries the load to the selection bias (``bias_step``) and the load
    counters."""
    b, s, h = x.shape
    dt = x.dtype
    x2d = x.reshape(b * s, h)
    k, held = cfg.num_experts_per_tok, cfg.experts_held
    first = cfg.first_expert_held
    chunk = min(cfg.moe_chunk_rows,
                -(-(b * s * min(k, held)) // cfg.moe_tile_rows)
                * cfg.moe_tile_rows)
    ids, weights, load = route(x2d, p["router"], p["selection_bias"], k,
                               cfg.routed_scaling_factor, cfg.norm_topk_prob)
    plan = plan_rows(ids, first, held, cfg.moe_tile_rows, chunk)
    with device_scope("moe.experts"):
        gate_up = jnp.concatenate([p["experts_gate"], p["experts_up"]],
                                  axis=-1).astype(dt)
        routed = grouped_experts(
            x2d, weights.reshape(-1).astype(jnp.float32), gate_up,
            p["experts_down"].astype(dt), plan, k, chunk, cfg.moe_tile_rows)
    with device_scope("mlp"):
        shared = gated_mlp(x2d, p["shared_gate"].astype(dt),
                           p["shared_up"].astype(dt),
                           p["shared_down"].astype(dt))
        y = (routed + shared.astype(jnp.float32)).astype(dt).reshape(b, s, h)
    with device_scope("moe.route"):
        rows = plan["rows_held"].astype(jnp.float32)
        stats = {
            "bias_step": load_as_cotangent(p["selection_bias"],
                                           load - load.mean()),
            "rows_max_over_mean": rows.max() / jnp.maximum(rows.mean(), 1.0),
            "held_share": rows.sum() / (b * s * k),
            "bias_abs_max": jnp.abs(p["selection_bias"]).max(),
        }
    return y, stats
