"""The windowed-attention sparse-expert decoder family: parameters and
the parts of a layer that need no cache.

Pre-norm layers, RMS norm, no biases, untied head. Layer ℓ, input ``x``:
``u = norm(x)``, ``h = x + Attn(u)``, ``v = norm(h)``, ``y = h + FF(v)``.
What the published members differ in is data of the config
(``SWAMoEConfig``; ``docs/swa_moe.md`` has each member's equations):

- *Attention*: ``H`` query heads (a count a layer) over
  ``num_key_value_heads`` key-value heads of ``head_dim``; rotary on
  queries and keys by the layer type's group of ``rope_parameters`` — YaRN
  on part of a head's dimensions (cos and sin times ``attention_factor``),
  plain rotary, or NONE: a layer type without a group carries no position
  signal and is not rotated. Scores ``q·k / sqrt(head_dim)``, causal, and
  in a window layer query *i* sees keys ``i − window + 1 … i``. With
  ``gating: per-head`` a gate ``g = sigmoid(u W_g)`` multiplies each head's
  output before the output projection; with ``none`` there is no such leaf.
- *Feed-forward*: a gated MLP (``down(act(gate v) * up v)``, ``hidden_act``
  SiLU or ReLU) in the layers ``mlp_only_layers`` names (there may be
  none); elsewhere router logits in float32 — of ``v``, or with
  ``router_input: pre_attention`` of the layer's normed input ``u``,
  computed before attention and applied after it — scored
  (``router_scoring``) by a softmax over all ``num_experts`` of which the
  ``num_experts_per_tok`` largest are chosen, or by choosing the largest
  logits and taking the softmax over the chosen alone; with
  ``norm_topk_prob`` the chosen scores over their sum; times the routed
  scaling; applied to the outputs of the experts HELD here (all of them,
  or a share: ``models/mla_moe/moe.py``'s sorted rows and
  ``ops/grouped_matmul.py:moe_gmm``), plus, where
  ``shared_expert_intermediate_size`` is not 0, one shared expert added
  unweighted. What absent experts would add is left out.

Layers of one shape are stacked (``SWAMoEConfig.kind_of``): the tree is
``{"embed", "head", "final_norm", "<kind>": {...leaves [layers, ...]}}``
with no leaf for what a member lacks (gate, shared expert, dense stack).
What walks the layers with their caches is ``serving/swa_moe.py``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from fleetx_tpu.models.mla_moe import moe as held_share
from fleetx_tpu.models.swa_moe.config import SWAMoEConfig
from fleetx_tpu.observability.trace import device_scope
from fleetx_tpu.ops import grouped_matmul

#: leaves kept in float32 whatever ``cfg.dtype`` is: the norms' scales
#: (multiplied in float32) and the router (float32 logits)
F32_GROUPS = frozenset({"attn_norm", "mlp_norm", "final_norm"})
F32_LEAVES = frozenset({"router"})


# ------------------------------------------------------------------ the tree
def param_shapes(cfg: SWAMoEConfig) -> dict:
    """The parameter tree as shapes: leaf -> tuple."""
    h, hd, kv = cfg.hidden_size, cfg.head_dim, cfg.num_key_value_heads
    f, fs, held = cfg.moe_intermediate_size, \
        cfg.shared_expert_intermediate_size, cfg.experts_held
    tree = {"embed": {"tokens": (cfg.vocab_size, h)},
            "head": {"kernel": (h, cfg.vocab_size)},
            "final_norm": {"scale": (h,)}}
    for kind, n in cfg.kinds().items():
        heads = cfg.heads_of(kind)
        layer = {
            "attn_norm": {"scale": (n, h)},
            # queries and keys a [head_dim, hidden] matrix a head, hidden
            # minor: the one layout both programs' products read as it is
            # on the v5e ([hidden, heads · head_dim], [hidden, heads,
            # head_dim] and [heads, hidden, head_dim] are each relaid out
            # on every call of one program or the other: 340 MB, ~4.6 ms
            # by the compiler's estimate, for six layers' queries)
            "attn": {"q": (n, heads, hd, h), "k": (n, kv, hd, h),
                     "v": (n, h, kv * hd), "out": (n, heads, hd, h)},
            "mlp_norm": {"scale": (n, h)},
        }
        if cfg.gating == "per-head":
            layer["attn"]["gate"] = (n, h, heads)
        if kind.endswith("dense"):
            i = cfg.intermediate_size
            layer["mlp"] = {"gate": (n, h, i), "up": (n, h, i),
                            "down": (n, i, h)}
        else:
            layer["moe"] = {
                "router": (n, h, cfg.num_experts),
                "experts_gate": (n, held, h, f), "experts_up": (n, held, h, f),
                "experts_down": (n, held, f, h)}
            if fs:
                layer["moe"].update(shared_gate=(n, h, fs),
                                    shared_up=(n, h, fs),
                                    shared_down=(n, fs, h))
        tree[kind] = layer
    return tree


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def served_dtype(path: tuple, cfg: SWAMoEConfig):
    """The dtype the serving programs hold the leaf at ``path`` in."""
    keys = {getattr(k, "key", k) for k in path}
    if keys & F32_GROUPS or keys & F32_LEAVES:
        return jnp.dtype(jnp.float32)
    return jnp.dtype(cfg.dtype)


def served_template(cfg: SWAMoEConfig):
    """The abstract tree the serving programs take: each leaf with its
    shape and the dtype it is served in."""
    return jax.tree_util.tree_map_with_path(
        lambda path, shape: jax.ShapeDtypeStruct(
            shape, served_dtype(path, cfg)),
        param_shapes(cfg), is_leaf=_is_shape)


def count_params(cfg: SWAMoEConfig) -> int:
    return sum(math.prod(s) for s in jax.tree.leaves(
        param_shapes(cfg), is_leaf=_is_shape))


def init_params(cfg: SWAMoEConfig, key: jax.Array,
                served: bool = False) -> dict:
    """Seeded parameters: N(0, 0.02) matrices, unit norm scales
    (``initializer_range`` is not in the published config), in
    ``cfg.param_dtype`` — or, ``served``, each leaf in the dtype the serving
    programs hold it in (under ``jax.jit`` the draw and the cast fuse, so a
    float32 copy of a tree that is served in bfloat16 never stands on the
    device: 12.8 GB for the recipe's 3.2 B parameters)."""
    shapes = param_shapes(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=_is_shape)
    keys = jax.random.split(key, len(flat))

    def make(path, shape, k):
        dtype = served_dtype(path, cfg) if served else cfg.param_dtype
        if {getattr(p, "key", p) for p in path} & F32_GROUPS:
            return jnp.ones(shape, dtype)
        return (0.02 * jax.random.normal(k, shape, jnp.float32)
                ).astype(dtype)

    return treedef.unflatten([make(p, s, k)
                              for (p, s), k in zip(flat, keys)])


# --------------------------------------------------------------------- parts
def rms_norm(x: jax.Array, scale: jax.Array, eps: float, dtype) -> jax.Array:
    """``x / rms(x) * scale`` in float32, cast to ``dtype``."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (y * scale).astype(dtype)


def rotary_frequencies(cfg: SWAMoEConfig, layer_type: str) -> tuple:
    """``(inverse frequencies [rot / 2] float64 on the host, factor on cos
    and sin)`` of a layer type that rotates: plain rotary, or YaRN — the
    low frequencies
    (whose wavelength passes the original context) divided by ``factor``,
    the high ones kept, a linear ramp between ``beta_fast`` and
    ``beta_slow`` turns within the original context."""
    rp = cfg.rope_parameters[layer_type]
    rot = int(cfg.head_dim * float(rp.get("partial_rotary_factor", 1)))
    base = float(rp["rope_theta"])
    pos_freqs = base ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    if rp.get("rope_type", "default") != "yarn":
        return 1.0 / pos_freqs, 1.0
    factor, orig = float(rp["factor"]), \
        float(rp["original_max_position_embeddings"])

    def correction_dim(turns):
        return rot * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(rp["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rp["beta_slow"]))), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    inv = (1.0 / (factor * pos_freqs)) * ramp \
        + (1.0 / pos_freqs) * (1.0 - ramp)
    scale = rp.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0
    return inv, float(scale)


def rotary_tables(cfg: SWAMoEConfig, layer_type: str,
                  positions: jax.Array):
    """``(cos, sin)`` float32 ``[..., rot / 2]`` at ``positions``; None for
    a layer type that carries no position signal."""
    if cfg.rope_parameters[layer_type] is None:
        return None
    inv, scale = rotary_frequencies(cfg, layer_type)
    angle = positions.astype(jnp.float32)[..., None] \
        * jnp.asarray(inv, jnp.float32)
    return jnp.cos(angle) * scale, jnp.sin(angle) * scale


def apply_rotary(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate the first ``2 · cos.shape[-1]`` dimensions of each head of
    ``x`` [..., heads, head_dim]: dimension *i* pairs with *i + rot / 2*
    (the published implementation's ``rotate_half``); the rest pass."""
    half = cos.shape[-1]
    x32 = x.astype(jnp.float32)
    a, b, rest = x32[..., :half], x32[..., half:2 * half], x32[..., 2 * half:]
    c, s = cos[..., None, :], sin[..., None, :]
    return jnp.concatenate([a * c - b * s, b * c + a * s, rest],
                           axis=-1).astype(x.dtype)


@device_scope("moe.route")
def route(u2d: jax.Array, router: jax.Array, cfg: SWAMoEConfig) -> tuple:
    """``u2d`` [N, h] -> (expert ids [N, k], weights [N, k] float32), the
    logits in float32. ``softmax_topk``: a softmax over all ``num_experts``,
    the k largest chosen; ``topk_softmax``: the k largest LOGITS chosen, a
    softmax over those k alone. Then their scores over their sum
    (``norm_topk_prob``; it changes nothing after a softmax over the
    chosen), times the routed scaling."""
    logits = jnp.einsum("nh,he->ne", u2d.astype(jnp.float32),
                        router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if cfg.router_scoring == "topk_softmax":
        top, ids = jax.lax.top_k(logits, cfg.num_experts_per_tok)
        picked = jax.nn.softmax(top, axis=-1)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        picked, ids = jax.lax.top_k(scores, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        picked = picked / picked.sum(-1, keepdims=True)
    return ids, picked * cfg.moe_routed_scaling_factor


def tile_rows(cfg: SWAMoEConfig) -> int:
    """Rows a tile of the sorted (token, expert) pairs holds (one expert a
    tile): the sublane tile of the served dtype, 16 rows of bfloat16 and 8
    of float32. The grouped products are bound by the read of an expert's
    matrices, which a larger tile does not shrink: it only pads more."""
    return 32 // jnp.dtype(cfg.dtype).itemsize


def pass_rows(cfg: SWAMoEConfig, tokens: int) -> int:
    """Rows one pass of the grouped products takes when ``tokens`` tokens
    are routed: what a uniform router sends each held expert plus the half
    tile its run is expected to be padded by, in whole tiles, for every
    held expert — with 32 of 256 experts held and 10 a token, 32 x 16 rows
    for a decode step of 64 rows (2.5 rows an expert) and 32 x 32 for a
    512-token chunk (20); with all 64 held and 6 a token, 64 x 16 for 48
    rows (4.5) and 64 x 64 for a chunk (48 rows an expert are three whole
    tiles: without the half tile every chunk's padding spills into a
    second pass in every layer, 3.2 ms of a 25 ms chunk on the chip:
    PERF.md section 6, PR 38). Rows past the last held one cost their
    gather and scatter-add and nothing else, so the pass is no larger than
    that; a pass is a turn of a loop that ends after the last held row, so
    a router that sends every token here takes more turns and drops none.
    (Tile, pass and the prefill's key block were swept on the chip:
    PERF.md section 6, PR 35.)"""
    tile = tile_rows(cfg)
    per_expert = -(-tokens * cfg.num_experts_per_tok // cfg.num_experts)
    return cfg.experts_held * -(-(per_expert + tile // 2) // tile) * tile


def held_experts(u2d: jax.Array, ids: jax.Array, weights: jax.Array,
                 moe: dict, layer: jax.Array, cfg: SWAMoEConfig,
                 pass_rows: int, kernel_name: str, glu=None) -> tuple:
    """The held experts' weighted sum for every token, [N, h] float32, the
    rows each held expert got [held], and the passes the loop took.
    ``glu`` (gate product, up product) -> their combination, for a family
    whose gated MLP is not plain ``act(gate) · up``.

    ``moe`` holds the STACKED experts of the layer's kind (``[layers, held,
    ...]``) and ``layer`` says which: a tile's matrix is read at index
    ``layer · held + expert`` of the stack seen as ``[layers · held, ...]``
    (a bitcast), so no layer of the stack is ever sliced out in front of
    the kernel. ``ids`` < 0 marks a token that routes nowhere (an empty
    decode slot, the tail of a ragged chunk)."""
    n, k = ids.shape
    held, tile = cfg.experts_held, tile_rows(cfg)
    plan = held_share.plan_rows(ids, cfg.first_expert_held, held, tile,
                                pass_rows)
    w_flat = weights.reshape(-1).astype(jnp.float32)
    stacks = {name: moe[name].reshape((-1,) + moe[name].shape[2:])
              for name in ("experts_gate", "experts_up", "experts_down")}
    gmm = lambda lhs, name, experts, n_tiles: grouped_matmul.moe_gmm(  # noqa: E731
        lhs, stacks[name], experts, n_tiles, tile=tile,
        out_dtype=jnp.float32, name=kernel_name)

    act = activation(cfg)
    glu = glu or (lambda g, u: act(g) * u)

    def body(state):
        c, y = state
        tok, wt, _, experts, n_tiles, xs = held_share.pass_inputs(
            c, u2d, w_flat, plan, k, pass_rows, tile)
        experts = experts + layer * held
        a = glu(gmm(xs, "experts_gate", experts, n_tiles),
                gmm(xs, "experts_up", experts, n_tiles)).astype(u2d.dtype)
        o = gmm(a, "experts_down", experts, n_tiles)
        with device_scope("moe.route"):
            return c + 1, y.at[tok].add(o * wt[:, None])

    with device_scope("moe.experts"):
        _, y = jax.lax.while_loop(
            lambda s: s[0] < plan["n_passes"], body,
            (jnp.int32(0), jnp.zeros(u2d.shape, jnp.float32)))
    return y, plan["rows_held"], plan["n_passes"]


def activation(cfg: SWAMoEConfig):
    """``hidden_act`` of every gated MLP: SiLU, or ReLU (ReGLU experts)."""
    return {"silu": jax.nn.silu, "relu": jax.nn.relu}[cfg.hidden_act]


def gated_mlp(u: jax.Array, gate: jax.Array, up: jax.Array,
              down: jax.Array, act=jax.nn.silu) -> jax.Array:
    """``down(act(gate u) * up u)``, float32 accumulation, ``u``'s dtype
    between the products."""
    g = jnp.einsum("...h,hf->...f", u, gate,
                   preferred_element_type=jnp.float32)
    v = jnp.einsum("...h,hf->...f", u, up,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("...f,fh->...h", (act(g) * v).astype(u.dtype),
                      down, preferred_element_type=jnp.float32)
