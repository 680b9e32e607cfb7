"""The short-convolution decoder family (gated depth-wise convolution
layers beside grouped-query attention over sparse experts): parameters, the
parts of a layer that need no cache, and the whole-sequence forward.
``docs/conv_moe.md`` has the equations with the source of each and every
reading that is ASSUMED.

Pre-norm layers, RMS norm (``norm_eps``), no bias anywhere, the head TIED
to the embedding (ASSUMED). Layer ℓ, input ``h``: ``u = norm(h;
operator_norm)``, ``h ← h + Op(u)``, ``f = norm(h; ffn_norm)``, ``h ← h +
FF(f)``.

- *conv layer*: ``[B, C, x] = split₃(u W_in)`` (ASSUMED: in that order);
  ``z = B ⊙ x``; ``c_t = Σ_j w[j] ⊙ z_{t − (K − 1) + j}`` over the ``K =
  conv_L_cache`` taps, depth-wise and causal, zeros before the sequence's
  first token; ``Op(u) = (C ⊙ c) W_out``. All a layer remembers of a
  sequence is its last ``K − 1`` values of ``z`` (the TAIL).
- *full_attention layer*: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key-value heads of ``hidden / heads``; an RMS norm
  a head on queries and on keys (``q_norm``, ``k_norm``) BEFORE the
  rotation; rotary over the whole head, half-split (ASSUMED:
  ``models/swa_moe/model.py:apply_rotary``); causal softmax of ``q·k /
  sqrt(head_dim)``.
- *feed-forward*: a gated SiLU MLP of ``intermediate_size`` in the leading
  ``num_dense_layers`` layers; elsewhere ``s = sigmoid(f W_g)`` in float32,
  the ``num_experts_per_tok`` largest of ``s + expert_bias`` chosen (the
  bias selects and weighs nothing), weights ``s[chosen] / (Σ s[chosen] +
  1e-6)`` (ASSUMED: the 1e-6) times ``routed_scaling_factor``, over every
  expert of the layer (``models/swa_moe/model.py:held_experts``). No shared
  expert.

Layers of one shape are stacked (``ConvMoEConfig.kind_of``): the tree is
``{"embed", "final_norm", "<kind>": {...leaves [layers, ...]}}`` — no head
leaf. What walks the layers with their caches is ``serving/conv_moe.py``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from fleetx_tpu.models.conv_moe.config import CONV, ConvMoEConfig
from fleetx_tpu.models.mla_moe import moe as held_share
from fleetx_tpu.models.swa_moe import model as shared

#: leaves kept in float32 whatever ``cfg.dtype`` is: every norm's weight,
#: the router and its selection bias
F32_GROUPS = frozenset({"operator_norm", "ffn_norm", "final_norm"})
F32_LEAVES = frozenset({"router", "expert_bias", "q_norm", "k_norm"})
#: ASSUMED: what the chosen scores' sum is kept from zero by
ROUTE_SUM_EPS = 1e-6

rms_norm = shared.rms_norm
gated_mlp = shared.gated_mlp
apply_rotary = shared.apply_rotary


# ------------------------------------------------------------------ the tree
def param_shapes(cfg: ConvMoEConfig) -> dict:
    """The parameter tree as shapes: leaf -> tuple."""
    h, hd = cfg.hidden_size, cfg.head_dim
    nh, kv = cfg.num_attention_heads, cfg.num_key_value_heads
    f, e = cfg.moe_intermediate_size, cfg.num_experts
    tree = {"embed": {"tokens": (cfg.vocab_size, h)},
            "final_norm": {"scale": (h,)}}
    for kind, n in cfg.kinds().items():
        op, mlp = kind.split("_")
        layer = {"operator_norm": {"scale": (n, h)},
                 "ffn_norm": {"scale": (n, h)}}
        if op == CONV:
            # the taps a [taps, channels] matrix: channels are lanes
            layer["conv"] = {"in": (n, h, 3 * h),
                             "taps": (n, cfg.conv_L_cache, h),
                             "out": (n, h, h)}
        else:
            # queries and keys a [head_dim, hidden] matrix a head, hidden
            # minor (models/swa_moe/model.py has the reason)
            layer["attn"] = {"q": (n, nh, hd, h), "k": (n, kv, hd, h),
                             "v": (n, h, kv * hd), "out": (n, nh, hd, h),
                             "q_norm": (n, hd), "k_norm": (n, hd)}
        if mlp == "dense":
            i = cfg.intermediate_size
            layer["mlp"] = {"gate": (n, h, i), "up": (n, h, i),
                            "down": (n, i, h)}
        else:
            layer["moe"] = {
                "router": (n, h, e), "expert_bias": (n, e),
                "experts_gate": (n, e, h, f), "experts_up": (n, e, h, f),
                "experts_down": (n, e, f, h)}
        tree[kind] = layer
    return tree


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def served_dtype(path: tuple, cfg: ConvMoEConfig):
    """The dtype the serving programs hold the leaf at ``path`` in."""
    keys = {getattr(k, "key", k) for k in path}
    if keys & F32_GROUPS or keys & F32_LEAVES:
        return jnp.dtype(jnp.float32)
    return jnp.dtype(cfg.dtype)


def served_template(cfg: ConvMoEConfig):
    """The abstract tree the serving programs take: each leaf with its
    shape and the dtype it is served in."""
    return jax.tree_util.tree_map_with_path(
        lambda path, shape: jax.ShapeDtypeStruct(
            shape, served_dtype(path, cfg)),
        param_shapes(cfg), is_leaf=_is_shape)


def count_params(cfg: ConvMoEConfig) -> int:
    return sum(math.prod(s) for s in jax.tree.leaves(
        param_shapes(cfg), is_leaf=_is_shape))


def init_params(cfg: ConvMoEConfig, key: jax.Array,
                served: bool = False) -> dict:
    """Seeded parameters: N(0, 0.02) matrices (the selection bias too),
    unit norm scales, taps of 1 + 0.1 N(0, 1) (``initializer_range`` is not
    in the published config; a depth-wise convolution's taps are of the
    size of 1 / sqrt(taps), not of a wide product's matrix), in
    ``cfg.param_dtype`` — or, ``served``, each leaf in the dtype
    the serving programs hold it in (under ``jax.jit`` the draw and the
    cast fuse: no float32 copy of a tree served in bfloat16)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=_is_shape)
    keys = jax.random.split(key, len(flat))

    def make(path, shape, k):
        dtype = served_dtype(path, cfg) if served else cfg.param_dtype
        names = {getattr(p, "key", p) for p in path}
        if names & F32_GROUPS or names & {"q_norm", "k_norm"}:
            return jnp.ones(shape, dtype)
        noise = jax.random.normal(k, shape, jnp.float32)
        return (1.0 + 0.1 * noise if "taps" in names
                else 0.02 * noise).astype(dtype)

    return treedef.unflatten([make(p, s, k)
                              for (p, s), k in zip(flat, keys)])


# --------------------------------------------------------------------- parts
def rotary_tables(cfg: ConvMoEConfig, positions: jax.Array) -> tuple:
    """``(cos, sin)`` float32 ``[..., head_dim / 2]`` at ``positions``:
    plain rotary (``rope_type: default``) over the whole head."""
    hd = cfg.head_dim
    inv = 1.0 / float(cfg.rope_parameters["rope_theta"]) ** (
        np.arange(0, hd, 2, dtype=np.float64) / hd)
    angle = positions.astype(jnp.float32)[..., None] \
        * jnp.asarray(inv, jnp.float32)
    return jnp.cos(angle), jnp.sin(angle)


def conv_project(u: jax.Array, lp: dict) -> jax.Array:
    """``u`` [rows, h] -> ``u W_in`` [rows, 3 h], in ``u``'s dtype."""
    return jnp.einsum("sh,hc->sc", u, lp["in"])


def conv_gates(bcx: jax.Array, cfg: ConvMoEConfig) -> tuple:
    """``u W_in`` -> ``(z = B ⊙ x in its dtype — what the convolution reads
    and the tail keeps —, C float32)``. ASSUMED: the in-projection's output
    splits into ``B, C, x`` in that order."""
    h = cfg.hidden_size
    b32 = bcx.astype(jnp.float32)
    return (b32[:, :h] * b32[:, 2 * h:]).astype(bcx.dtype), b32[:, h:2 * h]


def conv_taps(window: jax.Array, taps: jax.Array, axis: int) -> jax.Array:
    """``window`` (the last ``K`` values of ``z`` along ``axis``, the
    newest last) times the ``K`` taps (broadcast against it), summed over
    ``axis`` in float32: tap ``K − 1`` weighs the newest value."""
    return (window.astype(jnp.float32) * taps.astype(jnp.float32)).sum(axis)


def conv_sequence(ext: jax.Array, taps: jax.Array) -> jax.Array:
    """``ext`` [K − 1 + rows, h] (the tail, then the rows' ``z``) -> the
    convolution at every row, [rows, h] float32."""
    k = taps.shape[0]
    rows = ext.shape[0] - (k - 1)
    return conv_taps(jnp.stack([ext[j:j + rows] for j in range(k)], axis=1),
                     taps, axis=1)


def attention_project(u: jax.Array, lp: dict, cfg: ConvMoEConfig,
                      cos: jax.Array, sin: jax.Array) -> tuple:
    """``u`` [rows, h] -> ``(q [rows, heads, hd], k [rows, kv, hd], v [rows,
    kv · hd])``: queries and keys normed a head (``q_norm``, ``k_norm``:
    RMS over the head's values) and THEN rotated by the rows' ``cos`` /
    ``sin`` [rows, hd / 2]."""
    dt = u.dtype
    q = jnp.einsum("sh,ndh->snd", u, lp["q"])
    k = jnp.einsum("sh,ndh->snd", u, lp["k"])
    v = jnp.einsum("sh,hn->sn", u, lp["v"])
    q = rms_norm(q, lp["q_norm"], cfg.norm_eps, dt)
    k = rms_norm(k, lp["k_norm"], cfg.norm_eps, dt)
    return apply_rotary(q, cos, sin), apply_rotary(k, cos, sin), v


def route(f2d: jax.Array, moe: dict, cfg: ConvMoEConfig) -> tuple:
    """``f2d`` [N, h] -> (expert ids [N, k], weights [N, k] float32): the
    sigmoid router with a selection bias
    (``models/mla_moe/moe.py:route``), the chosen scores over their sum +
    `ROUTE_SUM_EPS`."""
    bias = moe["expert_bias"] if cfg.use_expert_bias \
        else jnp.zeros_like(moe["expert_bias"])
    ids, weights, _ = held_share.route(
        f2d, moe["router"], bias, cfg.num_experts_per_tok,
        cfg.routed_scaling_factor, cfg.norm_topk_prob, eps=ROUTE_SUM_EPS)
    return ids, weights


def logits(params: dict, x: jax.Array) -> jax.Array:
    """The head on ``x`` [rows, h] -> float32 ``[rows, vocab]``: ASSUMED
    tied to the embedding (the family's published convention)."""
    return jnp.einsum("bh,vh->bv", x, params["embed"]["tokens"],
                      preferred_element_type=jnp.float32)


# ------------------------------------------------------- the whole sequence
def forward(params: dict, cfg: ConvMoEConfig, tokens: jax.Array
            ) -> jax.Array:
    """One sequence ``tokens`` [S] from its first token, no cache: float32
    logits ``[S, vocab]``. ``params`` in ``cfg.dtype`` but the leaves
    `served_dtype` keeps in float32 (the tree the serving programs take)."""
    (S,) = tokens.shape
    dt, hd = cfg.dtype, cfg.head_dim
    kv, grp = cfg.num_key_value_heads, \
        cfg.num_attention_heads // cfg.num_key_value_heads
    cos, sin = rotary_tables(cfg, jnp.arange(S))
    causal = jnp.tril(jnp.ones((S, S), bool))
    pass_rows = shared.pass_rows(cfg, S)
    x = params["embed"]["tokens"][tokens]
    for kind, lo, n, _ in cfg.runs():
        stack = params[kind]
        op, mlp = kind.split("_")
        for i in range(lo, lo + n):
            lp = jax.tree.map(lambda w: w[i], {
                k: v for k, v in stack.items() if k != "moe"})
            u = rms_norm(x, lp["operator_norm"]["scale"], cfg.norm_eps, dt)
            if op == CONV:
                z, gate = conv_gates(conv_project(u, lp["conv"]), cfg)
                ext = jnp.concatenate([jnp.zeros(
                    (cfg.conv_L_cache - 1, z.shape[1]), z.dtype), z])
                c = conv_sequence(ext, lp["conv"]["taps"])
                y = jnp.einsum("sc,ch->sh", (gate * c).astype(dt),
                               lp["conv"]["out"])
            else:
                q, k, v = attention_project(u, lp["attn"], cfg, cos, sin)
                s = jnp.einsum("skgd,tkd->kgst", q.reshape(S, kv, grp, hd), k,
                               preferred_element_type=jnp.float32
                               ) / math.sqrt(hd)
                p = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
                o = jnp.einsum("kgst,tkd->skgd", p.astype(dt),
                               v.reshape(S, kv, hd),
                               preferred_element_type=jnp.float32)
                y = jnp.einsum("snd,ndh->sh", o.reshape(S, -1, hd).astype(dt),
                               lp["attn"]["out"])
            x = x + y
            f = rms_norm(x, lp["ffn_norm"]["scale"], cfg.norm_eps, dt)
            if mlp == "dense":
                y = gated_mlp(f, lp["mlp"]["gate"], lp["mlp"]["up"],
                              lp["mlp"]["down"])
            else:
                moe = {k: v[i] for k, v in stack["moe"].items()
                       if not k.startswith("experts_")}
                ids, weights = route(f, moe, cfg)
                y, _, _ = shared.held_experts(
                    f, ids, weights, stack["moe"], i, cfg, pass_rows,
                    "moe_gmm_prefill")
            x = x + y.astype(dt)
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps, dt)
    return logits(params, x)
