"""The hybrid decoder family (gated-delta-rule layers beside latent
attention over sparse experts): parameters and the parts of a layer that
need no cache. ``docs/gdn_mla.md`` has the equations with the source of
each and every reading that is ASSUMED.

Block (``layernorm_type: pre_post``): ``x ← x + N_post(F(N_pre(x)))`` for
the token mixer and again for the feed-forward part, four norms a layer.
``N`` is the model's norm everywhere (layers, the latents' norms, the final
norm): an RMS norm whose per-channel scale is ``layernorm_gating_weight ·
sigmoid(w)`` (ASSUMED: ``ZeroCenteredGatedNorm``; 1 at ``w = 0``).

- *Linear-attention layer*: ``[q; k; v]``, ``z`` and ``[a; b]`` by
  bias-free products of ``u = N_pre(x)``; a causal depth-wise convolution of
  ``linear_conv_kernel_dim`` taps over ``[q; k; v]``, then SiLU; ``q, k``
  L2-normalised a head, ``q`` scaled by ``dk^-1/2``; ``β = sigmoid(b)``, ``g
  = −exp(A_log) · softplus(a + dt_bias)`` (``α = exp g``); the rule
  (``ops/gated_delta.py``); the output's RMS norm over a head's values with
  scale ``1 + w``, times ``linear_sigmoid_gate_scale · sigmoid(z)``
  (ASSUMED), then the output product.
- *Latent-attention layer*: DeepSeek-V3's (``models/mla_moe``), the
  projections as separate leaves so that a decode step can absorb ``W_uk``
  into the query and ``W_uv`` into the output without slicing a weight;
  rotary on neighbouring pairs (``rope_interleave``), written here with a
  pair's members in the two halves of the rotary part — of queries AND of
  the cached key, so every score is what it was; YaRN frequencies, the
  softmax scale times ``m²`` (``use_mla_scaling_factor``); ASSUMED
  (``gated_attention``): the heads' output times ``sigmoid(W_g u)``.
- *Feed-forward*: a gated MLP in the leading ``first_k_dense_replace``
  layers; elsewhere the sigmoid router with a selection bias
  (``models/mla_moe/moe.py:route``) over the experts HELD here
  (``models/swa_moe/model.py:held_experts``) plus one shared expert.
  ASSUMED (``swiglu_limit``): the gate product clamped to ≤ limit and the
  up product to [−limit, limit] before ``silu(gate) · up``.

Layers of one shape are stacked (``GDNMLAConfig.kind_of``): the tree is
``{"embed", "head", "final_norm", "<kind>": {...leaves [layers, ...]}}``.
What walks the layers with their caches is ``serving/gdn_mla.py``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from fleetx_tpu.models.gdn_mla.config import LATENT, GDNMLAConfig

#: leaves kept in float32 whatever ``cfg.dtype`` is: every norm's weight,
#: the router and its selection bias, the decay's two vectors
F32_GROUPS = frozenset({"attn_norm", "attn_post_norm", "mlp_norm",
                        "mlp_post_norm", "final_norm"})
F32_LEAVES = frozenset({"router", "selection_bias", "q_norm", "kv_norm",
                        "o_norm", "A_log", "dt_bias"})
NORMS = ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm")


# ------------------------------------------------------------------ the tree
def _linear_shapes(c: GDNMLAConfig, n: int) -> dict:
    h = c.hidden_size
    hv, dv = c.linear_num_value_heads, c.linear_value_head_dim
    return {"qkv": (n, h, c.conv_channels), "z": (n, h, hv * dv),
            "ab": (n, h, 2 * hv),
            "conv": (n, c.linear_conv_kernel_dim, c.conv_channels),
            "A_log": (n, hv), "dt_bias": (n, hv), "o_norm": (n, dv),
            "out": (n, hv * dv, h)}


def _latent_shapes(c: GDNMLAConfig, n: int) -> dict:
    h, nh = c.hidden_size, c.num_attention_heads
    shapes = {"q_a": (n, h, c.q_lora_rank), "q_norm": (n, c.q_lora_rank),
              "q_bn": (n, c.q_lora_rank, nh, c.qk_nope_head_dim),
              "q_br": (n, c.q_lora_rank, nh, c.qk_rope_head_dim),
              "kv_a": (n, h, c.latent_width),
              "kv_norm": (n, c.kv_lora_rank),
              "k_b": (n, c.kv_lora_rank, nh, c.qk_nope_head_dim),
              "v_b": (n, c.kv_lora_rank, nh, c.v_head_dim),
              "out": (n, nh, c.v_head_dim, h)}
    if c.gated_attention:
        shapes["gate"] = (n, h, nh * c.v_head_dim)
    return shapes


def param_shapes(cfg: GDNMLAConfig) -> dict:
    """The parameter tree as shapes: leaf -> tuple."""
    h, f, held = cfg.hidden_size, cfg.moe_intermediate_size, cfg.experts_held
    tree = {"embed": {"tokens": (cfg.vocab_size, h)},
            "head": {"kernel": (h, cfg.vocab_size)},
            "final_norm": {"w": (h,)}}
    for kind, n in cfg.kinds().items():
        mixer, mlp = kind.split("_")
        layer = {name: {"w": (n, h)} for name in NORMS}
        layer["mixer"] = (_latent_shapes if mixer == LATENT
                          else _linear_shapes)(cfg, n)
        if mlp == "dense":
            i = cfg.intermediate_size
            layer["mlp"] = {"gate": (n, h, i), "up": (n, h, i),
                            "down": (n, i, h)}
        else:
            layer["moe"] = {
                "router": (n, h, cfg.n_routed_experts),
                "selection_bias": (n, cfg.n_routed_experts),
                "experts_gate": (n, held, h, f), "experts_up": (n, held, h, f),
                "experts_down": (n, held, f, h)}
            if cfg.n_shared_experts:
                layer["moe"].update(shared_gate=(n, h, f), shared_up=(n, h, f),
                                    shared_down=(n, f, h))
        tree[kind] = layer
    return tree


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def served_dtype(path: tuple, cfg: GDNMLAConfig):
    """The dtype the serving programs hold the leaf at ``path`` in."""
    keys = {getattr(k, "key", k) for k in path}
    if keys & F32_GROUPS or keys & F32_LEAVES:
        return jnp.dtype(jnp.float32)
    return jnp.dtype(cfg.dtype)


def served_template(cfg: GDNMLAConfig):
    """The abstract tree the serving programs take: each leaf with its
    shape and the dtype it is served in."""
    return jax.tree_util.tree_map_with_path(
        lambda path, shape: jax.ShapeDtypeStruct(
            shape, served_dtype(path, cfg)),
        param_shapes(cfg), is_leaf=_is_shape)


def count_params(cfg: GDNMLAConfig) -> int:
    return sum(math.prod(s) for s in jax.tree.leaves(
        param_shapes(cfg), is_leaf=_is_shape))


def init_params(cfg: GDNMLAConfig, key: jax.Array,
                served: bool = False) -> dict:
    """Seeded parameters, N(0, 0.02) every leaf (the norms' weights are
    zero-centred: a scale near 1; ``initializer_range`` is not in the
    published config), in ``cfg.param_dtype`` — or, ``served``, each leaf in
    the dtype the serving programs hold it in (under ``jax.jit`` the draw
    and the cast fuse: no float32 copy of a tree served in bfloat16)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=_is_shape)
    keys = jax.random.split(key, len(flat))

    def make(path, shape, k):
        dtype = served_dtype(path, cfg) if served else cfg.param_dtype
        return (0.02 * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    return treedef.unflatten([make(p, s, k)
                              for (p, s), k in zip(flat, keys)])


# --------------------------------------------------------------------- parts
def norm_scale(w: jax.Array, cfg: GDNMLAConfig) -> jax.Array:
    """ASSUMED (``ZeroCenteredGatedNorm``): the norm's per-channel scale,
    ``layernorm_gating_weight · sigmoid(w)`` — 1 at ``w = 0``."""
    return cfg.layernorm_gating_weight * jax.nn.sigmoid(w)


def norm(x: jax.Array, w: jax.Array, cfg: GDNMLAConfig, dtype) -> jax.Array:
    """The model's norm in float32: ``x / rms(x)`` times ``norm_scale(w)``,
    cast to ``dtype``."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True)
                            + cfg.rms_norm_eps)
    return (y * norm_scale(w, cfg)).astype(dtype)


def glu(cfg: GDNMLAConfig):
    """``(gate product, up product) -> silu(gate) · up`` with the clamp of
    ``swiglu_limit`` (ASSUMED: gate ≤ limit, up in [−limit, limit])."""
    limit = float(cfg.swiglu_limit or 0.0)

    def combine(g, u):
        if limit:
            g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
        return jax.nn.silu(g) * u

    return combine


def gated_mlp(u: jax.Array, gate: jax.Array, up: jax.Array, down: jax.Array,
              combine) -> jax.Array:
    """``down(combine(gate u, up u))``, float32 accumulation, ``u``'s dtype
    between the products."""
    g = jnp.einsum("...h,hf->...f", u, gate,
                   preferred_element_type=jnp.float32)
    v = jnp.einsum("...h,hf->...f", u, up,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("...f,fh->...h", combine(g, v).astype(u.dtype), down,
                      preferred_element_type=jnp.float32)


# ------------------------------------------------------------------- rotary
def rotary_frequencies(cfg: GDNMLAConfig) -> np.ndarray:
    """Inverse frequencies ``[qk_rope_head_dim / 2]`` (float64, host):
    plain rotary, or YaRN — the low frequencies (whose wavelength passes
    the original context) divided by ``factor``, the high ones kept, a
    linear ramp between ``beta_fast`` and ``beta_slow`` turns."""
    rot, base = cfg.qk_rope_head_dim, float(cfg.rope_theta)
    pos_freqs = base ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    rs = cfg.rope_scaling
    if not rs:
        return 1.0 / pos_freqs
    factor = float(rs["factor"])
    orig = float(rs["original_max_position_embeddings"])

    def correction_dim(turns):
        return rot * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rs["beta_slow"]))), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return (1.0 / (factor * pos_freqs)) * ramp + (1.0 / pos_freqs) * (1 - ramp)


def softmax_scale(cfg: GDNMLAConfig) -> float:
    """``qk_head_dim^-1/2``, times ``m²`` with ``m = 0.1 · mscale_all_dim ·
    ln(factor) + 1`` under ``use_mla_scaling_factor``."""
    scale = float(cfg.qk_head_dim) ** -0.5
    rs = cfg.rope_scaling
    if cfg.use_mla_scaling_factor and rs:
        m = 0.1 * float(rs.get("mscale_all_dim", 1)) \
            * math.log(float(rs["factor"])) + 1.0
        scale *= m * m
    return scale


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rotary_tables(cfg: GDNMLAConfig, positions: jax.Array) -> tuple:
    """``(cos, sin)`` float32 ``[..., rot / 2]`` at ``positions``, times
    YaRN's attention factor ``m(mscale) / m(mscale_all_dim)`` (1 with the
    published ``mscale == mscale_all_dim``)."""
    angle = positions.astype(jnp.float32)[..., None] \
        * jnp.asarray(rotary_frequencies(cfg), jnp.float32)
    rs = cfg.rope_scaling or {}
    factor = float(rs.get("factor", 1))
    ratio = _yarn_mscale(factor, float(rs.get("mscale", 1))) \
        / _yarn_mscale(factor, float(rs.get("mscale_all_dim", 1)))
    return jnp.cos(angle) * ratio, jnp.sin(angle) * ratio


def rotate_pairs(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate ``x`` [..., rot] whose NEIGHBOURING values are a pair
    (``rope_interleave``); the result holds the pairs' first members in its
    first half and the second in its second — the same permutation for
    queries and keys, so their products are unchanged."""
    x32 = x.astype(jnp.float32)
    a, b = x32[..., 0::2], x32[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


# -------------------------------------------------------- the linear mixer
def linear_project(u: jax.Array, lp: dict, cfg: GDNMLAConfig) -> tuple:
    """``u`` [rows, h] -> ``(qkv [rows, channels] in u's dtype, z [rows, Hv,
    dv] float32, g (log decay) [rows, Hv], beta [rows, Hv])``."""
    hv, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    qkv = jnp.einsum("sh,hc->sc", u, lp["qkv"])
    z = jnp.einsum("sh,hc->sc", u, lp["z"],
                   preferred_element_type=jnp.float32).reshape(-1, hv, dv)
    ab = jnp.einsum("sh,hc->sc", u, lp["ab"],
                    preferred_element_type=jnp.float32)
    g = -jnp.exp(lp["A_log"]) * jax.nn.softplus(ab[:, :hv] + lp["dt_bias"])
    return qkv, z, g, jax.nn.sigmoid(ab[:, hv:])


def conv_taps(window: jax.Array, taps: jax.Array, axis: int = -2
              ) -> jax.Array:
    """``window`` (the last ``K`` inputs along ``axis``, the newest last)
    times the ``K`` taps (broadcast against it) -> SiLU of their sum over
    ``axis``, in float32: tap ``K − 1`` weighs the newest input."""
    y = (window.astype(jnp.float32) * taps.astype(jnp.float32)).sum(axis)
    return jax.nn.silu(y)


def split_qkv(y: jax.Array, cfg: GDNMLAConfig) -> tuple:
    """The convolution's output [rows, channels] (float32) -> ``(q [rows,
    Hk, dk], k, v [rows, Hv, dv])``: ``q, k`` L2-normalised a head, ``q``
    times ``dk^-1/2``."""
    from fleetx_tpu.ops.gated_delta import l2_normalise

    hk, dk = cfg.linear_num_key_heads, cfg.linear_key_head_dim
    hv, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    q = l2_normalise(y[:, :hk * dk].reshape(-1, hk, dk)) * dk ** -0.5
    k = l2_normalise(y[:, hk * dk:2 * hk * dk].reshape(-1, hk, dk))
    return q, k, y[:, 2 * hk * dk:].reshape(-1, hv, dv)


def output_gate(z: jax.Array, cfg: GDNMLAConfig) -> jax.Array:
    """ASSUMED (``linear_gating_type``): the linear mixer's output gate,
    ``linear_sigmoid_gate_scale · sigmoid(z)``."""
    return cfg.linear_sigmoid_gate_scale * jax.nn.sigmoid(z)


def linear_output(o: jax.Array, z: jax.Array, lp: dict, cfg: GDNMLAConfig,
                  dtype) -> jax.Array:
    """The rule's output ``o`` [rows, Hv, dv] (float32) -> ``[rows, h]``:
    RMS norm over a head's values with scale ``1 + w``, times
    ``output_gate(z)``, the output product."""
    y = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True)
                          + cfg.linear_attn_o_norm_eps) * (1.0 + lp["o_norm"])
    y = (y * output_gate(z, cfg)).astype(dtype).reshape(o.shape[0], -1)
    return jnp.einsum("sc,ch->sh", y, lp["out"])


# -------------------------------------------------------- the latent mixer
def latent_project(u: jax.Array, lp: dict, cfg: GDNMLAConfig,
                   positions: jax.Array) -> tuple:
    """``u`` [rows, h] at ``positions`` [rows] -> ``(q_n [rows, heads, dn],
    q_r [rows, heads, dr] rotated, row [rows, latent_width]: the normed
    latent beside the one rotated key — what the cache holds a token)``."""
    dt = u.dtype
    cos, sin = rotary_tables(cfg, positions)
    cq = norm(jnp.einsum("sh,hr->sr", u, lp["q_a"]), lp["q_norm"], cfg, dt)
    q_n = jnp.einsum("sr,rnd->snd", cq, lp["q_bn"])
    q_r = rotate_pairs(jnp.einsum("sr,rnd->snd", cq, lp["q_br"]),
                       cos[:, None, :], sin[:, None, :])
    kv = jnp.einsum("sh,hr->sr", u, lp["kv_a"])
    ckv = norm(kv[:, :cfg.kv_lora_rank], lp["kv_norm"], cfg, dt)
    k_r = rotate_pairs(kv[:, cfg.kv_lora_rank:], cos, sin)
    return q_n, q_r, jnp.concatenate([ckv, k_r], axis=-1)


def latent_output(o: jax.Array, u: jax.Array, lp: dict,
                  cfg: GDNMLAConfig) -> jax.Array:
    """The heads' outputs ``o`` [rows, heads, dv] -> ``[rows, h]``, through
    the assumed gate ``sigmoid(W_g u)`` where the tree has one."""
    if "gate" in lp:
        gate = jax.nn.sigmoid(jnp.einsum(
            "sh,hc->sc", u, lp["gate"], preferred_element_type=jnp.float32))
        o = (o.astype(jnp.float32)
             * gate.reshape(o.shape)).astype(u.dtype)
    return jnp.einsum("snd,ndh->sh", o.astype(u.dtype), lp["out"])
