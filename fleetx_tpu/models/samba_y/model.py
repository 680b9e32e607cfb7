"""The decoder-hybrid-decoder family ("SambaY", arXiv:2507.06607, with
differential attention, arXiv:2410.05258): parameters, the parts of a layer
that need no cache, and the whole-sequence forward. ``docs/samba_y.md`` has
the equations with the source of each and every reading that is ASSUMED.

Pre-norm layers, LayerNorm with weight and bias (``layer_norm_eps``), the
head TIED to the embedding, no position signal anywhere (the scan layers
carry order). Layer ℓ, input ``h``: ``u = LN₁(h)``, ``h ← h + Mixer(u)``,
``f = LN₂(h)``, ``h ← h + W_down(silu(g) ⊙ p)`` with ``[g; p] = W_gate_up
f`` (ASSUMED: in that order). The mixers (``SambaYConfig.kind_of``):

- *scan*: ``[x; z] = W_in u`` (ASSUMED order); ``x ← silu(conv(x) + b_c)``
  (causal, depth-wise, ``d_conv`` taps, zeros before the first token);
  ``[δ; B; C] = W_x x``; ``Δ = softplus(W_Δ δ + b_Δ)``; ``A = −exp(A_log)``;
  the selective scan (``ops/selective_scan.py``) gives ``y`` (with the
  ``D`` skip); out ``W_out(y ⊙ silu(z))``. The LAST scan layer also hands
  ``m = y`` on. All a layer remembers of a sequence: its state ``[N,
  inner]`` float32 and the last ``d_conv − 1`` inputs of the convolution
  (the TAIL).
- *window* / *full*: differential attention. ``Q`` (heads), ``K``, ``V``
  (key-value heads) from one product with bias. Query pair ``p``: ``q₁ =
  Q[2p]``, ``q₂ = Q[2p+1]``; its key-value pair ``r = p // 2``: ``k₁ =
  K[2r]``, ``k₂ = K[2r+1]``, ``v = [V[2r]; V[2r+1]]``; ``o_p = (softmax(q₁
  k₁ᵀ / √d) − λ softmax(q₂ k₂ᵀ / √d)) v``; ``λ = exp(λ_q1 · λ_k1) −
  exp(λ_q2 · λ_k2) + λ_init(ℓ)``; ``o_p ← RMSNorm(o_p) · (1 − λ_init(ℓ))``;
  out product with bias. A window layer sees the last ``sliding_window``
  keys (the token itself among them), the full layer every earlier one.
- *cross*: the same with its OWN queries and the full layer's K and V: no
  key or value product.
- *gmu* (gated memory unit): ``W₂(m ⊙ silu(W₁ u))``, ``m`` the last scan
  layer's at the same token.

How the two score maps reach one kernel (`diff_queries`): a key-value pair
is 2 · head_dim lanes ``[K[2r]; K[2r+1]]``, four queries read it, each zero
in the half it does not score — exact, and the value product then yields
the whole 2 · head_dim-wide ``v`` for every map.

Layers of one kind are stacked: the tree is ``{"embed", "final_norm",
"scan", "window", "full", "gmu", "cross": {...leaves [layers, ...]}}`` — no
head leaf. What walks the layers with their caches is
``serving/samba_y.py``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from fleetx_tpu.models import scan_mixer
from fleetx_tpu.models.samba_y.config import (CROSS, FULL, GMU, SCAN, WINDOW,
                                              SambaYConfig)
# the scan mixer is one definition (``models/scan_mixer.py``), shared with
# the family whose scan layers norm their step, ``B`` and ``C``; its parts
# under the names this module has always had
from fleetx_tpu.models.scan_mixer import (conv_act, conv_sequence,  # noqa: F401
                                          conv_taps, ssm_decay, ssm_in,
                                          ssm_out, ssm_params)

#: leaves kept in float32 whatever ``cfg.dtype`` is: every norm's weight
#: and bias, the scan's own vectors, the λ vectors
F32_GROUPS = frozenset({"norm1", "norm2", "final_norm"})
F32_LEAVES = scan_mixer.F32_LEAVES | {
    "subln", "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"}
_NEG = -1e30


# ------------------------------------------------------------------ the tree
def param_shapes(cfg: SambaYConfig) -> dict:
    """The parameter tree as shapes: leaf -> tuple. Matrices ``[layers, in,
    out]``; the scan's per-state leaves STATE-major (``A_log`` [layers, N,
    inner]: ``ops/selective_scan.py`` has the reason)."""
    h, hd, f = cfg.hidden_size, cfg.head_dim, cfg.intermediate_size
    q, kv = cfg.num_attention_heads * hd, cfg.kv_lanes
    di = cfg.d_inner
    tree = {"embed": {"tokens": (cfg.vocab_size, h)},
            "final_norm": {"scale": (h,), "bias": (h,)}}
    for kind, layers in cfg.kinds().items():
        L = layers
        layer = {"norm1": {"scale": (L, h), "bias": (L, h)},
                 "norm2": {"scale": (L, h), "bias": (L, h)},
                 "mlp": {"gate_up": (L, h, 2 * f), "down": (L, f, h)}}
        if kind == SCAN:
            layer["ssm"] = scan_mixer.leaf_shapes(L, h, cfg)
        elif kind == GMU:
            layer["gmu"] = {"in": (L, h, di), "out": (L, di, h)}
        else:
            width = q if kind == CROSS else q + 2 * kv
            layer["attn"] = {
                "qkv": (L, h, width), "qkv_bias": (L, width),
                "out": (L, q, h), "out_bias": (L, h),
                "lambda_q1": (L, hd), "lambda_k1": (L, hd),
                "lambda_q2": (L, hd), "lambda_k2": (L, hd),
                "subln": (L, 2 * hd)}
        tree[kind] = layer
    return tree


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def _names(path: tuple) -> set:
    return {getattr(k, "key", k) for k in path}


def served_dtype(path: tuple, cfg: SambaYConfig):
    """The dtype the serving programs hold the leaf at ``path`` in."""
    keys = _names(path)
    if keys & F32_GROUPS or keys & F32_LEAVES:
        return jnp.dtype(jnp.float32)
    return jnp.dtype(cfg.dtype)


def served_template(cfg: SambaYConfig):
    """The abstract tree the serving programs take: each leaf with its
    shape and the dtype it is served in."""
    return jax.tree_util.tree_map_with_path(
        lambda path, shape: jax.ShapeDtypeStruct(
            shape, served_dtype(path, cfg)),
        param_shapes(cfg), is_leaf=_is_shape)


def count_params(cfg: SambaYConfig) -> int:
    return sum(math.prod(s) for s in jax.tree.leaves(
        param_shapes(cfg), is_leaf=_is_shape))


def init_params(cfg: SambaYConfig, key: jax.Array,
                served: bool = False) -> dict:
    """Seeded parameters: N(0, 0.02) matrices and biases, unit norm
    weights and zero norm biases, taps of 1 / sqrt(taps) + 0.1 N(0, 1),
    the λ vectors N(0, 0.1) (arXiv:2410.05258), and Mamba-1's own start for
    the scan's vectors: ``A_log = log(1 … N)`` a channel, ``D = 1``, a step
    bias whose softplus is spread log-uniformly over [1e-3, 1e-1] — in
    ``cfg.param_dtype`` or, ``served``, each leaf in the dtype the serving
    programs hold it in (under ``jax.jit`` the draw and the cast fuse)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=_is_shape)
    keys = jax.random.split(key, len(flat))

    def make(path, shape, k):
        dtype = served_dtype(path, cfg) if served else cfg.param_dtype
        names = _names(path)
        noise = jax.random.normal(k, shape, jnp.float32)
        if names & F32_GROUPS or "subln" in names:
            value = jnp.ones(shape) if "bias" not in names \
                else jnp.zeros(shape)
        elif any(n.startswith("lambda_") for n in names):
            value = 0.1 * noise
        else:
            value = scan_mixer.init_leaf(names, shape, noise)
            if value is None:
                value = 0.02 * noise
        return value.astype(dtype)

    return treedef.unflatten([make(p, s, k)
                              for (p, s), k in zip(flat, keys)])


# --------------------------------------------------------------------- parts
def layer_norm(x: jax.Array, p: dict, eps: float, dtype) -> jax.Array:
    """LayerNorm over the last axis with weight and bias, in float32."""
    x = x.astype(jnp.float32)
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps) * p["scale"]
            + p["bias"]).astype(dtype)


def gated_mlp(f: jax.Array, lp: dict) -> jax.Array:
    """``W_down(silu(g) ⊙ p)``, ``[g; p] = W_gate_up f`` (ASSUMED: the
    gate first), in ``f``'s dtype."""
    gu = jnp.einsum("sh,hf->sf", f, lp["gate_up"],
                    preferred_element_type=jnp.float32)
    half = gu.shape[-1] // 2
    a = (jax.nn.silu(gu[:, :half]) * gu[:, half:]).astype(f.dtype)
    return jnp.einsum("sf,fh->sh", a, lp["down"])


def memory_unit(u: jax.Array, m: jax.Array, lp: dict) -> jax.Array:
    """The gated memory unit: ``W₂(m ⊙ silu(W₁ u))`` (ASSUMED: the SiLU on
    the projected input, not on ``m``)."""
    g = jnp.einsum("sh,hc->sc", u, lp["in"],
                   preferred_element_type=jnp.float32)
    a = (m.astype(jnp.float32) * jax.nn.silu(g)).astype(u.dtype)
    return jnp.einsum("sc,ch->sh", a, lp["out"])


def attention_project(u: jax.Array, lp: dict, cfg: SambaYConfig) -> tuple:
    """``u`` [rows, h] -> ``(q [rows, heads, hd], k, v [rows, kv · hd])``
    from ONE product with bias; a cross layer's gives ``(q, None, None)``."""
    qkv = jnp.einsum("sh,hc->sc", u, lp["qkv"]) + lp["qkv_bias"]
    nq, kv = cfg.num_attention_heads * cfg.head_dim, cfg.kv_lanes
    q = qkv[:, :nq].reshape(-1, cfg.num_attention_heads, cfg.head_dim)
    if qkv.shape[1] == nq:
        return q, None, None
    return q, qkv[:, nq:nq + kv], qkv[:, nq + kv:]


def diff_queries(q: jax.Array) -> jax.Array:
    """``q`` [rows, heads, hd] -> [rows, heads, 2 hd]: head *j* in the half
    of its key-value PAIR's lanes that holds the key it scores (``j`` even:
    ``K[2r]``, the first half; odd: ``K[2r+1]``, the second), zero in the
    other. Four such queries read one ``[K[2r]; K[2r+1]]``."""
    even = (jnp.arange(q.shape[1]) % 2 == 0)[None, :, None]
    zero = jnp.zeros_like(q)
    return jnp.concatenate([jnp.where(even, q, zero),
                            jnp.where(even, zero, q)], axis=-1)


def diff_lambda(lp: dict, lambda_init: jax.Array) -> jax.Array:
    """``λ = exp(λ_q1 · λ_k1) − exp(λ_q2 · λ_k2) + λ_init`` (float32)."""
    return jnp.exp((lp["lambda_q1"] * lp["lambda_k1"]).sum()) \
        - jnp.exp((lp["lambda_q2"] * lp["lambda_k2"]).sum()) + lambda_init


def diff_combine(o: jax.Array, lp: dict, lambda_init: jax.Array, eps: float,
                 dtype) -> jax.Array:
    """The two maps' outputs ``o`` [rows, heads, 2 hd] float32 (head ``2p``
    the first map of pair ``p``, ``2p + 1`` the second) -> ``RMSNorm(o₁ − λ
    o₂) · (1 − λ_init)`` joined, [rows, heads · hd] in ``dtype``."""
    rows, heads, wide = o.shape
    o = o.astype(jnp.float32).reshape(rows, heads // 2, 2, wide)
    d = o[:, :, 0] - diff_lambda(lp, lambda_init) * o[:, :, 1]
    d = d * jax.lax.rsqrt(jnp.square(d).mean(-1, keepdims=True) + eps) \
        * lp["subln"] * (1.0 - lambda_init)
    return d.reshape(rows, heads // 2 * wide).astype(dtype)


def attention_out(o: jax.Array, lp: dict) -> jax.Array:
    """The joined heads through the out product with bias."""
    return jnp.einsum("sc,ch->sh", o, lp["out"]) + lp["out_bias"]


def logits(params: dict, x: jax.Array) -> jax.Array:
    """The head on ``x`` [rows, h] -> float32 ``[rows, vocab]``: tied to the
    embedding (``tie_word_embeddings``), no bias (``lm_head_bias``)."""
    return jnp.einsum("bh,vh->bv", x, params["embed"]["tokens"],
                      preferred_element_type=jnp.float32)


def lambda_inits(cfg: SambaYConfig, kind: str) -> jax.Array:
    """``λ_init`` of every layer of an attention kind's stack, float32."""
    return jnp.asarray(np.asarray(cfg.lambda_init(kind), np.float32))


# ------------------------------------------------------- the whole sequence
def forward(params: dict, cfg: SambaYConfig, tokens: jax.Array
            ) -> jax.Array:
    """One sequence ``tokens`` [S] from its first token, no cache: float32
    logits ``[S, vocab]``. ``params`` in ``cfg.dtype`` but the leaves
    `served_dtype` keeps in float32 (the tree the serving programs take).
    The scan is ``ops/selective_scan.py``'s plain form; attention scores
    the whole sequence at once through `diff_queries`."""
    (S,) = tokens.shape
    dt, eps, hd = cfg.dtype, cfg.layer_norm_eps, cfg.head_dim
    pairs = cfg.num_key_value_heads // 2
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]
    in_window = causal & (pos[None, :] > pos[:, None] - cfg.sliding_window)
    at = {kind: 0 for kind in cfg.kinds()}
    x = params["embed"]["tokens"][tokens]
    m = shared_k = shared_v = None
    for l in range(cfg.num_hidden_layers):
        kind = cfg.kind_of(l)
        lp = jax.tree.map(lambda w: w[at[kind]], params[kind])
        u = layer_norm(x, lp["norm1"], eps, dt)
        if kind == SCAN:
            mixed, y = scan_mixer.mix_sequence(u, lp["ssm"], cfg, dt)
            if l == cfg.half:
                m = y.astype(dt)
        elif kind == GMU:
            mixed = memory_unit(u, m, lp["gmu"])
        else:
            q, k, v = attention_project(u, lp["attn"], cfg)
            if kind == FULL:
                shared_k, shared_v = k, v
            elif kind == CROSS:
                k, v = shared_k, shared_v
            s = jnp.einsum("srgd,trd->rgst",
                           diff_queries(q).reshape(S, pairs, -1, 2 * hd),
                           k.reshape(S, pairs, 2 * hd),
                           preferred_element_type=jnp.float32) / math.sqrt(hd)
            seen = in_window if kind == WINDOW else causal
            p = jax.nn.softmax(jnp.where(seen, s, _NEG), axis=-1)
            o = jnp.einsum("rgst,trd->srgd", p.astype(dt),
                           v.reshape(S, pairs, 2 * hd),
                           preferred_element_type=jnp.float32)
            lam0 = lambda_inits(cfg, kind)[at[kind]]
            mixed = attention_out(diff_combine(
                o.reshape(S, -1, 2 * hd), lp["attn"], lam0, eps, dt),
                lp["attn"])
        x = x + mixed
        x = x + gated_mlp(layer_norm(x, lp["norm2"], eps, dt), lp["mlp"])
        at[kind] += 1
    return logits(params, layer_norm(x, params["final_norm"], eps, dt))

