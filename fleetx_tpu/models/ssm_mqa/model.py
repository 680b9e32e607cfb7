"""The scan / multi-query family (``model_type: jamba`` with one expert:
AI21-Jamba2-3B): parameters, the parts of a layer that need no cache, and
the whole-sequence forward. ``docs/ssm_mqa.md`` has the equations with the
source of each and every reading that is ASSUMED.

Pre-norm layers, RMS norms with a weight and no bias (``rms_norm_eps``),
the head TIED to the embedding, no position signal anywhere (the scan
layers carry order). Layer ℓ, input ``h``: ``u = RMS₁(h)``, ``h ← h +
Mixer(u)``, ``f = RMS₂(h)``, ``h ← h + W_down(silu(W_gate f) ⊙ W_up f)``.
The mixers (``SSMMQAConfig.kind_of``):

- *scan* (``l mod attn_layer_period ≠ attn_layer_offset``): the selective
  scan mixer of ``models/scan_mixer.py`` — the ONE definition, shared with
  ``models/samba_y`` — whose leaves here hold three more weights, so that
  the step, ``B`` and ``C`` pass an RMS norm each (``rms_norm_eps``) before
  they are used. All a layer remembers of a sequence: its state ``[N,
  inner]`` float32 and the last ``d_conv − 1`` inputs of the convolution.
- *full* (the rest): causal softmax attention, ``num_attention_heads``
  query heads over ``num_key_value_heads`` (ONE) key-value heads of
  ``head_dim``, scores scaled by ``1 / sqrt(head_dim)``, no bias on any
  product, no rotation, no window. ``[q; k; v] = W_qkv u`` is one product
  (ASSUMED order; the published checkpoint keeps three matrices: the same
  numbers side by side).

Layers of one kind are stacked: the tree is ``{"embed", "final_norm",
"scan", "full": {...leaves [layers, ...]}}`` — no head leaf. What walks the
layers with their caches is ``serving/ssm_mqa.py``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from fleetx_tpu.models import scan_mixer
from fleetx_tpu.models.ssm_mqa.config import SCAN, SSMMQAConfig
from fleetx_tpu.models.swa_moe import model as shared

#: leaves kept in float32 whatever ``cfg.dtype`` is: every norm's weight
#: (the scan's three inner ones among them) and the scan's own vectors
F32_GROUPS = frozenset({"norm1", "norm2", "final_norm"})
F32_LEAVES = scan_mixer.F32_LEAVES
_NEG = -1e30

rms_norm = shared.rms_norm
gated_mlp = shared.gated_mlp


# ------------------------------------------------------------------ the tree
def param_shapes(cfg: SSMMQAConfig) -> dict:
    """The parameter tree as shapes: leaf -> tuple. Matrices ``[layers, in,
    out]``."""
    h, f = cfg.hidden_size, cfg.intermediate_size
    q, kv = cfg.num_attention_heads * cfg.head_dim, cfg.kv_lanes
    tree = {"embed": {"tokens": (cfg.vocab_size, h)},
            "final_norm": {"scale": (h,)}}
    for kind, L in cfg.kinds().items():
        layer = {"norm1": {"scale": (L, h)}, "norm2": {"scale": (L, h)},
                 "mlp": {"gate": (L, h, f), "up": (L, h, f),
                         "down": (L, f, h)}}
        if kind == SCAN:
            layer["ssm"] = scan_mixer.leaf_shapes(L, h, cfg,
                                                  inner_norms=True)
        else:
            layer["attn"] = {"qkv": (L, h, q + 2 * kv), "out": (L, q, h)}
        tree[kind] = layer
    return tree


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def _names(path: tuple) -> set:
    return {getattr(k, "key", k) for k in path}


def served_dtype(path: tuple, cfg: SSMMQAConfig):
    """The dtype the serving programs hold the leaf at ``path`` in."""
    keys = _names(path)
    if keys & F32_GROUPS or keys & F32_LEAVES:
        return jnp.dtype(jnp.float32)
    return jnp.dtype(cfg.dtype)


def served_template(cfg: SSMMQAConfig):
    """The abstract tree the serving programs take: each leaf with its
    shape and the dtype it is served in."""
    return jax.tree_util.tree_map_with_path(
        lambda path, shape: jax.ShapeDtypeStruct(
            shape, served_dtype(path, cfg)),
        param_shapes(cfg), is_leaf=_is_shape)


def count_params(cfg: SSMMQAConfig) -> int:
    return sum(math.prod(s) for s in jax.tree.leaves(
        param_shapes(cfg), is_leaf=_is_shape))


def init_params(cfg: SSMMQAConfig, key: jax.Array,
                served: bool = False) -> dict:
    """Seeded parameters: N(0, 0.02) matrices and the convolution's bias,
    unit norm weights, and Mamba-1's own start for the scan's vectors
    (``scan_mixer.init_leaf``) — in ``cfg.param_dtype`` or, ``served``, each
    leaf in the dtype the serving programs hold it in (under ``jax.jit``
    the draw and the cast fuse)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=_is_shape)
    keys = jax.random.split(key, len(flat))

    def make(path, shape, k):
        dtype = served_dtype(path, cfg) if served else cfg.param_dtype
        names = _names(path)
        noise = jax.random.normal(k, shape, jnp.float32)
        if names & F32_GROUPS:
            value = jnp.ones(shape)
        else:
            value = scan_mixer.init_leaf(names, shape, noise)
            if value is None:
                value = 0.02 * noise
        return value.astype(dtype)

    return treedef.unflatten([make(p, s, k)
                              for (p, s), k in zip(flat, keys)])


# --------------------------------------------------------------------- parts
def attention_project(u: jax.Array, lp: dict, cfg: SSMMQAConfig) -> tuple:
    """``u`` [rows, h] -> ``(q [rows, heads, hd], k, v [rows, kv · hd])``
    from ONE product without bias; nothing is rotated."""
    qkv = jnp.einsum("sh,hc->sc", u, lp["qkv"])
    nq, kv = cfg.num_attention_heads * cfg.head_dim, cfg.kv_lanes
    q = qkv[:, :nq].reshape(-1, cfg.num_attention_heads, cfg.head_dim)
    return q, qkv[:, nq:nq + kv], qkv[:, nq + kv:]


def attention_out(o: jax.Array, lp: dict) -> jax.Array:
    """The heads ``o`` [rows, heads, hd] joined, through the out product."""
    return jnp.einsum("sc,ch->sh", o.reshape(o.shape[0], -1), lp["out"])


def logits(params: dict, x: jax.Array) -> jax.Array:
    """The head on ``x`` [rows, h] -> float32 ``[rows, vocab]``: tied to the
    embedding (``tie_word_embeddings``), no bias."""
    return jnp.einsum("bh,vh->bv", x, params["embed"]["tokens"],
                      preferred_element_type=jnp.float32)


# ------------------------------------------------------- the whole sequence
def forward(params: dict, cfg: SSMMQAConfig, tokens: jax.Array
            ) -> jax.Array:
    """One sequence ``tokens`` [S] from its first token, no cache: float32
    logits ``[S, vocab]``. ``params`` in ``cfg.dtype`` but the leaves
    `served_dtype` keeps in float32 (the tree the serving programs take).
    Attention scores the whole sequence at once."""
    (S,) = tokens.shape
    dt, eps, hd = cfg.dtype, cfg.rms_norm_eps, cfg.head_dim
    kvh = cfg.num_key_value_heads
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]
    at = {kind: 0 for kind in cfg.kinds()}
    x = params["embed"]["tokens"][tokens]
    for l in range(cfg.num_hidden_layers):
        kind = cfg.kind_of(l)
        lp = jax.tree.map(lambda w: w[at[kind]], params[kind])
        u = rms_norm(x, lp["norm1"]["scale"], eps, dt)
        if kind == SCAN:
            mixed, _ = scan_mixer.mix_sequence(u, lp["ssm"], cfg, dt, eps)
        else:
            q, k, v = attention_project(u, lp["attn"], cfg)
            s = jnp.einsum("skgd,tkd->kgst", q.reshape(S, kvh, -1, hd),
                           k.reshape(S, kvh, hd),
                           preferred_element_type=jnp.float32) / math.sqrt(hd)
            p = jax.nn.softmax(jnp.where(causal, s, _NEG), axis=-1)
            o = jnp.einsum("kgst,tkd->skgd", p.astype(dt),
                           v.reshape(S, kvh, hd),
                           preferred_element_type=jnp.float32)
            mixed = attention_out(o.reshape(S, -1, hd).astype(dt),
                                  lp["attn"])
        x = x + mixed.astype(dt)
        f = rms_norm(x, lp["norm2"]["scale"], eps, dt)
        x = x + gated_mlp(f, lp["mlp"]["gate"], lp["mlp"]["up"],
                          lp["mlp"]["down"]).astype(dt)
        at[kind] += 1
    return logits(params, rms_norm(x, params["final_norm"]["scale"], eps, dt))
