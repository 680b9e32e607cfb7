"""Plain reference: the LFM2 sparse-expert decoder block — gated short
convolutions three layers to one of grouped-query attention with a norm on
queries and keys, a sigmoid router with a selection bias — forward pass to
logits.

Written from the catalog row of LFM2-24B-A2B (``model_type: lfm2_moe``,
https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json: its
``config`` and ``described_as``) and the equations of ISSUE 44 /
``docs/conv_moe.md``; it reads the PUBLISHED keys (``layer_types``,
``conv_L_cache``, ``num_dense_layers``, ``use_expert_bias`` ...) and, of
the cut, ``kept_layers`` alone: the published layers this chip runs, in
order. float32 throughout, ``highest`` matmul precision, no kernel, no
cache, no batching: one row of tokens at a time, a layer at a time,
attention a block of queries against all keys at once, the convolution a
sum of shifted copies of the whole sequence. It imports nothing of the
program and takes nothing the program has made.

RMS norm (``norm_eps``), no bias anywhere (``conv_bias`` false). Layer ℓ,
input ``h``: ``u = norm(h; operator_norm)``;

- ``conv``: ``[B, C, x] = split₃(u W_in)``; ``z = B ⊙ x``; ``c_t = Σ_j
  w[j] ⊙ z_{t − (K − 1) + j}``, ``K = conv_L_cache``, zeros before the
  first token; ``h ← h + (C ⊙ c) W_out``.
- ``full_attention``: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key-value heads of ``hidden / heads`` (the row's
  ``head_dim`` is null); queries and keys normed a head (``q_layernorm``,
  ``k_layernorm``), then rotated (``rope_theta``, the whole head); causal
  ``softmax(q kᵀ / sqrt(head_dim)) v``; query head *j* reads key-value head
  ``j // (heads / kv)``; ``h ← h + o W_o``.

``f = norm(h; ffn_norm)``; the leading ``num_dense_layers`` layers: ``h ← h
+ W₂(silu(W₁ f) ⊙ W₃ f)``; every other layer: ``s = sigmoid(f W_g)`` in
float32, the ``num_experts_per_tok`` largest of ``s + expert_bias`` chosen
(``use_expert_bias``: the bias selects and weighs nothing), ``w = s[chosen]
/ (Σ s[chosen] + 1e-6)`` (``norm_topk_prob``) times
``routed_scaling_factor``, ``h ← h + Σ_e w_e W₂ᵉ(silu(W₁ᵉ f) ⊙ W₃ᵉ f)``. One
final norm, then the head.

ASSUMED (not given by the row; one line here, one in the model;
``docs/conv_moe.md`` says what each would change):

- the head is TIED to the embedding (the family's published convention);
- the in-projection's output splits in the order ``B, C, x``;
- the ``1e-6`` in the weights' sum;
- the rotation pairs dimension *i* with *i + head_dim / 2* (half-split);
- ``intermediate_size`` is the dense MLP's width as it stands.

Departures from the published description: none in the layers kept. The
cut (``kept_layers``: published layer 0 and layers 2–9) drops layers the
others do not read.

``precision`` selects the arithmetic of the matrix products only (the
router's stays float32: the architecture states it; the convolution and
the gates are element-wise) and exists for the control of ``correct``:
``float32`` is the reference, ``bfloat16`` the precision the configuration
states, ``float8`` the step below it (e4m3 operands, per-tensor scales),
which has to come out as not correct.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16", "float8")
QUERY_BLOCK = 128      # queries scored against every key at once
ROUTE_SUM_EPS = 1e-6   # ASSUMED
_PREFIX = {("conv", True): "cd", ("conv", False): "cm",
           ("full_attention", True): "fd", ("full_attention", False): "fm"}


# ------------------------------------------------------------- the pattern
def _layers(sizes: dict) -> list:
    """``(layer type, dense MLP or not, index in the stack of its kind)`` a
    layer this chip runs, in the published order. ``kept_layers`` (the
    cut): the published layers kept; of them the leading
    ``num_dense_layers`` carry the dense MLP."""
    n = int(sizes["num_hidden_layers"])
    kept = sizes.get("kept_layers") or list(range(n))
    assert len(kept) == n, (kept, n)
    seen: dict = {}
    out = []
    for l, published in enumerate(kept):
        kind = (sizes["layer_types"][published],
                l < int(sizes["num_dense_layers"]))
        out.append(kind + (seen.get(kind, 0),))
        seen[kind] = seen.get(kind, 0) + 1
    return out


def _head_dim(sizes: dict) -> int:
    return int(sizes["hidden_size"]) // int(sizes["num_attention_heads"])


def weight_spec(sizes: dict) -> dict:
    """Name -> (shape, kind) of every weight; kind is ``matrix`` (N(0,
    0.02)) or ``scale`` (1 + 0.1 N(0, 1): the norms' weights and the
    convolution's taps, which a depth-wise convolution initialises at the
    size of 1 / sqrt(taps), not of a 2,048-wide product's matrix). A prefix
    a stack of layers of one shape: ``cd`` conv + dense MLP, ``cm`` conv +
    experts, ``fd`` / ``fm`` the same under attention. No head: tied."""
    h, hd = int(sizes["hidden_size"]), _head_dim(sizes)
    nh, kv = int(sizes["num_attention_heads"]), \
        int(sizes["num_key_value_heads"])
    i, f = int(sizes["intermediate_size"]), \
        int(sizes["moe_intermediate_size"])
    e, taps = int(sizes["num_experts"]), int(sizes["conv_L_cache"])
    spec = {"emb": ((int(sizes["vocab_size"]), h), "matrix"),
            "norm_f": ((h,), "scale")}
    count: dict = {}
    for layer_type, dense, _ in _layers(sizes):
        count[layer_type, dense] = count.get((layer_type, dense), 0) + 1
    for (layer_type, dense), n in count.items():
        p = _PREFIX[layer_type, dense]
        spec.update({f"{p}_norm_op": ((n, h), "scale"),
                     f"{p}_norm_ffn": ((n, h), "scale")})
        if layer_type == "conv":
            spec.update({f"{p}_in": ((n, h, 3 * h), "matrix"),
                         f"{p}_taps": ((n, taps, h), "scale"),
                         f"{p}_out": ((n, h, h), "matrix")})
        else:
            spec.update({f"{p}_q": ((n, nh, hd, h), "matrix"),
                         f"{p}_k": ((n, kv, hd, h), "matrix"),
                         f"{p}_v": ((n, h, kv * hd), "matrix"),
                         f"{p}_o": ((n, nh, hd, h), "matrix"),
                         f"{p}_q_norm": ((n, hd), "scale"),
                         f"{p}_k_norm": ((n, hd), "scale")})
        if dense:
            spec.update({f"{p}_mlp_gate": ((n, h, i), "matrix"),
                         f"{p}_mlp_up": ((n, h, i), "matrix"),
                         f"{p}_mlp_down": ((n, i, h), "matrix")})
        else:
            spec.update({f"{p}_router": ((n, h, e), "matrix"),
                         f"{p}_bias": ((n, e), "matrix"),
                         f"{p}_e_gate": ((n, e, h, f), "matrix"),
                         f"{p}_e_up": ((n, e, h, f), "matrix"),
                         f"{p}_e_down": ((n, e, f, h), "matrix")})
    return spec


# ---------------------------------------------------------------- products
def _fake_quant(x, dtype):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _product(spec: str, a, b, precision: str):
    """One matrix product in the stated arithmetic, result in float32."""
    if precision == "float32":
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
    if precision == "bfloat16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16),
                          b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if precision == "float8":
        return jnp.einsum(spec, _fake_quant(a, jnp.float8_e4m3fn),
                          _fake_quant(b, jnp.float8_e4m3fn),
                          precision=jax.lax.Precision.HIGHEST)
    raise ValueError(f"precision {precision!r} is not one of {PRECISIONS}")


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rotate(x, theta: float):
    """Plain rotary on ``x`` [S, heads, head_dim] at positions 0 … S − 1,
    the whole head; ASSUMED: dimension *i* pairs with *i + head_dim / 2*."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


# ------------------------------------------------------------------- layers
def _conv(u, lw, sizes, precision):
    """``u`` [S, h] (normed) -> the gated short convolution through
    ``W_out``."""
    S, h = u.shape
    taps = int(sizes["conv_L_cache"])
    bcx = _product("sh,hc->sc", u, lw["in"], precision)
    b, c, x = bcx[:, :h], bcx[:, h:2 * h], bcx[:, 2 * h:]   # ASSUMED order
    z = jnp.concatenate([jnp.zeros((taps - 1, h), jnp.float32), b * x])
    conv = sum(lw["taps"][j][None, :] * z[j:j + S] for j in range(taps))
    return _product("sc,ch->sh", c * conv, lw["out"], precision)


def _attention(u, lw, sizes, precision):
    """``u`` [S, h] (normed) -> the heads' outputs through ``W_o``."""
    S = u.shape[0]
    hd, kv = _head_dim(sizes), int(sizes["num_key_value_heads"])
    nh = int(sizes["num_attention_heads"])
    grp, eps = nh // kv, float(sizes["norm_eps"])
    theta = float(sizes["rope_parameters"]["rope_theta"])
    q = _product("sh,ndh->snd", u, lw["q"], precision)
    k = _product("sh,ndh->snd", u, lw["k"], precision)
    v = _product("sh,hn->sn", u, lw["v"], precision).reshape(S, kv, hd)
    # the norm a head, BEFORE the rotation
    q = _rotate(_rms_norm(q, lw["q_norm"], eps), theta)
    k = _rotate(_rms_norm(k, lw["k_norm"], eps), theta)
    block = min(QUERY_BLOCK, S)
    assert S % block == 0, (S, block)
    qb = q.reshape(S // block, block, kv, grp, hd)
    key_pos = jnp.arange(S)

    def one_block(args):
        qi, first = args
        s = _product("qkgd,tkd->kgqt", qi, k, precision) / math.sqrt(hd)
        q_pos = first + jnp.arange(block)
        seen = key_pos[None, :] <= q_pos[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return _product("kgqt,tkd->qkgd", p, v, precision)

    o = jax.lax.map(one_block, (qb, jnp.arange(0, S, block)))
    return _product("snd,ndh->sh", o.reshape(S, nh, hd), lw["o"], precision)


def _swiglu(f, gate, up, down, precision):
    a = jax.nn.silu(_product("sh,hf->sf", f, gate, precision)) \
        * _product("sh,hf->sf", f, up, precision)
    return _product("sf,fh->sh", a, down, precision)


def _route(f, lw, sizes):
    """``f`` [S, h] -> (ids [S, k], weights [S, k]) in float32."""
    s = jax.nn.sigmoid(jnp.einsum("sh,he->se", f, lw["router"],
                                  precision=jax.lax.Precision.HIGHEST))
    choose = s + lw["bias"][None, :] if sizes.get("use_expert_bias") else s
    _, ids = jax.lax.top_k(choose, int(sizes["num_experts_per_tok"]))
    w = jnp.take_along_axis(s, ids, axis=-1)    # the bias weighs nothing
    if sizes.get("norm_topk_prob"):
        w = w / (w.sum(-1, keepdims=True) + ROUTE_SUM_EPS)
    return ids, w * float(sizes["routed_scaling_factor"])


def _experts(f, ids, weights, lw, precision):
    """The chosen experts' weighted sum on ``f`` [S, h]: every expert in
    turn, over all tokens, weighted by what the router gave it (0 for a
    token that did not choose it)."""
    def one_expert(y, args):
        e, gate, up, down = args
        w = jnp.where(ids == e, weights, 0.0).sum(-1)             # [S]
        return y + w[:, None] * _swiglu(f, gate, up, down, precision), None

    n = lw["e_gate"].shape[0]
    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(f),
                        (jnp.arange(n), lw["e_gate"], lw["e_up"],
                         lw["e_down"]))
    return y


def _layer(x, lw, sizes_key, layer_type, dense, precision):
    sizes = _SIZES[sizes_key]
    eps = float(sizes["norm_eps"])
    u = _rms_norm(x, lw["norm_op"], eps)
    op = _conv if layer_type == "conv" else _attention
    h = x + op(u, lw, sizes, precision)
    f = _rms_norm(h, lw["norm_ffn"], eps)
    if dense:
        return h + _swiglu(f, lw["mlp_gate"], lw["mlp_up"], lw["mlp_down"],
                           precision)
    ids, weights = _route(f, lw, sizes)
    return h + _experts(f, ids, weights, lw, precision)


_SIZES: dict = {}
_NEEDED = ("hidden_size", "num_attention_heads", "num_key_value_heads",
           "conv_L_cache", "norm_eps", "rope_parameters",
           "num_experts_per_tok", "norm_topk_prob", "use_expert_bias",
           "routed_scaling_factor")


def _sizes_key(sizes: dict) -> str:
    key = json.dumps({k: sizes.get(k) for k in _NEEDED}, sort_keys=True)
    _SIZES.setdefault(key, dict(sizes))
    return key


@functools.lru_cache(maxsize=None)
def _jitted_layer(sizes_key: str, layer_type: str, dense: bool,
                  precision: str):
    return jax.jit(lambda x, lw: _layer(x, lw, sizes_key, layer_type, dense,
                                        precision))


@functools.lru_cache(maxsize=None)
def _jitted_head(eps: float, precision: str):
    # ASSUMED: the head is the embedding, transposed
    return jax.jit(lambda x, scale, emb: _product(
        "sh,vh->sv", _rms_norm(x, scale, eps), emb, precision)[None])


def logits_streamed(leaf, sizes: dict, tokens, precision: str = "float32"):
    """``tokens`` [1, S] -> float32 logits [1, S, vocab]; ``leaf(name)`` /
    ``leaf(name, layer)`` hands over one float32 weight, so one layer's
    weights are alive at a time."""
    assert tokens.shape[0] == 1, "one row at a time"
    spec, key = weight_spec(sizes), _sizes_key(sizes)
    x = leaf("emb")[tokens[0]]
    for layer_type, dense, at in _layers(sizes):
        p = _PREFIX[layer_type, dense] + "_"
        lw = {n[len(p):]: leaf(n, at) for n in spec if n.startswith(p)}
        x = _jitted_layer(key, layer_type, dense, precision)(x, lw)
        del lw
    return _jitted_head(float(sizes["norm_eps"]), precision)(
        x, leaf("norm_f"), leaf("emb"))


def logits(w: dict, sizes: dict, tokens, precision: str = "float32"):
    """The same from a whole tree ``w`` (name -> float32 array), a row at
    a time: ``tokens`` [B, S] -> [B, S, vocab]."""
    def leaf(name, layer=None):
        return w[name] if layer is None else w[name][layer]

    return jnp.concatenate([logits_streamed(leaf, sizes, tokens[b:b + 1],
                                            precision)
                            for b in range(tokens.shape[0])], axis=0)
