"""Plain reference: the SmallThinker decoder block — a router that reads
the layer's input before attention, ReGLU experts, window layers with
rotary beside full layers with no position signal — forward pass to logits.

Written from the published ``config.json``
(PowerInfer/SmallThinker-21BA3B-Instruct) and the equations of ISSUE 38 /
``docs/swa_moe.md``, and it reads the PUBLISHED keys (``rope_layout``,
``sliding_window_layout``, ``moe_num_primary_experts`` ...), not the ones
the program's family derives from them. float32 throughout, ``highest``
matmul precision, no kernel, no cache, no batching: one row of tokens at a
time, a layer at a time, attention a block of queries against all keys at
once. It imports nothing of the program and takes nothing the program has
made.

RMS norm (``rms_norm_eps``), no biases, untied head. Layer ℓ, input ``x``:

1. ``u = norm_in(x)``. Router, in float32: ``z = u W_r``
   (``moe_num_primary_experts`` logits); the
   ``moe_num_active_primary_experts`` largest ``z`` are chosen; with
   ``moe_primary_router_apply_softmax`` ``w = softmax`` over the chosen
   alone (``norm_topk_prob`` then changes nothing), else ``w = sigmoid(z)``
   of the chosen over their sum.
2. Attention on ``u``: ``num_attention_heads`` query heads over
   ``num_key_value_heads`` key-value heads of ``head_dim``; query head *j*
   reads key-value head ``j // (heads / kv)``; scores ``q·k /
   sqrt(head_dim)``, causal. ``sliding_window_layout[ℓ] == 1``: a query sees
   the last ``sliding_window_size`` keys, itself in. ``rope_layout[ℓ] ==
   1``: plain rotary, ``rope_theta``, all of a head's dimensions; 0: no
   rotary and no other position term. ``h = x + Attn(u) W_o``.
3. ``v = norm_post(h)``; ``y = h + sum over the chosen e of w_e W_down,e
   (relu(W_gate,e v) * W_up,e v)``. The experts act on ``v``; their choice
   came from ``u``. No shared expert.

``assumed`` (not stated by the config's keys; one line here, one in the
model): ReLU as the gate's activation (``described_as``: "sparse ReGLU");
the router before attention (``described_as``); the window counted with
the query inside it; rotary pairs dimension *i* with *i + head_dim / 2*;
no norm on queries and keys.

``precision`` selects the arithmetic of the matrix products only (the
router's stays float32: the architecture states it) and exists for the
control of ``correct``: ``float32`` is the reference, ``bfloat16`` the
precision the configuration states, ``float8`` the step below it (e4m3
operands, per-tensor scales), which has to come out as not correct.
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
_PREFIX = {0: "f", 1: "w"}      # sliding_window_layout -> the stack's name


# ------------------------------------------------------------- the pattern
def _layers(sizes: dict) -> list:
    """``(windowed 0 / 1, rotated 0 / 1, index in the stack of its kind)``
    a layer, in the published order. Layers of one ``sliding_window_layout``
    value are one stack (they have one shape)."""
    seen = {0: 0, 1: 0}
    out = []
    for l in range(int(sizes["num_hidden_layers"])):
        win = int(sizes["sliding_window_layout"][l])
        out.append((win, int(sizes["rope_layout"][l]), seen[win]))
        seen[win] += 1
    return out


def weight_spec(sizes: dict) -> dict:
    """Name -> (shape, kind) of every weight; kind is ``matrix`` (N(0,
    0.02)) or ``scale``. ``f_*``: the stack of the layers that attend over
    everything; ``w_*``: of the window layers."""
    h, hd = int(sizes["hidden_size"]), int(sizes["head_dim"])
    nh, kv = int(sizes["num_attention_heads"]), \
        int(sizes["num_key_value_heads"])
    v, f = int(sizes["vocab_size"]), int(sizes["moe_ffn_hidden_size"])
    e = int(sizes["moe_num_primary_experts"])
    spec = {"emb": ((v, h), "matrix"), "head": ((h, v), "matrix"),
            "norm_f": ((h,), "scale")}
    count = {0: 0, 1: 0}
    for win, _, _ in _layers(sizes):
        count[win] += 1
    for win, n in count.items():
        if not n:
            continue
        p = _PREFIX[win]
        spec.update({
            f"{p}_norm_in": ((n, h), "scale"),
            f"{p}_q": ((n, nh, hd, h), "matrix"),
            f"{p}_k": ((n, kv, hd, h), "matrix"),
            f"{p}_v": ((n, h, kv * hd), "matrix"),
            f"{p}_out": ((n, nh, hd, h), "matrix"),
            f"{p}_norm_post": ((n, h), "scale"),
            f"{p}_router": ((n, h, e), "matrix"),
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
    all of a head's dimensions; dimension *i* pairs with *i + head_dim/2*."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


# ------------------------------------------------------------------- layers
def _route(u, router, sizes):
    """``u`` [S, h] (the layer's normed INPUT) -> (ids [S, k], weights
    [S, k]) in float32."""
    k = int(sizes["moe_num_active_primary_experts"])
    z = jnp.einsum("sh,he->se", u, router,
                   precision=jax.lax.Precision.HIGHEST)
    top, ids = jax.lax.top_k(z, k)
    if sizes.get("moe_primary_router_apply_softmax", True):
        return ids, jax.nn.softmax(top, axis=-1)
    w = jax.nn.sigmoid(top)
    return ids, w / w.sum(-1, keepdims=True)


def _attention(u, lw, sizes, windowed: int, rotated: int, precision):
    """``u`` [S, h] (normed) -> the heads' outputs through ``W_o``."""
    S = u.shape[0]
    hd, kv = int(sizes["head_dim"]), int(sizes["num_key_value_heads"])
    nh = int(sizes["num_attention_heads"])
    grp = nh // kv
    q = _product("sh,ndh->snd", u, lw["q"], precision)
    k = _product("sh,ndh->snd", u, lw["k"], precision)
    v = _product("sh,hn->sn", u, lw["v"], precision).reshape(S, kv, hd)
    if rotated:
        theta = float(sizes["rope_theta"])
        q, k = _rotate(q, theta), _rotate(k, theta)
    window = int(sizes["sliding_window_size"]) if windowed else None
    block = min(QUERY_BLOCK, S)
    assert S % block == 0, (S, block)
    qb = q.reshape(S // block, block, kv, grp, hd)
    key_pos = jnp.arange(S)

    def one_block(args):
        qi, first = args
        s = _product("qkgd,tkd->kgqt", qi, k, precision) / math.sqrt(hd)
        q_pos = first + jnp.arange(block)
        seen = key_pos[None, :] <= q_pos[:, None]
        if window is not None:
            seen = seen & (key_pos[None, :] > q_pos[:, None] - window)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return _product("kgqt,tkd->qkgd", p, v, precision)

    o = jax.lax.map(one_block, (qb, jnp.arange(0, S, block)))
    return _product("snd,ndh->sh", o.reshape(S, nh, hd), lw["out"],
                    precision)


def _reglu(v, gate, up, down, precision):
    a = jax.nn.relu(_product("sh,hf->sf", v, gate, precision)) \
        * _product("sh,hf->sf", v, up, precision)
    return _product("sf,fh->sh", a, down, precision)


def _experts(v, ids, weights, lw, precision):
    """The chosen experts' weighted sum on ``v`` [S, h]: every expert in
    turn, over all tokens, weighted by what the router gave it (0 for a
    token that did not choose it)."""
    def one_expert(y, args):
        e, gate, up, down = args
        w = jnp.where(ids == e, weights, 0.0).sum(-1)             # [S]
        return y + w[:, None] * _reglu(v, gate, up, down, precision), None

    n = lw["e_gate"].shape[0]
    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(v),
                        (jnp.arange(n), lw["e_gate"], lw["e_up"],
                         lw["e_down"]))
    return y


def _layer(x, lw, sizes_key, windowed, rotated, precision):
    sizes = _SIZES[sizes_key]
    eps = float(sizes["rms_norm_eps"])
    u = _rms_norm(x, lw["norm_in"], eps)
    ids, weights = _route(u, lw["router"], sizes)       # BEFORE attention
    h = x + _attention(u, lw, sizes, windowed, rotated, precision)
    v = _rms_norm(h, lw["norm_post"], eps)
    return h + _experts(v, ids, weights, lw, precision)


_SIZES: dict = {}
_NEEDED = ("hidden_size", "head_dim", "num_attention_heads",
           "num_key_value_heads", "sliding_window_size", "rope_theta",
           "rms_norm_eps", "moe_num_active_primary_experts",
           "moe_primary_router_apply_softmax")


def _sizes_key(sizes: dict) -> str:
    key = json.dumps({k: sizes.get(k) for k in _NEEDED}, sort_keys=True)
    _SIZES.setdefault(key, dict(sizes))
    return key


@functools.lru_cache(maxsize=None)
def _jitted_layer(sizes_key: str, windowed: int, rotated: int,
                  precision: str):
    return jax.jit(lambda x, lw: _layer(x, lw, sizes_key, windowed, rotated,
                                        precision))


@functools.lru_cache(maxsize=None)
def _jitted_head(eps: float, precision: str):
    return jax.jit(lambda x, scale, head: _product(
        "sh,hv->sv", _rms_norm(x, scale, eps), head, precision)[None])


def logits_streamed(leaf, sizes: dict, tokens, precision: str = "float32"):
    """``tokens`` [1, S] -> float32 logits [1, S, vocab]; ``leaf(name)`` /
    ``leaf(name, layer)`` hands over one float32 weight, so one layer's
    weights are alive at a time."""
    assert tokens.shape[0] == 1, "one row at a time"
    spec, key = weight_spec(sizes), _sizes_key(sizes)
    x = leaf("emb")[tokens[0]]
    for windowed, rotated, at in _layers(sizes):
        p = _PREFIX[windowed] + "_"
        lw = {n[len(p):]: leaf(n, at) for n in spec if n.startswith(p)}
        x = _jitted_layer(key, windowed, rotated, precision)(x, lw)
        del lw
    return _jitted_head(float(sizes["rms_norm_eps"]), precision)(
        x, leaf("norm_f"), leaf("head"))


def logits(w: dict, sizes: dict, tokens, precision: str = "float32"):
    """The same from a whole tree ``w`` (name -> float32 array), a row at
    a time: ``tokens`` [B, S] -> [B, S, vocab]."""
    def leaf(name, layer=None):
        return w[name] if layer is None else w[name][layer]

    return jnp.concatenate([logits_streamed(leaf, sizes, tokens[b:b + 1],
                                            precision)
                            for b in range(tokens.shape[0])], axis=0)
