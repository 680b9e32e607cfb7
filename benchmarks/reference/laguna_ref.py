"""Plain reference: the Laguna decoder block — windowed and full
grouped-query attention with per-head gates, a softmax-routed sparse-expert
layer held as a share — forward pass to logits.

Written from the published ``config.json`` (poolside/Laguna-S-2.1) and the
equations of ISSUE 35 / ``docs/swa_moe.md``. float32 throughout,
``highest`` matmul precision, no kernel, no cache, no batching: one row of
tokens at a time, attention a block of queries against all keys at once.
It imports nothing of the program and takes nothing the program has made.

Pre-norm layers, RMS norm, no biases, untied head. Layer ℓ:
``h = x + Attn(norm(x))``, ``y = h + FF(norm(h))``.

- ``H = num_attention_heads_per_layer[ℓ]`` query heads over
  ``num_key_value_heads`` key-value heads of ``head_dim``; query head *j*
  reads key-value head ``j // (H / kv)``. Rotary: by ``layer_types[ℓ]``'s
  group of ``rope_parameters`` — ``yarn`` (frequencies interpolated by
  ``factor`` below ``beta_slow`` turns in the original context, kept above
  ``beta_fast``, a linear ramp between; cos and sin times
  ``attention_factor``) on the first ``partial_rotary_factor`` of a head's
  dimensions, or plain rotary. Scores ``q·k / sqrt(head_dim)``, causal; in
  a ``sliding_attention`` layer query *i* sees keys ``i − window + 1 … i``.
  ``g = sigmoid(u W_g)`` (one gate a head) multiplies each head's output
  before ``W_o``.
- ``mlp_only_layers``: ``W_d(silu(W_g u) * W_u u)``. Elsewhere: router
  logits ``u W_r`` over ``router_experts`` in float32, a softmax, the
  ``num_experts_per_tok`` largest chosen, their scores over their sum
  (``norm_topk_prob``) times ``moe_routed_scaling_factor``, applied to the
  outputs of the experts HELD here (``first_expert_held`` …
  ``first_expert_held + num_experts − 1``; what the absent experts would
  add is left out), plus one shared expert added unweighted.

``assumed`` (not stated by the config's keys; one line here, one in the
model): SiLU; a sigmoid gate whose input is the normed layer input; softmax
router scores and no selection bias; no norm on queries and keys; the
shared expert ungated; the window counted with the query inside it; rotary
pairs dimension *i* with *i + rot / 2*.

``precision`` selects the arithmetic of the matrix products only (the
router's stays float32: the architecture states it) and exists for the
control of ``correct``: ``float32`` is the reference, ``bfloat16`` the
precision the configuration states, ``float8`` the step below it (e4m3
operands, per-tensor scales), which has to come out as not correct.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16", "float8")
QUERY_BLOCK = 128      # queries scored against every key at once
_PREFIX = {"full_dense": "fd", "full_moe": "fm", "window_dense": "wd",
           "window_moe": "wm"}


# ------------------------------------------------------------- the pattern
def _kind(sizes: dict, layer: int) -> str:
    attn = "window" if sizes["layer_types"][layer] == "sliding_attention" \
        else "full"
    mlp = "dense" if layer in sizes["mlp_only_layers"] else "moe"
    return f"{attn}_{mlp}"


def _layers(sizes: dict) -> list:
    """``(kind, index in the kind's stack, query heads)`` a layer, in the
    published order."""
    seen: dict = {}
    out = []
    for l in range(int(sizes["num_hidden_layers"])):
        kind = _kind(sizes, l)
        out.append((kind, seen.get(kind, 0),
                    int(sizes["num_attention_heads_per_layer"][l])))
        seen[kind] = seen.get(kind, 0) + 1
    return out


def weight_spec(sizes: dict) -> dict:
    """Name -> (shape, kind) of every weight; kind is ``matrix`` (N(0,
    0.02)) or ``scale``. Layers of one shape are stacked."""
    h, hd = int(sizes["hidden_size"]), int(sizes["head_dim"])
    kv, v = int(sizes["num_key_value_heads"]), int(sizes["vocab_size"])
    f, fs = int(sizes["moe_intermediate_size"]), \
        int(sizes["shared_expert_intermediate_size"])
    held, routed = int(sizes["num_experts"]), int(sizes["router_experts"])
    i = int(sizes["intermediate_size"])
    spec = {"emb": ((v, h), "matrix"), "head": ((h, v), "matrix"),
            "norm_f": ((h,), "scale")}
    count, heads = {}, {}
    for kind, _, nh in _layers(sizes):
        count[kind] = count.get(kind, 0) + 1
        heads[kind] = nh
    for kind, n in count.items():
        p, nh = _PREFIX[kind], heads[kind]
        spec.update({
            f"{p}_attn_norm": ((n, h), "scale"),
            f"{p}_q": ((n, nh, hd, h), "matrix"),
            f"{p}_k": ((n, kv, hd, h), "matrix"),
            f"{p}_v": ((n, h, kv * hd), "matrix"),
            f"{p}_gate": ((n, h, nh), "matrix"),
            f"{p}_out": ((n, nh, hd, h), "matrix"),
            f"{p}_mlp_norm": ((n, h), "scale")})
        if kind.endswith("dense"):
            spec.update({f"{p}_mlp_gate": ((n, h, i), "matrix"),
                         f"{p}_mlp_up": ((n, h, i), "matrix"),
                         f"{p}_mlp_down": ((n, i, h), "matrix")})
        else:
            spec.update({
                f"{p}_router": ((n, h, routed), "matrix"),
                f"{p}_e_gate": ((n, held, h, f), "matrix"),
                f"{p}_e_up": ((n, held, h, f), "matrix"),
                f"{p}_e_down": ((n, held, f, h), "matrix"),
                f"{p}_s_gate": ((n, h, fs), "matrix"),
                f"{p}_s_up": ((n, h, fs), "matrix"),
                f"{p}_s_down": ((n, fs, h), "matrix")})
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


# ------------------------------------------------------------------ rotary
def _inverse_frequencies(group: dict, head_dim: int) -> tuple:
    """``(inverse frequencies [rot / 2], factor on cos and sin)``."""
    rot = int(head_dim * float(group.get("partial_rotary_factor", 1)))
    base = float(group["rope_theta"])
    freq = base ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    if group.get("rope_type", "default") != "yarn":
        return 1.0 / freq, 1.0
    factor = float(group["factor"])
    orig = float(group["original_max_position_embeddings"])

    def dim_of(turns: float) -> float:
        # the dimension whose frequency turns ``turns`` times in ``orig``
        return rot * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(dim_of(float(group["beta_fast"]))), 0)
    high = min(math.ceil(dim_of(float(group["beta_slow"]))), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2) - low) / (high - low), 0.0, 1.0)
    inv = ramp / (factor * freq) + (1.0 - ramp) / freq
    return inv, float(group.get("attention_factor")
                      or 0.1 * math.log(factor) + 1.0)


def _rotate(x, inv, factor):
    """``x`` [S, heads, head_dim] at positions 0 … S − 1."""
    half = len(inv)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None, :]
    cos = (jnp.cos(angle) * factor)[:, None, :]
    sin = (jnp.sin(angle) * factor)[:, None, :]
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


# ------------------------------------------------------------------- layers
def _attention(u, lw, sizes, layer_type, precision):
    """``u`` [S, h] (normed) -> the gated heads' outputs through ``W_o``."""
    S = u.shape[0]
    hd, kv = int(sizes["head_dim"]), int(sizes["num_key_value_heads"])
    nh = lw["gate"].shape[-1]
    grp = nh // kv
    q = _product("sh,ndh->snd", u, lw["q"], precision)
    k = _product("sh,ndh->snd", u, lw["k"], precision)
    v = _product("sh,hn->sn", u, lw["v"], precision).reshape(S, kv, hd)
    inv, factor = _inverse_frequencies(
        sizes["rope_parameters"][layer_type], hd)
    q, k = _rotate(q, inv, factor), _rotate(k, inv, factor)
    window = int(sizes["sliding_window"]) \
        if layer_type == "sliding_attention" else None
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
    o = o.reshape(S, nh, hd)
    g = jax.nn.sigmoid(_product("sh,hn->sn", u, lw["gate"], precision))
    return _product("snd,ndh->sh", o * g[..., None], lw["out"], precision)


def _gated_mlp(u, gate, up, down, precision):
    a = jax.nn.silu(_product("sh,hf->sf", u, gate, precision)) \
        * _product("sh,hf->sf", u, up, precision)
    return _product("sf,fh->sh", a, down, precision)


def _experts(u, lw, sizes, precision):
    """The held experts' part plus the shared expert, [S, h]."""
    k = int(sizes["num_experts_per_tok"])
    first = int(sizes.get("first_expert_held", 0))
    logits = jnp.einsum("sh,he->se", u, lw["router"],
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.softmax(logits, axis=-1)
    picked, ids = jax.lax.top_k(scores, k)
    if sizes.get("norm_topk_prob", True):
        picked = picked / picked.sum(-1, keepdims=True)
    picked = picked * float(sizes["moe_routed_scaling_factor"])

    def one_expert(y, args):
        e, gate, up, down = args
        w = jnp.where(ids == first + e, picked, 0.0).sum(-1)      # [S]
        return y + w[:, None] * _gated_mlp(u, gate, up, down, precision), None

    held = lw["e_gate"].shape[0]
    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(u),
                        (jnp.arange(held), lw["e_gate"], lw["e_up"],
                         lw["e_down"]))
    return y + _gated_mlp(u, lw["s_gate"], lw["s_up"], lw["s_down"],
                          precision)


def _layer(x, lw, sizes_key, kind, precision):
    sizes = _SIZES[sizes_key]
    eps = float(sizes["rms_norm_eps"])
    layer_type = "sliding_attention" if kind.startswith("window") \
        else "full_attention"
    h = x + _attention(_rms_norm(x, lw["attn_norm"], eps), lw, sizes,
                       layer_type, precision)
    u = _rms_norm(h, lw["mlp_norm"], eps)
    if kind.endswith("dense"):
        return h + _gated_mlp(u, lw["mlp_gate"], lw["mlp_up"],
                              lw["mlp_down"], precision)
    return h + _experts(u, lw, sizes, precision)


_SIZES: dict = {}
_NEEDED = ("hidden_size", "head_dim", "num_key_value_heads", "sliding_window",
           "rope_parameters", "rms_norm_eps", "num_experts_per_tok",
           "first_expert_held", "norm_topk_prob", "moe_routed_scaling_factor")


def _sizes_key(sizes: dict) -> str:
    import json

    key = json.dumps({k: sizes.get(k) for k in _NEEDED}, sort_keys=True)
    _SIZES.setdefault(key, dict(sizes))
    return key


@functools.lru_cache(maxsize=None)
def _jitted_layer(sizes_key: str, kind: str, precision: str):
    return jax.jit(lambda x, lw: _layer(x, lw, sizes_key, kind, precision))


def _layer_names(spec: dict, kind: str) -> list:
    p = _PREFIX[kind] + "_"
    return [n for n in spec if n.startswith(p)]


def logits_streamed(leaf, sizes: dict, tokens, precision: str = "float32"):
    """``tokens`` [1, S] -> float32 logits [1, S, vocab]; ``leaf(name)`` /
    ``leaf(name, layer)`` hands over one float32 weight, so one layer's
    weights are alive at a time."""
    assert tokens.shape[0] == 1, "one row at a time"
    spec, key = weight_spec(sizes), _sizes_key(sizes)
    x = leaf("emb")[tokens[0]]
    for kind, at, _ in _layers(sizes):
        p = _PREFIX[kind] + "_"
        lw = {n[len(p):]: leaf(n, at) for n in _layer_names(spec, kind)}
        x = _jitted_layer(key, kind, precision)(x, lw)
        del lw
    x = _rms_norm(x, leaf("norm_f"), float(sizes["rms_norm_eps"]))
    return _product("sh,hv->sv", x, leaf("head"), precision)[None]


def logits(w: dict, sizes: dict, tokens, precision: str = "float32"):
    """The same from a whole tree ``w`` (name -> float32 array), a row at
    a time: ``tokens`` [B, S] -> [B, S, vocab]."""
    def leaf(name, layer=None):
        return w[name] if layer is None else w[name][layer]

    return jnp.concatenate([logits_streamed(leaf, sizes, tokens[b:b + 1],
                                            precision)
                            for b in range(tokens.shape[0])], axis=0)
