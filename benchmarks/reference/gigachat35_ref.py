"""Plain reference: the GigaChat3.5 decoder block — gated-delta-rule
(linear-attention) layers beside latent-attention layers, sigmoid-routed
experts with a shared one — forward pass to logits.

Written from the published ``config.json`` (ai-sage/GigaChat3.5-432B-A28B),
the Gated DeltaNet paper (arXiv:2412.06464) in the form the Qwen3-Next
modelling code gives it, DeepSeek-V3's latent attention and routing, and the
equations of ISSUE 42 / ``docs/gdn_mla.md``. It reads the PUBLISHED keys.
float32 throughout, ``highest`` matmul precision, no kernel, no cache, no
chunked form, no absorbed product: one row of tokens at a time, a layer
(and, in it, the token mixer and the feed-forward part) at a time, the rule
as its three-line recurrence under ``lax.scan``, latent attention with the
full per-head keys and values. It imports nothing of the program and takes
nothing the program has made.

Block (``layernorm_type: pre_post``): ``x ← x + N_post(F(N_pre(x)))`` for
the token mixer, again for the feed-forward part. ``N`` everywhere (layers,
latents, final): ``x / rms(x) · layernorm_gating_weight · sigmoid(w)``
(ASSUMED: ``norm_type: ZeroCenteredGatedNorm``).

*Linear-attention layer* (not in ``full_attention_layers``), ``u =
N_pre(x)``: ``[q; k; v] = u W_qkv`` (``linear_num_key_heads`` heads of
``linear_key_head_dim`` for q and for k, ``linear_num_value_heads`` of
``linear_value_head_dim`` for v), ``z = u W_z``, ``[a; b] = u W_ab``; a
causal depth-wise convolution of ``linear_conv_kernel_dim`` taps over ``[q;
k; v]`` (tap K − 1 weighs the token itself), then SiLU; ``q, k`` ←
``x · rsqrt(sum x² + 1e-6)`` a head, ``q`` times ``dk^-1/2``; key head ``h //
(Hv / Hk)`` serves value head ``h``; ``β = sigmoid(b)``, ``α = exp(−exp(A_log)
· softplus(a + dt_bias))``. A value head's state ``S`` [value, key], zero at
the sequence's start::

    S ← α S;   S ← S + β (v − S k) kᵀ;   o = S q

``o`` ← RMS norm over its values (``linear_attn_o_norm_eps``) with scale ``1
+ w``, times ``linear_sigmoid_gate_scale · sigmoid(z)`` (ASSUMED:
``linear_gating_type``), then ``W_out``.

*Latent-attention layer*: ``c_q = N(u W_qa)``, ``[q_n; q_r] = c_q W_qb`` a
head, ``[c_kv; k_r] = u W_kva``, ``c_kv ← N(c_kv)``; rotary on NEIGHBOURING
pairs (``rope_interleave``) of ``q_r`` and of the one ``k_r`` all heads
share, YaRN frequencies; ``k_n = c_kv W_uk``, ``v = c_kv W_uv`` a head; score
``(q_n · k_n + q_r · k_r) · qk_head_dim^-1/2 · m²``, ``m = 0.1 · mscale_all_dim
· ln(factor) + 1`` (``use_mla_scaling_factor``), causal softmax; ASSUMED
(``gated_attention``): the heads' output times ``sigmoid(u W_g)``; ``W_o``.

*Feed-forward*: below ``first_k_dense_replace`` a gated MLP; else ``s =
sigmoid(u W_r)``, the ``num_experts_per_tok`` largest of ``s + bias``, weights
``s`` of the chosen over their sum (``norm_topk_prob``) times
``routed_scaling_factor``; of the chosen experts those HELD here
(``n_routed_experts`` from ``first_expert_held``; the router is
``router_experts`` wide) add their weighted outputs — what the absent ones
would add is left out, as in the program —, plus the shared expert,
ungated. ASSUMED (``swiglu_limit``): in every gated MLP the gate product is
clamped to ≤ limit and the up product to [−limit, limit] before
``silu(gate) · up``.

``precision`` selects the arithmetic of the matrix products only (router,
recurrence, norms and gates stay float32) and exists for the control of
``correct``: ``float32`` is the reference, ``bfloat16`` the precision the
configuration states, ``float8`` the step below it (e4m3 operands,
per-tensor scales), which has to come out as not correct.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16", "float8")
QUERY_BLOCK = 64        # queries scored against every key at once
TOKEN_BLOCK = 4096      # tokens a gated MLP takes at once
_HI = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------- the pattern
def _layers(sizes: dict) -> list:
    """``(prefix of its stack, index in the stack, latent?, dense?)`` a
    layer, in the published order; layers of one shape are one stack."""
    seen: dict = {}
    out = []
    for l in range(int(sizes["num_hidden_layers"])):
        latent = l in [int(x) for x in sizes["full_attention_layers"]]
        dense = l < int(sizes["first_k_dense_replace"])
        p = ("a" if latent else "l") + ("d" if dense else "m")
        out.append((p, seen.get(p, 0), latent, dense))
        seen[p] = seen.get(p, 0) + 1
    return out


def weight_spec(sizes: dict) -> dict:
    """Name -> (shape, kind) of every weight, all ``matrix`` (N(0, 0.02):
    the norms' weights are zero-centred). ``ld_*`` / ``lm_*``: the stacks of
    the linear-attention layers with a dense MLP / with experts; ``ad_*`` /
    ``am_*``: of the latent-attention layers."""
    g = lambda k: int(sizes[k])  # noqa: E731
    h, v = g("hidden_size"), g("vocab_size")
    hk, dk = g("linear_num_key_heads"), g("linear_key_head_dim")
    hv, dv = g("linear_num_value_heads"), g("linear_value_head_dim")
    chan = 2 * hk * dk + hv * dv
    nh, dn, dr, vd = g("num_attention_heads"), g("qk_nope_head_dim"), \
        g("qk_rope_head_dim"), g("v_head_dim")
    rq, rkv = g("q_lora_rank"), g("kv_lora_rank")
    f, held, wide = g("moe_intermediate_size"), g("n_routed_experts"), \
        g("router_experts")
    spec = {"emb": ((v, h), "matrix"), "head": ((h, v), "matrix"),
            "norm_f": ((h,), "matrix")}
    count: dict = {}
    for p, _, _, _ in _layers(sizes):
        count[p] = count.get(p, 0) + 1
    for p, n in count.items():
        shapes = {"norm_a_pre": (h,), "norm_a_post": (h,),
                  "norm_f_pre": (h,), "norm_f_post": (h,)}
        if p[0] == "l":
            shapes.update(qkv=(h, chan), z=(h, hv * dv), ab=(h, 2 * hv),
                          conv=(g("linear_conv_kernel_dim"), chan),
                          A_log=(hv,), dt_bias=(hv,), o_norm=(dv,),
                          out=(hv * dv, h))
        else:
            shapes.update(q_a=(h, rq), q_norm=(rq,), q_bn=(rq, nh, dn),
                          q_br=(rq, nh, dr), kv_a=(h, rkv + dr),
                          kv_norm=(rkv,), k_b=(rkv, nh, dn),
                          v_b=(rkv, nh, vd), o=(nh, vd, h))
            if sizes.get("gated_attention"):
                shapes["gate"] = (h, nh * vd)
        if p[1] == "d":
            i = g("intermediate_size")
            shapes.update(mlp_gate=(h, i), mlp_up=(h, i), mlp_down=(i, h))
        else:
            shapes.update(router=(h, wide), bias=(wide,),
                          e_gate=(held, h, f), e_up=(held, h, f),
                          e_down=(held, f, h))
            if g("n_shared_experts"):
                shapes.update(s_gate=(h, f), s_up=(h, f), s_down=(f, h))
        spec.update({f"{p}_{name}": ((n,) + s, "matrix")
                     for name, s in shapes.items()})
    return spec


_MIXER = {"l": ("norm_a_pre", "norm_a_post", "qkv", "z", "ab", "conv",
                "A_log", "dt_bias", "o_norm", "out"),
          "a": ("norm_a_pre", "norm_a_post", "q_a", "q_norm", "q_bn", "q_br",
                "kv_a", "kv_norm", "k_b", "v_b", "o", "gate")}
_FEED = {"d": ("norm_f_pre", "norm_f_post", "mlp_gate", "mlp_up", "mlp_down"),
         "m": ("norm_f_pre", "norm_f_post", "router", "bias", "e_gate",
               "e_up", "e_down", "s_gate", "s_up", "s_down")}


# ---------------------------------------------------------------- products
def _fake_quant(x, dtype):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _product(spec: str, a, b, precision: str):
    """One matrix product in the stated arithmetic, result in float32."""
    if precision == "float32":
        return jnp.einsum(spec, a, b, precision=_HI)
    if precision == "bfloat16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16),
                          b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if precision == "float8":
        return jnp.einsum(spec, _fake_quant(a, jnp.float8_e4m3fn),
                          _fake_quant(b, jnp.float8_e4m3fn), precision=_HI)
    raise ValueError(f"precision {precision!r} is not one of {PRECISIONS}")


def _norm(x, w, sizes):
    """ASSUMED (ZeroCenteredGatedNorm): scale ``gating_weight · sigmoid(w)``."""
    y = x / jnp.sqrt((x * x).mean(-1, keepdims=True)
                     + float(sizes["rms_norm_eps"]))
    return y * float(sizes["layernorm_gating_weight"]) * jax.nn.sigmoid(w)


def _swiglu(gate, up, sizes):
    """ASSUMED (swiglu_limit): gate ≤ limit, up in [−limit, limit]."""
    limit = float(sizes.get("swiglu_limit") or 0.0)
    if limit:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return jax.nn.silu(gate) * up


def _gated_mlp(v, gate, up, down, sizes, precision):
    """``down(swiglu(v gate, v up))``, ``TOKEN_BLOCK`` tokens at a time."""
    S = v.shape[0]
    block = min(TOKEN_BLOCK, S)
    assert S % block == 0, (S, block)

    def one(vb):
        a = _swiglu(_product("sh,hf->sf", vb, gate, precision),
                    _product("sh,hf->sf", vb, up, precision), sizes)
        return _product("sf,fh->sh", a, down, precision)

    return jax.lax.map(one, v.reshape(S // block, block, -1)).reshape(S, -1)


# ----------------------------------------------------- the linear mixer
def _linear_attention(u, lw, sizes, precision):
    S = u.shape[0]
    hk, dk = int(sizes["linear_num_key_heads"]), \
        int(sizes["linear_key_head_dim"])
    hv, dv = int(sizes["linear_num_value_heads"]), \
        int(sizes["linear_value_head_dim"])
    taps = int(sizes["linear_conv_kernel_dim"])
    qkv = _product("sh,hc->sc", u, lw["qkv"], precision)
    z = _product("sh,hc->sc", u, lw["z"], precision).reshape(S, hv, dv)
    ab = _product("sh,hc->sc", u, lw["ab"], precision)
    # causal depth-wise convolution: y_t = sum_j w_j x_{t - (K-1) + j}
    padded = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
    y = sum(padded[j:j + S] * lw["conv"][j] for j in range(taps))
    y = jax.nn.silu(y)

    def unit(x):
        return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q = unit(y[:, :hk * dk].reshape(S, hk, dk)) * dk ** -0.5
    k = unit(y[:, hk * dk:2 * hk * dk].reshape(S, hk, dk))
    v = y[:, 2 * hk * dk:].reshape(S, hv, dv)
    q, k = jnp.repeat(q, hv // hk, axis=1), jnp.repeat(k, hv // hk, axis=1)
    alpha = jnp.exp(-jnp.exp(lw["A_log"])
                    * jax.nn.softplus(ab[:, :hv] + lw["dt_bias"]))
    beta = jax.nn.sigmoid(ab[:, hv:])

    def step(S_, x):        # S_ [Hv, value, key]
        q_t, k_t, v_t, a_t, b_t = x
        S_ = a_t[:, None, None] * S_
        S_ = S_ + (b_t[:, None] * (v_t - jnp.einsum(
            "hvk,hk->hv", S_, k_t, precision=_HI)))[:, :, None] \
            * k_t[:, None, :]
        return S_, jnp.einsum("hvk,hk->hv", S_, q_t, precision=_HI)

    _, o = jax.lax.scan(step, jnp.zeros((hv, dv, dk), jnp.float32),
                        (q, k, v, alpha, beta))
    o = o / jnp.sqrt((o * o).mean(-1, keepdims=True)
                     + float(sizes["linear_attn_o_norm_eps"])) \
        * (1.0 + lw["o_norm"])
    # ASSUMED (gated_rmsnorm_sigmoid_zero_centered)
    o = o * float(sizes["linear_sigmoid_gate_scale"]) * jax.nn.sigmoid(z)
    return _product("sc,ch->sh", o.reshape(S, hv * dv), lw["out"], precision)


# ----------------------------------------------------- the latent mixer
def _inverse_frequencies(sizes: dict) -> np.ndarray:
    rot, base = int(sizes["qk_rope_head_dim"]), float(sizes["rope_theta"])
    pos_freqs = base ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    rs = sizes.get("rope_scaling")
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


def _rotate_interleaved(x, sizes):
    """Rotary on ``x`` [S, ..., rot] at positions 0 … S − 1: values 2i and
    2i + 1 are a pair (``rope_interleave``), rotated in place."""
    S = x.shape[0]
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] \
        * jnp.asarray(_inverse_frequencies(sizes), jnp.float32)[None, :]
    shape = (S,) + (1,) * (x.ndim - 2) + (angle.shape[-1],)
    rs = sizes.get("rope_scaling") or {}    # YaRN's attention factor: 1
    m = lambda s: 0.1 * float(rs.get(s, 1)) * math.log(  # noqa: E731
        float(rs["factor"])) + 1.0
    ratio = m("mscale") / m("mscale_all_dim") if rs else 1.0
    cos = jnp.cos(angle).reshape(shape) * ratio
    sin = jnp.sin(angle).reshape(shape) * ratio
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _latent_attention(u, lw, sizes, precision):
    S = u.shape[0]
    nh = int(sizes["num_attention_heads"])
    dn, dr = int(sizes["qk_nope_head_dim"]), int(sizes["qk_rope_head_dim"])
    rkv = int(sizes["kv_lora_rank"])
    cq = _norm(_product("sh,hr->sr", u, lw["q_a"], precision), lw["q_norm"],
               sizes)
    q_n = _product("sr,rnd->snd", cq, lw["q_bn"], precision)
    q_r = _rotate_interleaved(
        _product("sr,rnd->snd", cq, lw["q_br"], precision), sizes)
    kv = _product("sh,hr->sr", u, lw["kv_a"], precision)
    ckv = _norm(kv[:, :rkv], lw["kv_norm"], sizes)
    k_r = _rotate_interleaved(kv[:, rkv:], sizes)
    k_n = _product("sr,rnd->snd", ckv, lw["k_b"], precision)
    v = _product("sr,rnd->snd", ckv, lw["v_b"], precision)
    scale = float(dn + dr) ** -0.5
    rs = sizes.get("rope_scaling")
    if sizes.get("use_mla_scaling_factor") and rs:
        m = 0.1 * float(rs.get("mscale_all_dim", 1)) \
            * math.log(float(rs["factor"])) + 1.0
        scale *= m * m
    block = min(QUERY_BLOCK, S)
    assert S % block == 0, (S, block)
    key_pos = jnp.arange(S)

    def one_block(args):
        qn, qr, first = args
        s = (_product("qnd,tnd->nqt", qn, k_n, precision)
             + _product("qnd,td->nqt", qr, k_r, precision)) * scale
        seen = key_pos[None, :] <= (first + jnp.arange(block))[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return _product("nqt,tnd->qnd", p, v, precision)

    o = jax.lax.map(one_block, (
        q_n.reshape(S // block, block, nh, dn),
        q_r.reshape(S // block, block, nh, dr), jnp.arange(0, S, block)))
    o = o.reshape(S, nh, -1)
    if "gate" in lw:        # ASSUMED (gated_attention)
        o = o * jax.nn.sigmoid(_product("sh,hc->sc", u, lw["gate"],
                                        precision)).reshape(o.shape)
    return _product("snd,ndh->sh", o, lw["o"], precision)


# ------------------------------------------------------------ feed-forward
def _experts(v, lw, sizes, precision):
    """The held experts' weighted sum and the shared expert on ``v`` [S,
    h]: every held expert in turn, over all tokens, weighted by what the
    router gave it (0 for a token that did not choose it)."""
    k = int(sizes["num_experts_per_tok"])
    s = jax.nn.sigmoid(jnp.einsum("sh,he->se", v, lw["router"],
                                  precision=_HI))
    _, ids = jax.lax.top_k(s + lw["bias"][None], k)
    w = jnp.take_along_axis(s, ids, axis=-1)
    if sizes.get("norm_topk_prob", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * float(sizes["routed_scaling_factor"])
    first = int(sizes.get("first_expert_held", 0))

    def one_expert(y, args):
        e, gate, up, down = args
        we = jnp.where(ids == e + first, w, 0.0).sum(-1)           # [S]
        return y + we[:, None] * _gated_mlp(v, gate, up, down, sizes,
                                            precision), None

    n = lw["e_gate"].shape[0]
    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(v),
                        (jnp.arange(n), lw["e_gate"], lw["e_up"],
                         lw["e_down"]))
    if "s_gate" in lw:      # ungated (use_shared_expert_sigmoid false)
        y = y + _gated_mlp(v, lw["s_gate"], lw["s_up"], lw["s_down"], sizes,
                           precision)
    return y


def _mixer_half(x, lw, sizes_key, latent, precision):
    sizes = _SIZES[sizes_key]
    u = _norm(x, lw["norm_a_pre"], sizes)
    y = (_latent_attention if latent else _linear_attention)(
        u, lw, sizes, precision)
    return x + _norm(y, lw["norm_a_post"], sizes)


def _feed_half(x, lw, sizes_key, dense, precision):
    sizes = _SIZES[sizes_key]
    u = _norm(x, lw["norm_f_pre"], sizes)
    if dense:
        y = _gated_mlp(u, lw["mlp_gate"], lw["mlp_up"], lw["mlp_down"],
                       sizes, precision)
    else:
        y = _experts(u, lw, sizes, precision)
    return x + _norm(y, lw["norm_f_post"], sizes)


_SIZES: dict = {}


def _sizes_key(sizes: dict) -> str:
    key = json.dumps({k: v for k, v in sizes.items()
                      if isinstance(v, (int, float, str, bool, list, dict))
                      and k not in ("assumed", "bytes", "param_paths",
                                    "serve", "check", "deployment",
                                    "reduced_why", "derived")},
                     sort_keys=True, default=str)
    _SIZES.setdefault(key, dict(sizes))
    return key


@functools.lru_cache(maxsize=None)
def _jitted_half(sizes_key: str, mixer: bool, flag: bool, precision: str):
    fn = _mixer_half if mixer else _feed_half
    return jax.jit(lambda x, lw: fn(x, lw, sizes_key, flag, precision))


@functools.lru_cache(maxsize=None)
def _jitted_head(sizes_key: str, precision: str):
    return jax.jit(lambda x, w, head: _product(
        "sh,hv->sv", _norm(x, w, _SIZES[sizes_key]), head, precision)[None])


def logits_streamed(leaf, sizes: dict, tokens, precision: str = "float32"):
    """``tokens`` [1, S] -> float32 logits [1, S, vocab]; ``leaf(name)`` /
    ``leaf(name, layer)`` hands over one float32 weight: the weights of half
    a layer (its token mixer, or its feed-forward part) are alive at a
    time, and a gated MLP takes ``TOKEN_BLOCK`` tokens at a time."""
    assert tokens.shape[0] == 1, "one row at a time"
    spec, key = weight_spec(sizes), _sizes_key(sizes)
    x = leaf("emb")[tokens[0]]
    for p, at, latent, dense in _layers(sizes):
        for mixer, names, flag in ((True, _MIXER[p[0]], latent),
                                   (False, _FEED[p[1]], dense)):
            lw = {n: leaf(f"{p}_{n}", at) for n in names
                  if f"{p}_{n}" in spec}
            x = _jitted_half(key, mixer, flag, precision)(x, lw)
            del lw
    return _jitted_head(key, precision)(x, leaf("norm_f"), leaf("head"))


def logits(w: dict, sizes: dict, tokens, precision: str = "float32"):
    """The same from a whole tree ``w`` (name -> float32 array), a row at
    a time: ``tokens`` [B, S] -> [B, S, vocab]."""
    def leaf(name, layer=None):
        return w[name] if layer is None else w[name][layer]

    return jnp.concatenate([logits_streamed(leaf, sizes, tokens[b:b + 1],
                                            precision)
                            for b in range(tokens.shape[0])], axis=0)
