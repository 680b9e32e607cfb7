"""Plain reference: the AI21-Jamba2-3B decoder (``model_type: jamba`` with
one expert) — selective-scan (Mamba-1) layers whose step, ``B`` and ``C``
pass an RMS norm each, a multi-query attention layer once a period, a dense
gated MLP in every layer — forward pass to logits.

Written from the catalog row of AI21-Jamba2-3B
(https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json: its
``config`` and ``described_as``) and the equations of ISSUE 51 /
``docs/ssm_mqa.md``. float32 throughout, ``highest`` matmul precision, no
kernel, no cache, no batching: one row of tokens at a time, a layer at a
time, the scan a ``lax.scan`` over the positions with the state ``[inner,
N]`` as the paper writes it, attention a block of queries against all keys
at once (a ``[20, T, T]`` score never stands whole; blocks change no sum's
order within a row of the map). It imports nothing of the program, nothing
of another reference, and takes nothing the program has made.

RMS norms with a weight and no bias (``rms_norm_eps``), no position signal
anywhere. Layer ``l`` of ``num_hidden_layers``, input ``h``: ``u =
RMS₁(h)``, ``h ← h + Mixer(u)``, ``h ← h + W_down(silu(W_gate f) ⊙ W_up
f)`` with ``f = RMS₂(h)``; after the last layer an RMS norm, then ``logits
= h Eᵀ`` (``tie_word_embeddings``). The mixer of layer ``l``:

- ``l mod attn_layer_period ≠ attn_layer_offset``: *selective scan*. ``[x;
  z] = W_in u``; ``x ← silu(conv(x) + b_c)`` (causal, depth-wise,
  ``mamba_d_conv`` taps, zeros before the first token); ``[δ; B; C] = W_x
  x``; ``δ ← rms(δ) w_δ``, ``B ← rms(B) w_B``, ``C ← rms(C) w_C`` (eps
  ``rms_norm_eps``); ``Δ = softplus(W_Δ δ + b_Δ)``; ``A = −exp(A_log)``;
  channel ``c``: ``h_t[c, :] = exp(Δ_t[c] A[c, :]) ⊙ h_{t−1}[c, :] +
  Δ_t[c] x_t[c] B_t``, ``y_t[c] = h_t[c, :] · C_t + D[c] x_t[c]``; out
  ``W_out(y ⊙ silu(z))``.
- otherwise: causal softmax attention, ``num_attention_heads`` query heads
  over ``num_key_value_heads`` key-value heads of ``head_dim``, scores over
  ``sqrt(head_dim)``, no bias, nothing rotated, no window.

ASSUMED (not given by the row; one line here, one in the model;
``docs/ssm_mqa.md`` says what each would change): ``[x; z]`` and ``[δ; B;
C]`` in that order; the attention layers are those with ``l mod 14 = 7``
(the ``jamba`` convention for the two keys); ``head_dim = hidden_size /
num_attention_heads``; ``[q; k; v]`` side by side in one matrix.

Departure from the published description: one weight is used at a power of
two of what is handed over (``WEIGHT_SCALE_LOG2``: the seeded draw's regime,
not the model).

``precision`` selects the arithmetic of the matrix products only (the scan,
the norms and the gates are element-wise and stay float32) and exists for
the control of ``correct``: ``float32`` is the reference, ``bfloat16`` the
precision the configuration states, ``float8`` the step below it (e4m3
operands, per-tensor scales), which has to come out as not correct.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

PRECISIONS = ("float32", "bfloat16", "float8")
#: weight name -> log2 of the factor on the weight as it is handed over. The
#: harness draws every norm's weight 1 + 0.1 N(0, 1); B and C are NORMED, so
#: |B . C| is ~4 whatever W_x's draw and the state's answer stands at ~3
#: times its skip: a regime in which a rounding grows through the 26 scan
#: layers until bfloat16 and float8 read alike (PERF.md section 6, PR 51).
#: The answer is linear in C: with C's norm's weight at a sixteenth it no
#: longer does, and the state still rules the logits; a power of two is exact
WEIGHT_SCALE_LOG2 = {"sc_c_norm_w": -4}
QUERY_BLOCK = 128      # queries scored against every key at once
_PREFIX = {"scan": "sc", "full": "at"}


# ------------------------------------------------------------- the pattern
def _kind(sizes: dict, layer: int) -> str:
    # ASSUMED: the ``jamba`` convention for the two keys
    return "full" if layer % int(sizes["attn_layer_period"]) \
        == int(sizes["attn_layer_offset"]) else "scan"


def _layers(sizes: dict) -> list:
    """``(kind, index in the stack of its kind)`` a layer, in the published
    order."""
    seen: dict = {}
    out = []
    for l in range(int(sizes["num_hidden_layers"])):
        kind = _kind(sizes, l)
        out.append((kind, seen.get(kind, 0)))
        seen[kind] = seen.get(kind, 0) + 1
    return out


def _scan_sizes(sizes: dict) -> tuple:
    """``(inner, d_state, d_conv, dt_rank)``, all published."""
    return (int(sizes["mamba_expand"]) * int(sizes["hidden_size"]),
            int(sizes["mamba_d_state"]), int(sizes["mamba_d_conv"]),
            int(sizes["mamba_dt_rank"]))


def _head_dim(sizes: dict) -> int:
    # ASSUMED: the row's head_dim is null
    return int(sizes["hidden_size"]) // int(sizes["num_attention_heads"])


def weight_spec(sizes: dict) -> dict:
    """Name -> (shape, kind) of every weight; kind is ``matrix`` (N(0,
    0.02): every product's matrix, the two biases and ``A_log``) or
    ``scale`` (1 + 0.1 N(0, 1): every norm's weight, the convolution's taps
    and ``D``). A prefix a stack of layers of one kind: ``sc`` scan, ``at``
    attention. No head: tied."""
    h, hd, f = int(sizes["hidden_size"]), _head_dim(sizes), \
        int(sizes["intermediate_size"])
    q = int(sizes["num_attention_heads"]) * hd
    kv = int(sizes["num_key_value_heads"]) * hd
    di, n, taps, r = _scan_sizes(sizes)
    spec = {"emb": ((int(sizes["vocab_size"]), h), "matrix"),
            "norm_f_w": ((h,), "scale")}
    count: dict = {}
    for kind, _ in _layers(sizes):
        count[kind] = count.get(kind, 0) + 1
    for kind, L in count.items():
        p = _PREFIX[kind]
        spec.update({
            f"{p}_norm1_w": ((L, h), "scale"),
            f"{p}_norm2_w": ((L, h), "scale"),
            f"{p}_mlp_gate": ((L, h, f), "matrix"),
            f"{p}_mlp_up": ((L, h, f), "matrix"),
            f"{p}_mlp_down": ((L, f, h), "matrix")})
        if kind == "scan":
            spec.update({
                f"{p}_in": ((L, h, 2 * di), "matrix"),
                f"{p}_taps": ((L, taps, di), "scale"),
                f"{p}_conv_b": ((L, di), "matrix"),
                f"{p}_x": ((L, di, r + 2 * n), "matrix"),
                f"{p}_dt_norm_w": ((L, r), "scale"),
                f"{p}_b_norm_w": ((L, n), "scale"),
                f"{p}_c_norm_w": ((L, n), "scale"),
                f"{p}_dt": ((L, r, di), "matrix"),
                f"{p}_dt_b": ((L, di), "matrix"),
                f"{p}_A_log": ((L, n, di), "matrix"),
                f"{p}_D": ((L, di), "scale"),
                f"{p}_out": ((L, di, h), "matrix")})
        else:
            spec.update({
                f"{p}_qkv": ((L, h, q + 2 * kv), "matrix"),
                f"{p}_o": ((L, q, h), "matrix")})
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


def _rms_norm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


# ------------------------------------------------------------------- layers
def _scan(u, lw, sizes, precision):
    """``u`` [S, h] (normed) -> the mixer's output [S, h]."""
    S = u.shape[0]
    di, n, taps, r = _scan_sizes(sizes)
    eps = float(sizes["rms_norm_eps"])
    xz = _product("sh,hc->sc", u, lw["in"], precision)
    x, z = xz[:, :di], xz[:, di:]                       # ASSUMED order
    padded = jnp.concatenate([jnp.zeros((taps - 1, di), jnp.float32), x])
    conv = sum(lw["taps"][j][None, :] * padded[j:j + S]
               for j in range(taps))
    x = jax.nn.silu(conv + lw["conv_b"])
    dbc = _product("sc,cr->sr", x, lw["x"], precision)  # ASSUMED order
    step = _rms_norm(dbc[:, :r], lw["dt_norm_w"], eps)
    b = _rms_norm(dbc[:, r:r + n], lw["b_norm_w"], eps)
    c = _rms_norm(dbc[:, r + n:], lw["c_norm_w"], eps)
    delta = jax.nn.softplus(
        _product("sr,rc->sc", step, lw["dt"], precision) + lw["dt_b"])
    a = -jnp.exp(lw["A_log"]).T                          # [inner, N]

    def one(h, xs):
        x_t, d_t, b_t, c_t = xs
        h = jnp.exp(d_t[:, None] * a) * h \
            + (d_t * x_t)[:, None] * b_t[None, :]
        # (a sum of products, not ``h @ c_t``: a product of matrices runs
        # in one bfloat16 pass on the chip unless told otherwise)
        return h, (h * c_t[None, :]).sum(-1) + lw["D"] * x_t

    _, y = jax.lax.scan(one, jnp.zeros((di, n), jnp.float32),
                        (x, delta, b, c))
    return _product("sc,ch->sh", y * jax.nn.silu(z), lw["out"], precision)


def _attention(u, lw, sizes, precision):
    """``u`` [S, h] (normed) -> the mixer's output [S, h]: every query head
    against its key-value head's keys, causal, a block of queries at a
    time."""
    S = u.shape[0]
    hd = _head_dim(sizes)
    nh, kv = int(sizes["num_attention_heads"]), \
        int(sizes["num_key_value_heads"])
    qkv = _product("sh,hc->sc", u, lw["qkv"], precision)
    q = qkv[:, :nh * hd].reshape(S, kv, nh // kv, hd)
    k = qkv[:, nh * hd:(nh + kv) * hd].reshape(S, kv, hd)
    v = qkv[:, (nh + kv) * hd:].reshape(S, kv, hd)
    block = min(QUERY_BLOCK, S)
    assert S % block == 0, (S, block)
    key_pos = jnp.arange(S)

    def one_block(args):
        qi, first = args
        s = _product("qkgd,tkd->kgqt", qi, k, precision) / math.sqrt(hd)
        seen = key_pos[None, :] <= (first + jnp.arange(block))[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return _product("kgqt,tkd->qkgd", p, v, precision)

    o = jax.lax.map(one_block, (q.reshape((S // block, block) + q.shape[1:]),
                                jnp.arange(0, S, block)))
    return _product("sc,ch->sh", o.reshape(S, nh * hd), lw["o"], precision)


def _layer(x, lw, sizes_key, kind, precision):
    """One layer: ``x`` [S, h] -> [S, h]."""
    sizes = _SIZES[sizes_key]
    eps = float(sizes["rms_norm_eps"])
    u = _rms_norm(x, lw["norm1_w"], eps)
    mixer = _scan if kind == "scan" else _attention
    h = x + mixer(u, lw, sizes, precision)
    f = _rms_norm(h, lw["norm2_w"], eps)
    g = jax.nn.silu(_product("sh,hf->sf", f, lw["mlp_gate"], precision)) \
        * _product("sh,hf->sf", f, lw["mlp_up"], precision)
    return h + _product("sf,fh->sh", g, lw["mlp_down"], precision)


_SIZES: dict = {}
_NEEDED = ("hidden_size", "intermediate_size", "num_attention_heads",
           "num_key_value_heads", "num_hidden_layers", "attn_layer_period",
           "attn_layer_offset", "mamba_d_state", "mamba_d_conv",
           "mamba_expand", "mamba_dt_rank", "rms_norm_eps")


def _sizes_key(sizes: dict) -> str:
    key = json.dumps({k: sizes.get(k) for k in _NEEDED}, sort_keys=True,
                     default=str)
    _SIZES.setdefault(key, dict(sizes))
    return key


@functools.lru_cache(maxsize=None)
def _jitted_layer(sizes_key: str, kind: str, precision: str):
    return jax.jit(lambda x, lw: _layer(x, lw, sizes_key, kind, precision))


@functools.lru_cache(maxsize=None)
def _jitted_head(eps: float, precision: str):
    # tie_word_embeddings: the head is the embedding, transposed
    return jax.jit(lambda x, w, emb: _product(
        "sh,vh->sv", _rms_norm(x, w, eps), emb, precision)[None])


def logits_streamed(leaf, sizes: dict, tokens, precision: str = "float32"):
    """``tokens`` [1, S] -> float32 logits [1, S, vocab]; ``leaf(name)`` /
    ``leaf(name, layer)`` hands over one float32 weight, so one layer's
    weights are alive at a time."""
    assert tokens.shape[0] == 1, "one row at a time"
    spec, key = weight_spec(sizes), _sizes_key(sizes)
    x = leaf("emb")[tokens[0]]
    for kind, at in _layers(sizes):
        p = _PREFIX[kind] + "_"
        lw = {n[len(p):]: leaf(n, at) * 2.0 ** WEIGHT_SCALE_LOG2.get(n, 0)
              for n in spec if n.startswith(p)}
        x = _jitted_layer(key, kind, precision)(x, lw)
        del lw
    return _jitted_head(float(sizes["rms_norm_eps"]), precision)(
        x, leaf("norm_f_w"), leaf("emb"))


def logits(w: dict, sizes: dict, tokens, precision: str = "float32"):
    """The same from a whole tree ``w`` (name -> float32 array), a row at
    a time: ``tokens`` [B, S] -> [B, S, vocab]."""
    def leaf(name, layer=None):
        return w[name] if layer is None else w[name][layer]

    return jnp.concatenate([logits_streamed(leaf, sizes, tokens[b:b + 1],
                                            precision)
                            for b in range(tokens.shape[0])], axis=0)
