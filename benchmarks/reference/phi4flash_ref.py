"""Plain reference: the Phi-4-mini-flash-reasoning decoder ("SambaY",
arXiv:2507.06607, with differential attention, arXiv:2410.05258) — selective
scan layers alternating with differential attention over a window, one full
attention layer whose keys and values every later attention layer reads,
gated memory units over the last scan layer's output — forward pass to
logits.

Written from the catalog row of Phi-4-mini-flash-reasoning (``model_type:
phi4flash``,
https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json:
its ``config`` and ``described_as``) and the equations of ISSUE 48 /
``docs/samba_y.md``. float32 throughout, ``highest`` matmul precision, no
kernel, no cache, no batching: one row of tokens at a time, a layer at a
time, the scan a ``lax.scan`` over the tokens with the state ``[inner, N]``
as the paper writes it, attention a block of queries against all keys at
once (a ``[40, T, T]`` score never stands whole; blocks change no sum's
order within a row of the map). It imports nothing of the program and
takes nothing the program has made.

LayerNorm (weight and bias, ``layer_norm_eps``), no position signal
anywhere. Layer ``l`` (published numbering, ``N`` layers, ``mb_per_layer``
2), input ``h``: ``u = LN₁(h)``, ``h ← h + Mixer(u)``, ``h ← h +
W_down(silu(g) ⊙ p)`` with ``[g; p] = W_gate_up LN₂(h)``; after the last
layer a LayerNorm, then ``logits = h Eᵀ`` (``tie_word_embeddings``, no
bias). The mixer of layer ``l``:

- ``l`` even, ``l ≤ N/2``: *selective scan*. ``[x; z] = W_in u``; ``x ←
  silu(conv(x) + b_c)`` (causal, depth-wise, ``d_conv`` taps, zeros before
  the first token); ``[δ; B; C] = W_x x``; ``Δ = softplus(W_Δ δ + b_Δ)``;
  ``A = −exp(A_log)``; channel ``c``: ``h_t[c, :] = exp(Δ_t[c] A[c, :]) ⊙
  h_{t−1}[c, :] + Δ_t[c] x_t[c] B_t``, ``y_t[c] = h_t[c, :] · C_t + D[c]
  x_t[c]``; out ``W_out(y ⊙ silu(z))``. Layer ``N/2`` also hands ``m = y``
  on (with the ``D`` skip, before the gate).
- ``l`` odd, ``l < N/2``: *differential attention* over the last
  ``sliding_window`` tokens (``t − window < s ≤ t``); ``l = N/2 + 1``: over
  every earlier token. ``Q`` (40 heads), ``K``, ``V`` (20 heads of 64) from
  one product with bias; pair ``p``: ``q₁ = Q[2p]``, ``q₂ = Q[2p+1]``, its
  key-value pair ``r = p // 2``: ``k₁ = K[2r]``, ``k₂ = K[2r+1]``, ``v =
  [V[2r]; V[2r+1]]``; ``o_p = (softmax(q₁k₁ᵀ/8) − λ softmax(q₂k₂ᵀ/8)) v``,
  ``λ = exp(λ_q1·λ_k1) − exp(λ_q2·λ_k2) + λ_init(l)``, ``λ_init(l) = 0.8 −
  0.6 exp(−0.3 l)``; ``o_p ← RMSNorm(o_p; w, ε) · (1 − λ_init(l))``; the
  pairs joined, out product with bias.
- ``l`` odd, ``l > N/2 + 1``: differential CROSS attention: its own
  queries, layer ``N/2 + 1``'s ``K`` and ``V``.
- ``l`` even, ``l > N/2 + 1``: *gated memory unit*: ``W₂(m ⊙ silu(W₁ u))``.

ASSUMED (not given by the row; one line here, one in the model;
``docs/samba_y.md`` says what each would change): the scan's ``d_state``
16, ``d_conv`` 4, ``expand`` 2, ``dt_rank`` ceil(hidden / 16), its
convolution and step biases, no bias on its in / x / out products; biases on
``Wqkv`` and the attention's out product; the window holds 512 keys, the
token itself among them; ``[g; p]`` and ``[x; z]`` in that order; ``m``
taken with the ``D`` skip; the memory unit's SiLU on the projected input;
the λ vectors and ``λ_init`` as in arXiv:2410.05258.

Departures from the published description: the vocabulary is what the
configuration holds (``vocab_size``: the benchmark's file keeps an eighth,
``published.vocab_size`` beside it; embedding and tied head alike). The
state is a parameter-free ``[inner, N]`` here and ``[N, inner]`` in the
program: the same numbers. One weight is used at a power of two of what is
handed over (``WEIGHT_SCALE_LOG2``: the seeded draw's regime, not the model).

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
#: harness draws every matrix N(0, 0.02); at 5,120 channels that puts the
#: scan's B and C at ~1.7 each and the state's answer at ~10 times the skip:
#: a regime no released scan starts in (Mamba-1's own start for this matrix
#: is uniform in +- 1 / sqrt(inner), 0.0081 here, under steps of 1e-3 .. 1e-1
#: where this draw's are 0.69) and one in which a rounding grows through the
#: nine scan layers until bfloat16 and float8 read alike. At 0.02 / 8 it no
#: longer does and the state still rules the logits; a power of two is exact
#: in bfloat16
WEIGHT_SCALE_LOG2 = {"sc_x": -3}
QUERY_BLOCK = 128      # queries scored against every key at once
_PREFIX = {"scan": "sc", "window": "wn", "full": "fl", "gmu": "gm",
           "cross": "cr"}


# ------------------------------------------------------------- the pattern
def _kind(sizes: dict, layer: int) -> str:
    half, every = int(sizes["num_hidden_layers"]) // 2, \
        int(sizes["mb_per_layer"])
    if layer % every == 0:
        return "scan" if layer <= half else "gmu"
    if layer < half:
        return "window"
    return "full" if layer == half + 1 else "cross"


def _layers(sizes: dict) -> list:
    """``(kind, index in the stack of its kind, published index)`` a layer,
    in the published order."""
    seen: dict = {}
    out = []
    for l in range(int(sizes["num_hidden_layers"])):
        kind = _kind(sizes, l)
        out.append((kind, seen.get(kind, 0), l))
        seen[kind] = seen.get(kind, 0) + 1
    return out


def _scan_sizes(sizes: dict) -> tuple:
    """``(inner, d_state, d_conv, dt_rank)``: ASSUMED (Mamba-1's defaults),
    read from ``sizes["assumed"]`` where the file states them."""
    a = sizes.get("assumed") or {}
    h = int(sizes["hidden_size"])
    return (int(a.get("expand", 2)) * h, int(a.get("d_state", 16)),
            int(a.get("d_conv", 4)),
            int(a.get("dt_rank") or math.ceil(h / 16)))


def _head_dim(sizes: dict) -> int:
    return int(sizes["hidden_size"]) // int(sizes["num_attention_heads"])


def weight_spec(sizes: dict) -> dict:
    """Name -> (shape, kind) of every weight; kind is ``matrix`` (N(0,
    0.02): every product's matrix, every bias, ``A_log`` and the λ vectors)
    or ``scale`` (1 + 0.1 N(0, 1): the norms' weights, the sub-norm's, the
    convolution's taps and ``D``). A prefix a stack of layers of one kind:
    ``sc`` scan, ``wn`` window, ``fl`` full, ``gm`` memory unit, ``cr``
    cross. No head: tied."""
    h, hd, f = int(sizes["hidden_size"]), _head_dim(sizes), \
        int(sizes["intermediate_size"])
    q = int(sizes["num_attention_heads"]) * hd
    kv = int(sizes["num_key_value_heads"]) * hd
    di, n, taps, r = _scan_sizes(sizes)
    spec = {"emb": ((int(sizes["vocab_size"]), h), "matrix"),
            "norm_f_w": ((h,), "scale"), "norm_f_b": ((h,), "matrix")}
    count: dict = {}
    for kind, _, _ in _layers(sizes):
        count[kind] = count.get(kind, 0) + 1
    for kind, L in count.items():
        p = _PREFIX[kind]
        spec.update({
            f"{p}_norm1_w": ((L, h), "scale"),
            f"{p}_norm1_b": ((L, h), "matrix"),
            f"{p}_norm2_w": ((L, h), "scale"),
            f"{p}_norm2_b": ((L, h), "matrix"),
            f"{p}_mlp_gate_up": ((L, h, 2 * f), "matrix"),
            f"{p}_mlp_down": ((L, f, h), "matrix")})
        if kind == "scan":
            spec.update({
                f"{p}_in": ((L, h, 2 * di), "matrix"),
                f"{p}_taps": ((L, taps, di), "scale"),
                f"{p}_conv_b": ((L, di), "matrix"),
                f"{p}_x": ((L, di, r + 2 * n), "matrix"),
                f"{p}_dt": ((L, r, di), "matrix"),
                f"{p}_dt_b": ((L, di), "matrix"),
                f"{p}_A_log": ((L, n, di), "matrix"),
                f"{p}_D": ((L, di), "scale"),
                f"{p}_out": ((L, di, h), "matrix")})
        elif kind == "gmu":
            spec.update({f"{p}_in": ((L, h, di), "matrix"),
                         f"{p}_out": ((L, di, h), "matrix")})
        else:
            width = q if kind == "cross" else q + 2 * kv
            spec.update({
                f"{p}_qkv": ((L, h, width), "matrix"),
                f"{p}_qkv_b": ((L, width), "matrix"),
                f"{p}_o": ((L, q, h), "matrix"),
                f"{p}_o_b": ((L, h), "matrix"),
                f"{p}_lq1": ((L, hd), "matrix"),
                f"{p}_lk1": ((L, hd), "matrix"),
                f"{p}_lq2": ((L, hd), "matrix"),
                f"{p}_lk2": ((L, hd), "matrix"),
                f"{p}_subln": ((L, 2 * hd), "scale")})
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


def _layer_norm(x, w, b, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


# ------------------------------------------------------------------- layers
def _scan(u, lw, sizes, precision):
    """``u`` [S, h] (normed) -> ``(the mixer's output [S, h], y [S,
    inner])``; ``y`` is the scan's output with the ``D`` skip."""
    S = u.shape[0]
    di, n, taps, r = _scan_sizes(sizes)
    xz = _product("sh,hc->sc", u, lw["in"], precision)
    x, z = xz[:, :di], xz[:, di:]                       # ASSUMED order
    padded = jnp.concatenate([jnp.zeros((taps - 1, di), jnp.float32), x])
    conv = sum(lw["taps"][j][None, :] * padded[j:j + S]
               for j in range(taps))
    x = jax.nn.silu(conv + lw["conv_b"])
    dbc = _product("sc,cr->sr", x, lw["x"], precision)
    delta = jax.nn.softplus(
        _product("sr,rc->sc", dbc[:, :r], lw["dt"], precision) + lw["dt_b"])
    b, c = dbc[:, r:r + n], dbc[:, r + n:]
    a = -jnp.exp(lw["A_log"]).T                          # [inner, N]

    def step(h, xs):
        x_t, d_t, b_t, c_t = xs
        h = jnp.exp(d_t[:, None] * a) * h \
            + (d_t * x_t)[:, None] * b_t[None, :]
        # (a sum of products, not ``h @ c_t``: a product of matrices runs
        # in one bfloat16 pass on the chip unless told otherwise)
        return h, (h * c_t[None, :]).sum(-1) + lw["D"] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((di, n), jnp.float32),
                        (x, delta, b, c))
    return _product("sc,ch->sh", y * jax.nn.silu(z), lw["out"],
                    precision), y


def _attention(u, lw, sizes, published, window, shared, precision):
    """``u`` [S, h] (normed) -> ``(the mixer's output, (K, V))``. ``window``
    None: every earlier key. ``shared`` (a cross layer): another layer's
    ``(K, V)`` in place of this layer's own."""
    S = u.shape[0]
    hd = _head_dim(sizes)
    nh, kv = int(sizes["num_attention_heads"]), \
        int(sizes["num_key_value_heads"])
    eps = float(sizes["layer_norm_eps"])
    qkv = _product("sh,hc->sc", u, lw["qkv"], precision) + lw["qkv_b"]
    q = qkv[:, :nh * hd].reshape(S, kv // 2, 2, 2, hd)  # [r, pair in r, map]
    if shared is None:
        k = qkv[:, nh * hd:(nh + kv) * hd]
        v = qkv[:, (nh + kv) * hd:]
    else:
        k, v = shared
    kh = k.reshape(S, kv // 2, 2, hd)                   # [r, map]: K[2r + map]
    vh = v.reshape(S, kv // 2, 2 * hd)                  # [V[2r]; V[2r+1]]
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * published)
    lam = jnp.exp((lw["lq1"] * lw["lk1"]).sum()) \
        - jnp.exp((lw["lq2"] * lw["lk2"]).sum()) + lam0
    block = min(QUERY_BLOCK, S)
    assert S % block == 0, (S, block)
    key_pos = jnp.arange(S)

    def one_block(args):
        qi, first = args
        s = _product("qrpwd,trwd->rpwqt", qi, kh, precision) / math.sqrt(hd)
        q_pos = first + jnp.arange(block)
        seen = key_pos[None, :] <= q_pos[:, None]
        if window is not None:      # ASSUMED: the token itself among them
            seen = seen & (key_pos[None, :] > q_pos[:, None] - window)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        o = _product("rpwqt,tre->qrpwe", p, vh, precision)
        d = o[:, :, :, 0] - lam * o[:, :, :, 1]         # [q, r, pair, 2 hd]
        d = d / jnp.sqrt((d * d).mean(-1, keepdims=True) + eps) * lw["subln"]
        return d * (1.0 - lam0)

    o = jax.lax.map(one_block, (q.reshape((S // block, block) + q.shape[1:]),
                                jnp.arange(0, S, block)))
    return _product("sc,ch->sh", o.reshape(S, nh * hd), lw["o"],
                    precision) + lw["o_b"], (k, v)


def _memory_unit(u, m, lw, precision):
    # ASSUMED: the SiLU on the projected input, not on m
    g = jax.nn.silu(_product("sh,hc->sc", u, lw["in"], precision))
    return _product("sc,ch->sh", m * g, lw["out"], precision)


def _layer(x, lw, carried, published, sizes_key, kind, hands_on, precision):
    """One layer: ``x`` [S, h], ``carried`` what earlier layers handed on
    (``m``, ``k``, ``v``: zeros until made), ``published`` its index (a
    float32 scalar: ``λ_init`` is a function of it), ``hands_on``: the last
    scan layer -> ``(x, carried)``."""
    sizes = _SIZES[sizes_key]
    eps = float(sizes["layer_norm_eps"])
    u = _layer_norm(x, lw["norm1_w"], lw["norm1_b"], eps)
    carried = dict(carried)
    if kind == "scan":
        mixed, y = _scan(u, lw, sizes, precision)
        if hands_on:
            carried["m"] = y                            # ASSUMED: with D
    elif kind == "gmu":
        mixed = _memory_unit(u, carried["m"], lw, precision)
    else:
        window = int(sizes["sliding_window"]) if kind == "window" else None
        shared = (carried["k"], carried["v"]) if kind == "cross" else None
        mixed, kv = _attention(u, lw, sizes, published, window, shared,
                               precision)
        if kind == "full":
            carried["k"], carried["v"] = kv
    h = x + mixed
    f = _layer_norm(h, lw["norm2_w"], lw["norm2_b"], eps)
    gu = _product("sh,hf->sf", f, lw["mlp_gate_up"], precision)
    half = gu.shape[1] // 2                             # ASSUMED: [g; p]
    return h + _product("sf,fh->sh", jax.nn.silu(gu[:, :half]) * gu[:, half:],
                        lw["mlp_down"], precision), carried


_SIZES: dict = {}
_NEEDED = ("hidden_size", "num_attention_heads", "num_key_value_heads",
           "num_hidden_layers", "mb_per_layer", "sliding_window",
           "layer_norm_eps", "assumed")


def _sizes_key(sizes: dict) -> str:
    key = json.dumps({k: sizes.get(k) for k in _NEEDED}, sort_keys=True,
                     default=str)
    _SIZES.setdefault(key, dict(sizes))
    return key


@functools.lru_cache(maxsize=None)
def _jitted_layer(sizes_key: str, kind: str, hands_on: bool, precision: str):
    return jax.jit(lambda x, lw, carried, published: _layer(
        x, lw, carried, published, sizes_key, kind, hands_on, precision))


@functools.lru_cache(maxsize=None)
def _jitted_head(eps: float, precision: str):
    # tie_word_embeddings: the head is the embedding, transposed
    return jax.jit(lambda x, w, b, emb: _product(
        "sh,vh->sv", _layer_norm(x, w, b, eps), emb, precision)[None])


def logits_streamed(leaf, sizes: dict, tokens, precision: str = "float32"):
    """``tokens`` [1, S] -> float32 logits [1, S, vocab]; ``leaf(name)`` /
    ``leaf(name, layer)`` hands over one float32 weight, so one layer's
    weights are alive at a time."""
    assert tokens.shape[0] == 1, "one row at a time"
    spec, key = weight_spec(sizes), _sizes_key(sizes)
    S = tokens.shape[1]
    x = leaf("emb")[tokens[0]]
    di = _scan_sizes(sizes)[0]
    kv = int(sizes["num_key_value_heads"]) * _head_dim(sizes)
    carried = {"m": jnp.zeros((S, di), jnp.float32),
               "k": jnp.zeros((S, kv), jnp.float32),
               "v": jnp.zeros((S, kv), jnp.float32)}
    for kind, at, published in _layers(sizes):
        p = _PREFIX[kind] + "_"
        lw = {n[len(p):]: leaf(n, at) * 2.0 ** WEIGHT_SCALE_LOG2.get(n, 0)
              for n in spec if n.startswith(p)}
        hands_on = published == int(sizes["num_hidden_layers"]) // 2
        x, carried = _jitted_layer(key, kind, hands_on, precision)(
            x, lw, carried, jnp.float32(published))
        del lw
    return _jitted_head(float(sizes["layer_norm_eps"]), precision)(
        x, leaf("norm_f_w"), leaf("norm_f_b"), leaf("emb"))


def logits(w: dict, sizes: dict, tokens, precision: str = "float32"):
    """The same from a whole tree ``w`` (name -> float32 array), a row at
    a time: ``tokens`` [B, S] -> [B, S, vocab]."""
    def leaf(name, layer=None):
        return w[name] if layer is None else w[name][layer]

    return jnp.concatenate([logits_streamed(leaf, sizes, tokens[b:b + 1],
                                            precision)
                            for b in range(tokens.shape[0])], axis=0)
