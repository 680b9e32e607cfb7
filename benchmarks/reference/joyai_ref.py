"""Plain reference: the JoyAI-LLM-Flash decoder (the DeepSeek-V3 layer
equations, whose configuration keys these are), its training loss and its
gradients, for ONE chip's share of a stated deployment.

Written from the published description (``config.json`` of
``jdopensource/JoyAI-LLM-Flash``; DeepSeek-V3 technical report, sections
2.1 and 2.2): token embedding; ``first_k_dense_replace`` blocks with a
gated SiLU MLP, then blocks with a sparse-expert layer; every block
pre-RMS-norm latent attention and pre-RMS-norm feed-forward with residual
adds; a final RMS norm; an untied output head; one multi-token-prediction
module whose loss is added with weight ``mtp_loss_weight``. float32
throughout, ``highest`` matmul precision, no kernels, no cache. It imports
nothing of the program and takes nothing the program has made.

The share (``/opt/skills/guides/model-configs`` section 4): ``sizes``
gives ``n_routed_experts`` experts HELD here, numbered from
``first_expert_held`` among the ``router_experts`` the router scores. A
token's result is the sum over those of its 8 chosen experts that are held
here, plus the shared expert; what the absent experts would have added is
left out, here exactly as in the program. ``vocab_size`` is the slice.

Departures and choices, each noted where it applies:

- memory, not mathematics: attention runs over blocks of heads, the
  experts one after another over all tokens (a dense sum with zero weight
  where a token did not choose the expert), the two losses in blocks of
  tokens, and ``rows_per_block`` rows at a time, so that an 8,192-token
  row fits beside 680 M float32 weights;
- the selection bias takes no gradient; ``loss_and_grads`` returns, in
  its place, the experts' load over the whole batch less its mean, which
  ``adamw_noaux_ref`` turns into the bias step;
- the prediction module reads the main model's last hidden state BEFORE
  the final norm and joins the next token's normed embedding FIRST
  (``assumed`` in the configuration's file).

``precision`` selects the arithmetic of the matrix products only (the
router's stays float32) and exists for the control of ``correct``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

PRECISIONS = ("float32", "bfloat16", "float8")
# the selection bias moves by the sign of the load, not by a gradient: a
# near-tie in a load (rounding in the router's input) flips a whole step,
# so its norms are left out of the comparison (the whole leaf)
GRADIENT_FREE = {"m_sel_bias": (0, slice(None)),
                 "t_sel_bias": (0, slice(None))}
HEADS_PER_BLOCK = 2
LOSS_ROWS = 2048

_ATTN = ("attn_norm", "q_a", "q_norm", "q_b", "kv_a", "kv_norm", "kv_b",
         "out", "mlp_norm")
_DENSE = _ATTN + ("gate", "up", "down")
_MOE = _ATTN + ("router", "sel_bias", "e_gate", "e_up", "e_down",
                "s_gate", "s_up", "s_down")


def weight_spec(sizes: dict) -> dict:
    """Name -> (shape, kind). Kinds: ``matrix`` (decayed, N(0, 0.02)),
    ``scale`` (norm weights, not decayed), ``selection_bias`` (moved by
    the load). Prefixes: ``d_`` the dense blocks, ``m_`` the expert
    blocks, ``t_`` the prediction module."""
    h, heads = int(sizes["hidden_size"]), int(sizes["num_attention_heads"])
    rq, rkv = int(sizes["q_lora_rank"]), int(sizes["kv_lora_rank"])
    dn, dr = int(sizes["qk_nope_head_dim"]), int(sizes["qk_rope_head_dim"])
    dv, f = int(sizes["v_head_dim"]), int(sizes["intermediate_size"])
    fe, held = int(sizes["moe_intermediate_size"]), \
        int(sizes["n_routed_experts"])
    fs = fe * int(sizes["n_shared_experts"])
    n_dense = int(sizes["first_k_dense_replace"])
    n_moe = int(sizes["num_hidden_layers"]) - n_dense
    attn = {
        "attn_norm": ((h,), "scale"), "q_a": ((h, rq), "matrix"),
        "q_norm": ((rq,), "scale"), "q_b": ((rq, heads, dn + dr), "matrix"),
        "kv_a": ((h, rkv + dr), "matrix"), "kv_norm": ((rkv,), "scale"),
        "kv_b": ((rkv, heads, dn + dv), "matrix"),
        "out": ((heads, dv, h), "matrix"), "mlp_norm": ((h,), "scale"),
    }
    dense = dict(attn, gate=((h, f), "matrix"), up=((h, f), "matrix"),
                 down=((f, h), "matrix"))
    moe = dict(attn, router=((h, int(sizes["router_experts"])), "matrix"),
               sel_bias=((int(sizes["router_experts"]),), "selection_bias"),
               e_gate=((held, h, fe), "matrix"), e_up=((held, h, fe), "matrix"),
               e_down=((held, fe, h), "matrix"), s_gate=((h, fs), "matrix"),
               s_up=((h, fs), "matrix"), s_down=((fs, h), "matrix"))
    v = int(sizes["vocab_size"])
    spec = {"emb": ((v, h), "matrix"), "head": ((v, h), "matrix"),
            "norm_f": ((h,), "scale")}
    for prefix, n, table in (("d_", n_dense, dense), ("m_", n_moe, moe)):
        for name, (shape, kind) in table.items():
            spec[prefix + name] = ((n,) + shape, kind)
    if int(sizes.get("num_nextn_predict_layers", 0)):
        for name, (shape, kind) in moe.items():
            spec["t_" + name] = ((1,) + shape, kind)
        spec.update(t_enorm=((h,), "scale"), t_hnorm=((h,), "scale"),
                    t_proj=((2 * h, h), "matrix"), t_norm_f=((h,), "scale"))
    return spec


# ------------------------------------------------- arithmetic of the control
def _fake_quant(x, dtype):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _ste(x, dtype):
    return x + jax.lax.stop_gradient(_fake_quant(x, dtype) - x)


@jax.custom_vjp
def _quant_cotangent(y):
    return y


_quant_cotangent.defvjp(
    lambda y: (y, None),
    lambda _, g: (_fake_quant(g, jnp.float8_e5m2),))


def _product(spec: str, a, b, precision: str):
    """One matrix product in the stated arithmetic, result in float32."""
    if precision == "float32":
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
    if precision == "bfloat16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16),
                          b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if precision == "float8":
        return _quant_cotangent(jnp.einsum(
            spec, _ste(a, jnp.float8_e4m3fn), _ste(b, jnp.float8_e4m3fn),
            precision=jax.lax.Precision.HIGHEST))
    raise ValueError(f"precision {precision!r} is not one of {PRECISIONS}")


# ------------------------------------------------------------------- layers
def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta: float):
    """Rotary position on ``x`` [B, S, ..., d], pairs in neighbouring
    columns (``rope_interleave``): pair i at position p turns by
    ``p * theta ** (-2 i / d)``; no scaling (``rope_scaling`` null)."""
    d, s = x.shape[-1], x.shape[1]
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * (
        theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    shape = (1, s) + (1,) * (x.ndim - 3) + (d // 2,)
    cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _attention(x, lw, sizes, precision):
    """Latent attention on normed ``x`` [B, S, h]: low-rank queries with
    an RMS norm on the latent, one low-rank key-value latent with an RMS
    norm and a rotary key all heads share, causal softmax over
    ``(q_nope . k_nope + q_rope . k_rope) / sqrt(d_nope + d_rope)``."""
    eps, theta = float(sizes["rms_norm_eps"]), float(sizes["rope_theta"])
    dn, rkv = int(sizes["qk_nope_head_dim"]), int(sizes["kv_lora_rank"])
    dr = int(sizes["qk_rope_head_dim"])
    heads, s = lw["q_b"].shape[1], x.shape[1]
    cq = _rms_norm(_product("bsh,hr->bsr", x, lw["q_a"], precision),
                   lw["q_norm"], eps)
    kv = _product("bsh,hr->bsr", x, lw["kv_a"], precision)
    ckv = _rms_norm(kv[..., :rkv], lw["kv_norm"], eps)
    k_rope = _rope(kv[..., rkv:], theta)                       # [B, S, dr]
    causal = jnp.tril(jnp.ones((s, s), bool))
    hb = math.gcd(heads, HEADS_PER_BLOCK)

    def split(w, axis):       # [.., heads, ..] -> [heads / hb, .., hb, ..]
        shape = w.shape[:axis] + (heads // hb, hb) + w.shape[axis + 1:]
        return jnp.moveaxis(w.reshape(shape), axis, 0)

    @jax.checkpoint
    def head_block(y, ws):
        q_b, kv_b, out = ws
        q = _product("bsr,rnd->bsnd", cq, q_b, precision)
        q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], theta)
        kv_up = _product("bsr,rnd->bsnd", ckv, kv_b, precision)
        k_nope, v = kv_up[..., :dn], kv_up[..., dn:]
        scores = (_product("bqnd,bknd->bnqk", q_nope, k_nope, precision)
                  + _product("bqnd,bkd->bnqk", q_rope, k_rope, precision)
                  ) / math.sqrt(dn + dr)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        o = _product("bnqk,bknd->bqnd", probs, v, precision)
        return y + _product("bsnd,ndh->bsh", o, out, precision), None

    y, _ = jax.lax.scan(head_block, jnp.zeros_like(x),
                        (split(lw["q_b"], 1), split(lw["kv_b"], 1),
                         split(lw["out"], 0)))
    return y


def _gated_mlp(x, gate, up, down, precision):
    g = _product("...h,hf->...f", x, gate, precision)
    u = _product("...h,hf->...f", x, up, precision)
    return _product("...f,fh->...h", jax.nn.silu(g) * u, down, precision)


def _moe(x, lw, sizes, precision):
    """Sparse-expert layer on normed ``x`` [B, S, h] -> (y, load [E]).

    ``s = sigmoid(x W_r)`` in float32; the ``num_experts_per_tok`` largest
    of ``s + b`` are chosen (``noaux_tc``; one group, so no group limit);
    weights are the chosen ``s`` over their sum (``norm_topk_prob``) times
    ``routed_scaling_factor``; the result adds the shared expert. Only the
    experts held here contribute."""
    k, first = int(sizes["num_experts_per_tok"]), \
        int(sizes.get("first_expert_held", 0))
    logits = jnp.einsum("bsh,he->bse", x, lw["router"],
                        precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(s + jax.lax.stop_gradient(lw["sel_bias"]), k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if sizes.get("norm_topk_prob", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * float(sizes["routed_scaling_factor"])
    n_experts = lw["router"].shape[-1]
    onehot = chosen[..., None] == jnp.arange(n_experts)        # [B, S, k, E]
    load = onehot.sum((0, 1, 2)).astype(jnp.float32)
    per_expert = (w[..., None] * onehot).sum(2)                # [B, S, E]

    @jax.checkpoint
    def one_expert(y, ws):
        gate, up, down, weight = ws
        return y + weight[..., None] * _gated_mlp(x, gate, up, down,
                                                  precision), None

    held = lw["e_gate"].shape[0]
    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (lw["e_gate"], lw["e_up"], lw["e_down"],
         jnp.moveaxis(per_expert[..., first:first + held], -1, 0)))
    return y + _gated_mlp(x, lw["s_gate"], lw["s_up"], lw["s_down"],
                          precision), load


def _blocks(x, w, prefix, names, sizes, precision):
    """The stacked blocks ``prefix``; returns ``(x, loads [L, E] | None)``."""
    eps = float(sizes["rms_norm_eps"])

    # layer by layer, rematerialised in the backward pass
    @jax.checkpoint
    def body(x, lw):
        x = x + _attention(_rms_norm(x, lw["attn_norm"], eps), lw, sizes,
                           precision)
        y = _rms_norm(x, lw["mlp_norm"], eps)
        if "router" not in lw:
            return x + _gated_mlp(y, lw["gate"], lw["up"], lw["down"],
                                  precision), None
        out, load = _moe(y, lw, sizes, precision)
        return x + out, load

    return jax.lax.scan(body, x, {n: w[prefix + n] for n in names})


def _hidden(w, sizes, tokens, precision):
    """The main model before its final norm, and the expert blocks' loads."""
    x = w["emb"][tokens]
    x, _ = _blocks(x, w, "d_", _DENSE, sizes, precision)
    return _blocks(x, w, "m_", _MOE, sizes, precision)


def logits(w: dict, sizes: dict, tokens, precision: str = "float32"):
    """Full forward: ``tokens`` [B, S] -> float32 logits [B, S, vocab]."""
    x, _ = _hidden(w, sizes, tokens, precision)
    x = _rms_norm(x, w["norm_f"], float(sizes["rms_norm_eps"]))
    return _product("bsh,vh->bsv", x, w["head"], precision)


def _loss_sum(x, norm, head, targets, mask, eps, precision):
    """Sum of the masked next-token losses, in blocks of tokens."""
    h = x.shape[-1]
    n = x.shape[0] * x.shape[1]
    rows = math.gcd(n, LOSS_ROWS)

    @jax.checkpoint
    def block(total, blk):
        xb, tb, mb = blk
        lg = _product("nh,vh->nv", _rms_norm(xb, norm, eps), head, precision)
        logz = jax.scipy.special.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(lg, tb[:, None], axis=-1)[:, 0]
        return total + ((logz - picked) * mb).sum(), None

    total, _ = jax.lax.scan(block, jnp.float32(0.0), (
        x.reshape(n // rows, rows, h), targets.reshape(n // rows, rows),
        mask.reshape(n // rows, rows)))
    return total


def _block_loss(w, sizes, blk, denoms, precision):
    """This block of rows' part of ``loss_main + lambda * loss_mtp`` and
    the expert blocks' loads, main blocks first."""
    eps = float(sizes["rms_norm_eps"])
    tokens, labels, mask = blk["tokens"], blk["labels"], blk["loss_mask"]
    x, loads = _hidden(w, sizes, tokens, precision)
    loss = _loss_sum(x, w["norm_f"], w["head"], labels, mask, eps,
                     precision) / denoms[0]
    if "t_proj" not in w:
        return loss, loads
    # position i joins its hidden state with the embedding of token i + 1
    # (its label) and predicts token i + 2; the last position has no target
    joined = jnp.concatenate([_rms_norm(w["emb"][labels], w["t_enorm"], eps),
                              _rms_norm(x, w["t_hnorm"], eps)], axis=-1)
    xm = _product("bsk,kh->bsh", joined, w["t_proj"], precision)
    xm, t_loads = _blocks(xm, w, "t_", _MOE, sizes, precision)
    target = jnp.roll(labels, -1, axis=1)
    tmask = jnp.roll(mask, -1, axis=1).at[:, -1].set(0.0)
    mtp = _loss_sum(xm, w["t_norm_f"], w["head"], target, tmask, eps,
                    precision) / denoms[1]
    return loss + float(sizes["mtp_loss_weight"]) * mtp, \
        jnp.concatenate([loads, t_loads])


@functools.partial(jax.jit, static_argnames=("sizes_key", "precision"))
def _block_loss_and_grads(w, blk, denoms, sizes_key, precision):
    (loss, loads), grads = jax.value_and_grad(_block_loss, has_aux=True)(
        w, dict(sizes_key), blk, denoms, precision)
    return loss, loads, grads


@functools.partial(jax.jit, donate_argnums=(0,))
def _add(total, part):
    return jax.tree.map(jnp.add, total, part)


def loss_and_grads(w: dict, sizes: dict, batch: dict,
                   precision: str = "float32", rows_per_block: int = 1):
    """``loss_main + mtp_loss_weight * loss_mtp`` over the whole batch (each
    a mean over its own targets) and its gradient, ``rows_per_block`` rows
    at a time. The selection biases' entries hold the load of every expert
    over the whole batch less its mean, block by block of the model."""
    key = tuple(sorted((k, v) for k, v in sizes.items()
                       if isinstance(v, (int, float, bool))))
    mask = jnp.asarray(batch["loss_mask"], jnp.float32)
    denoms = (jnp.maximum(mask.sum(), 1.0),
              jnp.maximum(mask[:, 1:].sum(), 1.0))
    rows, per = mask.shape[0], int(rows_per_block)
    total = None
    # block b holds rows b, n + b, 2 n + b, ...: one row from each
    # contiguous share of the batch (the loss is a sum over rows)
    n_blocks = rows // per
    for b in range(n_blocks):
        blk = {k: jnp.asarray(batch[k])[b::n_blocks]
               for k in ("tokens", "labels", "loss_mask")}
        blk["loss_mask"] = blk["loss_mask"].astype(jnp.float32)
        part = _block_loss_and_grads(w, blk, denoms, key, precision)
        total = part if total is None else _add(total, part)
    loss, loads, grads = total
    grads = dict(grads)
    excess = loads - loads.mean(-1, keepdims=True)
    n_main = w["m_sel_bias"].shape[0]
    grads["m_sel_bias"] = excess[:n_main]
    if "t_sel_bias" in w:
        grads["t_sel_bias"] = excess[n_main:]
    return loss, grads
