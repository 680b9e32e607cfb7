"""Plain reference: a GPT-2 style decoder, its loss and its gradients.

Written from the architecture (Radford et al. 2019; Megatron-LM's GPT-2
345M): learned token and position embeddings, ``layers`` pre-LayerNorm
blocks of causal multi-head attention and a 4x GELU (tanh form) MLP, a
final LayerNorm and the token embedding tied as the output head. float32
throughout, ``highest`` matmul precision, no kernels, no cache, no
batching tricks. It imports nothing of the program and takes nothing the
program has made.

``precision`` selects the arithmetic of the matrix products only and
exists for the control of ``correct``: ``float32`` is the reference;
``bfloat16`` is the precision the configurations state; ``float8`` is the
step below it (e4m3 operands, e5m2 cotangents, per-tensor scales) and has
to come out as not correct.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

PRECISIONS = ("float32", "bfloat16", "float8")
# name -> (axis, index) of a slice whose gradient is zero by construction:
# the key bias adds the same q.b to every score of a row, and softmax does
# not see it. Adam turns that slice's rounding noise into full-size steps,
# so norms are compared without it.
GRADIENT_FREE = {"qkv_b": (1, 1)}


def weight_spec(sizes: dict) -> dict:
    """Name -> (shape, kind) of every weight; kind is ``matrix`` (decayed,
    N(0, 0.02)), ``bias`` or ``scale`` (not decayed)."""
    L, h = int(sizes["num_layers"]), int(sizes["hidden_size"])
    nh = int(sizes["num_attention_heads"])
    hd, f = h // nh, int(sizes["ffn_hidden_size"])
    return {
        "wte": ((int(sizes["vocab_size"]), h), "matrix"),
        "wpe": ((int(sizes["max_position_embeddings"]), h), "matrix"),
        "ln1_g": ((L, h), "scale"), "ln1_b": ((L, h), "bias"),
        "qkv_w": ((L, h, 3, nh, hd), "matrix"),
        "qkv_b": ((L, 3, nh, hd), "bias"),
        "proj_w": ((L, nh, hd, h), "matrix"), "proj_b": ((L, h), "bias"),
        "ln2_g": ((L, h), "scale"), "ln2_b": ((L, h), "bias"),
        "fc_w": ((L, h, f), "matrix"), "fc_b": ((L, f), "bias"),
        "out_w": ((L, f, h), "matrix"), "out_b": ((L, h), "bias"),
        "lnf_g": ((h,), "scale"), "lnf_b": ((h,), "bias"),
    }


def _fake_quant(x, dtype):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _ste(x, dtype):
    """Quantized value, straight-through gradient."""
    return x + jax.lax.stop_gradient(_fake_quant(x, dtype) - x)


@jax.custom_vjp
def _quant_cotangent(y):
    return y


def _qc_fwd(y):
    return y, None


def _qc_bwd(_, g):
    return (_fake_quant(g, jnp.float8_e5m2),)


_quant_cotangent.defvjp(_qc_fwd, _qc_bwd)


def _product(spec: str, a, b, precision: str):
    """One matrix product in the stated arithmetic, result in float32."""
    if precision == "float32":
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
    if precision == "bfloat16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16),
                          b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if precision == "float8":
        y = jnp.einsum(spec, _ste(a, jnp.float8_e4m3fn),
                       _ste(b, jnp.float8_e4m3fn),
                       precision=jax.lax.Precision.HIGHEST)
        return _quant_cotangent(y)
    raise ValueError(f"precision {precision!r} is not one of {PRECISIONS}")


def _layer_norm(x, g, b, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, lw, eps, precision):
    """One pre-LayerNorm block on ``x`` [B, S, h]."""
    S, hd = x.shape[1], lw["qkv_w"].shape[-1]
    y = _layer_norm(x, lw["ln1_g"], lw["ln1_b"], eps)
    qkv = _product("bsh,hcnd->bcsnd", y, lw["qkv_w"], precision)
    qkv = qkv + lw["qkv_b"][:, None]
    q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
    scores = _product("bqnd,bknd->bnqk", q, k, precision) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = _product("bnqk,bknd->bqnd", probs, v, precision)
    x = x + _product("bsnd,ndh->bsh", attn, lw["proj_w"], precision) \
        + lw["proj_b"]
    y = _layer_norm(x, lw["ln2_g"], lw["ln2_b"], eps)
    y = _gelu_tanh(_product("bsh,hf->bsf", y, lw["fc_w"], precision)
                   + lw["fc_b"])
    return x + _product("bsf,fh->bsh", y, lw["out_w"], precision) \
        + lw["out_b"]


_PER_LAYER = ("ln1_g", "ln1_b", "qkv_w", "qkv_b", "proj_w", "proj_b",
              "ln2_g", "ln2_b", "fc_w", "fc_b", "out_w", "out_b")


def logits(w: dict, sizes: dict, tokens, precision: str = "float32"):
    """Full forward: ``tokens`` [B, S] -> float32 logits [B, S, vocab]."""
    eps = float(sizes.get("layer_norm_epsilon", 1e-5))
    S = tokens.shape[1]
    x = w["wte"][tokens] + w["wpe"][jnp.arange(S)]

    # layer by layer, rematerialised in the backward pass so that a block
    # of rows fits beside the weights
    @jax.checkpoint
    def body(x, lw):
        return _block(x, lw, eps, precision), None

    x, _ = jax.lax.scan(body, x, {k: w[k] for k in _PER_LAYER})
    x = _layer_norm(x, w["lnf_g"], w["lnf_b"], eps)
    return _product("bsh,vh->bsv", x, w["wte"], precision)


def _block_loss_sum(w, sizes, tokens, labels, mask, precision):
    lg = logits(w, sizes, tokens, precision)
    logz = jax.scipy.special.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return ((logz - picked) * mask).sum()


@functools.partial(jax.jit, static_argnames=("sizes_key", "precision",
                                              "rows_per_block"))
def _loss_and_grads(w, batch, sizes_key, precision, rows_per_block):
    sizes = dict(sizes_key)
    rows = batch["tokens"].shape[0]
    denom = jnp.maximum(batch["loss_mask"].sum(), 1.0)
    # block b holds rows b, n + b, 2n + b, ...: one row from each contiguous
    # share of the batch, so a batch split over chips by rows stays split
    # inside every block (the loss is a sum over rows: any grouping serves)
    blocks = jax.tree.map(
        lambda a: jnp.swapaxes(a.reshape(
            (rows_per_block, rows // rows_per_block) + a.shape[1:]), 0, 1),
        {k: batch[k] for k in ("tokens", "labels", "loss_mask")})

    def step(carry, blk):
        total, grads = carry
        s, g = jax.value_and_grad(_block_loss_sum)(
            w, sizes, blk["tokens"], blk["labels"], blk["loss_mask"],
            precision)
        return (total + s, jax.tree.map(jnp.add, grads, g)), None

    zero = jax.tree.map(jnp.zeros_like, w)
    (total, grads), _ = jax.lax.scan(step, (jnp.float32(0.0), zero), blocks)
    return total / denom, jax.tree.map(lambda g: g / denom, grads)


def loss_and_grads(w: dict, sizes: dict, batch: dict,
                   precision: str = "float32", rows_per_block: int = 1):
    """Mean masked next-token loss over the whole batch and its gradient,
    computed in blocks of ``rows_per_block`` rows."""
    key = tuple(sorted((k, v) for k, v in sizes.items()
                       if isinstance(v, (int, float))))
    return _loss_and_grads(w, batch, key, precision, int(rows_per_block))
