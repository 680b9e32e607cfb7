"""Latent-attention sparse-expert decoder: parameters, forward and loss.

The block is described by its configuration (``config.py``): rotary
positions on a 64-wide part of every query and of one key all heads
share, RMS norms, gated SiLU MLPs, no biases, an untied output head. The
leading ``first_k_dense_replace`` layers have a dense MLP, the rest the
expert layer of ``moe.py``; ``num_nextn_predict_layers`` adds the
multi-token-prediction module, which exists only as a training loss.

Pure functions over a plain parameter tree (no flax): the partition
rules of ``parallel/rules.py`` resolve by leaf name, and the two layer
stacks are ``lax.scan`` over stacked leaves, each layer recomputed in the
backward pass.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from fleetx_tpu.models.mla_moe import moe
from fleetx_tpu.models.mla_moe.config import MLAMoEConfig
from fleetx_tpu.observability.trace import device_scope
from fleetx_tpu.ops import mla_attention

# not in the published config.json; the family's convention
INITIALIZER_RANGE = 0.02

_ATTN_SHAPES = {
    "q_a": lambda c: (c.hidden_size, c.q_lora_rank),
    "q_norm": lambda c: (c.q_lora_rank,),
    "q_b": lambda c: (c.q_lora_rank, c.num_attention_heads, c.qk_head_dim),
    "kv_a": lambda c: (c.hidden_size, c.kv_lora_rank + c.qk_rope_head_dim),
    "kv_norm": lambda c: (c.kv_lora_rank,),
    "kv_b": lambda c: (c.kv_lora_rank, c.num_attention_heads,
                       c.qk_nope_head_dim + c.v_head_dim),
    "out": lambda c: (c.num_attention_heads, c.v_head_dim, c.hidden_size),
}


def _layer_shapes(cfg: MLAMoEConfig, dense: bool) -> dict:
    h = cfg.hidden_size
    shapes = {"attn": {k: f(cfg) for k, f in _ATTN_SHAPES.items()},
              "attn_norm": {"scale": (h,)}, "mlp_norm": {"scale": (h,)}}
    if dense:
        f = cfg.intermediate_size
        shapes["mlp"] = {"gate": (h, f), "up": (h, f), "down": (f, h)}
        return shapes
    f, held = cfg.moe_intermediate_size, cfg.experts_held
    fs = f * cfg.n_shared_experts
    shapes["moe"] = {
        "router": (h, cfg.n_routed_experts),
        "selection_bias": (cfg.n_routed_experts,),
        "experts_gate": (held, h, f), "experts_up": (held, h, f),
        "experts_down": (held, f, h),
        "shared_gate": (h, fs), "shared_up": (h, fs), "shared_down": (fs, h),
    }
    return shapes


def param_shapes(cfg: MLAMoEConfig) -> dict:
    """The parameter tree as shapes; stacked layers lead with their count."""
    h = cfg.hidden_size

    def stacked(n, tree):
        return jax.tree.map(lambda s: (n,) + s, tree,
                            is_leaf=lambda s: isinstance(s, tuple))

    tree = {"embed": {"tokens": (cfg.vocab_size, h)},
            "head": {"kernel": (cfg.vocab_size, h)},
            "final_norm": {"scale": (h,)}}
    if cfg.first_k_dense_replace:
        tree["dense_layers"] = stacked(cfg.first_k_dense_replace,
                                       _layer_shapes(cfg, dense=True))
    if cfg.num_expert_layers:
        tree["moe_layers"] = stacked(cfg.num_expert_layers,
                                     _layer_shapes(cfg, dense=False))
    if cfg.num_nextn_predict_layers:
        tree["mtp"] = {
            "embed_norm": {"scale": (h,)}, "hidden_norm": {"scale": (h,)},
            "proj": (2 * h, h),
            "layers": stacked(1, _layer_shapes(cfg, dense=False)),
            "final_norm": {"scale": (h,)},
        }
    return tree


def init_params(cfg: MLAMoEConfig, rng: jax.Array) -> dict:
    """N(0, ``INITIALIZER_RANGE``) matrices, unit norm scales, a zero
    selection bias."""
    shapes = param_shapes(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    leaves = []
    for i, (path, shape) in enumerate(flat):
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "scale" or name.endswith("_norm"):
            leaf = jnp.ones(shape, cfg.param_dtype)
        elif name == "selection_bias":
            leaf = jnp.zeros(shape, cfg.param_dtype)
        else:
            leaf = INITIALIZER_RANGE * jax.random.normal(
                jax.random.fold_in(rng, i), shape, cfg.param_dtype)
        leaves.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ------------------------------------------------------------------- pieces
def rms_norm(x, scale, eps):
    """``x / rms(x) * scale`` over the last axis, in float32."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope_tables(positions, dim: int, theta: float):
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def _rotate(x, cos, sin):
    """Rotate ``x`` [..., d] whose halves hold the pairs' first and second
    members (see ``_pairs_to_halves``)."""
    x32 = x.astype(jnp.float32)
    a, b = jnp.split(x32, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _pairs_to_halves(w):
    """The published weights hold a rotary pair in neighbouring columns
    (``rope_interleave``). Reordering the columns of BOTH projections to
    (first members, second members) leaves every score unchanged and
    turns the rotation into whole-half arithmetic."""
    return jnp.concatenate([w[..., 0::2], w[..., 1::2]], axis=-1)


@device_scope("attn.proj")
def attention(x, p, cfg: MLAMoEConfig, positions):
    """Latent attention on normed ``x`` [B, S, h]: the products, latent
    norms and rotary are ``attn.proj``, the kernel ``attn.core``."""
    dt, heads = x.dtype, cfg.num_attention_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    b, s, _ = x.shape
    cos, sin = _rope_tables(positions, dr, cfg.rope_theta)     # [B, S, dr/2]
    cq = rms_norm(jnp.einsum("bsh,hr->bsr", x, p["q_a"].astype(dt)),
                  p["q_norm"], cfg.rms_norm_eps)
    q_b = p["q_b"].astype(dt)
    qn = jnp.einsum("bsr,rnd->bnsd", cq, q_b[..., :dn])
    # two heads' rotary queries to a 128-lane row, straight from the product
    pack = 2 if (heads % 2 == 0 and 2 * dr == 128) else 1
    w_qr = _pairs_to_halves(q_b[..., dn:]).reshape(
        q_b.shape[0], heads // pack, pack * dr)
    qr2 = jnp.einsum("bsr,rmd->bmsd", cq, w_qr)
    qr2 = _rotate(qr2.reshape(b, heads // pack, s, pack, dr),
                  cos[:, None, :, None], sin[:, None, :, None]
                  ).reshape(b, heads // pack, s, pack * dr)
    kv_a = p["kv_a"].astype(dt)
    ckv = rms_norm(jnp.einsum("bsh,hr->bsr", x, kv_a[:, :cfg.kv_lora_rank]),
                   p["kv_norm"], cfg.rms_norm_eps)
    kr = _rotate(jnp.einsum("bsh,hd->bsd", x, _pairs_to_halves(
        kv_a[:, cfg.kv_lora_rank:])), cos, sin)
    kv_b = p["kv_b"].astype(dt)
    kn = jnp.einsum("bsr,rnd->bnsd", ckv, kv_b[..., :dn])
    v = jnp.einsum("bsr,rnd->bnsd", ckv, kv_b[..., dn:])
    scale = float(cfg.qk_head_dim) ** -0.5
    with device_scope("attn.core"):
        if (cfg.use_flash_attention and pack == 2 and dn == dv and
                mla_attention.supported(qn, qr2, v)):
            o = mla_attention.mla_flash_attention(qn, qr2, kn, kr, v,
                                                  scale=scale)
        else:
            o = mla_attention.reference_attention(qn, qr2, kn, kr, v,
                                                  scale=scale)
    return jnp.einsum("bnsd,ndh->bsh", o, p["out"].astype(dt))


def _layer(x, p, cfg: MLAMoEConfig, positions, dense: bool):
    eps = cfg.rms_norm_eps
    with device_scope("norm"):
        u = rms_norm(x, p["attn_norm"]["scale"], eps)
    y = attention(u, p["attn"], cfg, positions)
    with device_scope("norm"):
        x = x + y
        y = rms_norm(x, p["mlp_norm"]["scale"], eps)
    if dense:
        dt = x.dtype
        m = p["mlp"]
        with device_scope("mlp"):
            return x + moe.gated_mlp(y, m["gate"].astype(dt),
                                     m["up"].astype(dt),
                                     m["down"].astype(dt)), {}
    out, stats = moe.moe_layer(y, p["moe"], cfg)
    with device_scope("mlp"):
        return x + out, stats


def _stack(x, layers, cfg: MLAMoEConfig, positions, dense: bool):
    """``lax.scan`` over stacked layers; returns ``(x, stats [L, ...])``."""
    def body(x, p):
        return _layer(x, p, cfg, positions, dense)

    with device_scope("stack"):
        return jax.lax.scan(jax.checkpoint(body, prevent_cse=False), x,
                            layers)


def lm_loss_sum(x, norm_scale, head, targets, mask, cfg: MLAMoEConfig):
    """Sum over positions of the masked next-token loss of ``x`` [B, S, h]
    through the final norm and the output head, in blocks of
    ``loss_chunk_rows`` tokens so that the float32 logits of one block
    exist at a time (forward and backward)."""
    h = x.shape[-1]
    n = x.shape[0] * x.shape[1]
    rows = min(cfg.loss_chunk_rows, n)
    assert n % rows == 0, (n, rows)
    blocks = (x.reshape(n // rows, rows, h),
              targets.reshape(n // rows, rows),
              mask.astype(jnp.float32).reshape(n // rows, rows))
    with device_scope("head"):
        w = head.astype(x.dtype)

    @jax.checkpoint
    def block(total, blk):
        xb, tb, mb = blk
        with device_scope("head"):
            y = rms_norm(xb, norm_scale, cfg.rms_norm_eps)
            logits = jnp.einsum("nh,vh->nv", y, w,
                                preferred_element_type=jnp.float32)
        with device_scope("loss"):
            logz = jax.scipy.special.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
            return total + ((logz - picked) * mb).sum(), None

    with device_scope("loss"):
        total, _ = jax.lax.scan(block, jnp.float32(0.0), blocks)
    return total


def _positions(tokens, positions=None):
    if positions is not None:
        return positions
    return jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)


def hidden_states(params, cfg: MLAMoEConfig, tokens, positions):
    """The main model up to (not through) its final norm, and the expert
    layers' stats."""
    with device_scope("embed"):
        x = params["embed"]["tokens"].astype(cfg.dtype)[tokens]
    stats = {}
    if cfg.first_k_dense_replace:
        x, _ = _stack(x, params["dense_layers"], cfg, positions, dense=True)
    if cfg.num_expert_layers:
        x, stats = _stack(x, params["moe_layers"], cfg, positions,
                          dense=False)
    return x, stats


def logits(params, cfg: MLAMoEConfig, tokens, positions=None):
    """Float32 logits of the main head, [B, S, vocab]."""
    x, _ = hidden_states(params, cfg, tokens, _positions(tokens, positions))
    with device_scope("head"):
        y = rms_norm(x, params["final_norm"]["scale"], cfg.rms_norm_eps)
        return jnp.einsum("bsh,vh->bsv", y, params["head"]["kernel"].astype(
            cfg.dtype), preferred_element_type=jnp.float32)


def _mtp_hidden(params, cfg: MLAMoEConfig, x, next_tokens, positions):
    """The prediction module: join the normed embedding of the next token
    (first) with the normed hidden state, project back to the hidden size
    and run one expert block. Returns its state and the block's stats."""
    m, eps = params["mtp"], cfg.rms_norm_eps
    with device_scope("mtp"):
        with device_scope("embed"):
            emb = params["embed"]["tokens"].astype(cfg.dtype)[next_tokens]
        joined = jnp.concatenate([
            rms_norm(emb, m["embed_norm"]["scale"], eps),
            rms_norm(x, m["hidden_norm"]["scale"], eps)], axis=-1)
        x = jnp.einsum("bsk,kh->bsh", joined, m["proj"].astype(cfg.dtype))
        return _stack(x, m["layers"], cfg, positions, dense=False)


def training_loss(params, cfg: MLAMoEConfig, batch: dict):
    """``(loss, metrics)``: the main next-token loss plus
    ``mtp_loss_weight`` x the prediction module's loss of the token after
    (``labels[i + 1]``; the last position has none)."""
    tokens, labels = batch["tokens"], batch["labels"]
    mask = batch["loss_mask"].astype(jnp.float32)
    positions = _positions(tokens, batch.get("position_ids"))
    x, stats = hidden_states(params, cfg, tokens, positions)
    head = params["head"]["kernel"]
    with device_scope("loss"):
        main = lm_loss_sum(x, params["final_norm"]["scale"], head, labels,
                           mask, cfg) / jnp.maximum(mask.sum(), 1.0)
    loss, metrics = main, {"loss_main": main}
    stats_all = [stats] if stats else []
    if cfg.num_nextn_predict_layers:
        xm, mtp_stats = _mtp_hidden(params, cfg, x, labels, positions)
        stats_all.append(mtp_stats)
        # position i holds token i + 1 (its label) and predicts token i + 2
        with device_scope("loss"):
            target = jnp.roll(labels, -1, axis=1)
            tmask = jnp.roll(mask, -1, axis=1).at[:, -1].set(0.0)
            mtp = lm_loss_sum(xm, params["mtp"]["final_norm"]["scale"], head,
                              target, tmask, cfg) \
                / jnp.maximum(tmask.sum(), 1.0)
            loss = loss + cfg.mtp_loss_weight * mtp
        metrics["loss_mtp"] = mtp
    if stats_all:
        with device_scope("moe.route"):     # the load-bias step, counters
            stats = jax.tree.map(lambda *a: jnp.concatenate(a), *stats_all)
            # value zero; its cotangent moves the selection biases by the
            # load
            loss = loss + stats["bias_step"].sum()
            metrics["moe_load_max_over_mean"] = \
                stats["rows_max_over_mean"].max()
            metrics["moe_load_max_over_mean_by_layer"] = \
                stats["rows_max_over_mean"]
            metrics["moe_held_share"] = stats["held_share"].mean()
            metrics["moe_bias_abs_max"] = stats["bias_abs_max"].max()
    metrics["loss"] = loss
    return loss, metrics
