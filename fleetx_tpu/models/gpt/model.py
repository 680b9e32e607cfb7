"""GPT decoder family, TPU-native.

Re-designs the reference GPT models (``ppfleetx/models/language_model/gpt/dygraph/
single_model.py`` and ``hybrid_model.py``) as ONE pure-functional Flax module.
The reference maintains three hand-wired variants — single-card, hybrid
(Megatron TP layers + sequence parallel + recompute granularities,
``hybrid_model.py:69-962``) and pipeline (``GPTForPretrainingPipe``) — because
parallelism there is imperative.  Here parallelism is metadata: every kernel
and activation carries *logical* axis names (see ``parallel/sharding.py``) and
the same module runs single-chip or 3D-sharded depending on the mesh rules.

Key mappings (reference → here):
- fused qkv (``single_model.py:98``)            → one [embed, 3, heads, kv] einsum
- ColumnParallel/RowParallel (``hybrid_model.py:111-112``) → ``heads``/``mlp``
  logical axes on kernels
- fused causal softmax ``core_attn`` (``hybrid_model.py:268-298``) →
  Pallas flash attention (``ops/flash_attention.py``) or XLA-fused einsum path
- recompute granularities full/full_attn/core_attn (``hybrid_model.py:332-539``)
  → ``jax.checkpoint`` policies on the scanned layer
- sequence parallel scatter/gather (``hybrid_model.py:613-619,738-740``) →
  ``act_seq`` logical constraint
- kv-cache Cache namedtuple (``single_model.py:164-188``) → explicit decode
  cache pytree threaded through ``lax.scan``
"""

from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn
from flax import struct
from jax.ad_checkpoint import checkpoint_name

from fleetx_tpu.observability.trace import device_scope
from fleetx_tpu.utils.log import logger

_NEG_INF_F32 = -1e30  # finite stand-in for -inf (keeps exp/grad NaN-free)

param_with_axes = nn.with_logical_partitioning
with_logical = nn.with_logical_constraint


@dataclasses.dataclass(unsafe_hash=True)
class GPTConfig:
    """Architecture + execution config (reference yaml ``Model:`` section)."""

    vocab_size: int = 50304
    hidden_size: int = 1024
    num_layers: int = 24
    num_attention_heads: int = 16
    ffn_hidden_size: int | None = None  # defaults to 4*hidden
    max_position_embeddings: int = 1024
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    initializer_range: float = 0.02
    layer_norm_epsilon: float = 1e-5
    use_recompute: bool = False
    # full | full_attn | core_attn (reference granularities) | dots
    # ("dots" keeps matmul outputs and recomputes elementwise — the
    # TPU-native middle ground between memory and recompute FLOPs)
    recompute_granularity: str = "full"
    scan_layers: bool = True
    scan_unroll: int = 1  # layers per scan-body unroll (perf lever)
    # dtype for remat-saved residuals (docs/bandwidth_levers.md): when set
    # (e.g. bfloat16), the remat-saveable matmul outputs are routed through
    # a named cast and the "dots" policy saves the CAST values instead of
    # the originals — halving the scan-stacked dynamic-update-slice bytes
    # the backward pays per layer; the backward upcasts on use. None keeps
    # residuals at the compute dtype. Effective only with use_recompute +
    # "dots" granularity on dense (non-MoE) stacks — elsewhere the casts
    # stay inert instead of quantising the forward for no saving
    # (_residual_casts_active).
    remat_save_dtype: Any = None
    # write remat-saved residuals in their CONSUMED layout
    # (docs/bandwidth_levers.md): transpose the named saved values at the
    # save point so the scan-stacked buffer is laid out the way the
    # backward reads it (res_qkv: [b,3,s,n,d] -> [3,b,s,n,d], making the
    # q/k/v split contiguous leading slices instead of strided mid-axis
    # copies) and re-constrain the stacked values so GSPMD cannot
    # re-introduce the copy. Exact math — only layout changes. Same
    # activation gate as remat_save_dtype (use_recompute + "dots" on
    # dense stacks); the two compose into ONE save-point transform
    # pipeline (_save_residual).
    remat_consumed_layout: bool = True
    # dtype of the gradient-accumulation scan carry (docs/zero_sharding.md):
    # fp32 (default) accumulates microbatch grads in full precision
    # regardless of the compute dtype; bfloat16 opt-in halves the
    # accumulator bytes that stay live across the whole step — under ZeRO-2
    # the carry is additionally fsdp-sharded. None (YAML: "native") keeps
    # the grads' native dtype (legacy behaviour).
    grad_accum_dtype: Any = jnp.float32
    use_flash_attention: bool = True
    # single-pass fused flash backward (ops/flash_attention.py): one Pallas
    # kernel sweeps the (q-block, k-block) tiles once and emits dq/dk/dv
    # together — one backward kernel per layer where the split path runs a
    # dq and a dkv kernel that each recompute P. `flash_bwd_roofline` 21.7 %
    # (GPT-345M) / 43.6 % (GPT-1.3B) of the compute floor (ledger, PR 30);
    # fused against split: not measured on the chip (ROADMAP S10). Applies
    # only where fused_backward_supported admits the shape; other shapes
    # (wide heads, non-tiling seqs) keep the split kernels regardless.
    flash_fused_bwd: bool = True
    # fused residual-add + f32 LayerNorm + output cast (ops/fused_norm.py):
    # one Pallas pass per pre-norm LayerNorm deletes the elementwise HBM
    # round-trips XLA bills around the norm (the `elementwise` trace line);
    # shapes `fused_norm_supported` rejects keep the unfused jnp path.
    # f32 loss/grads are bitwise identical on/off.
    fused_residual_norm: bool = True
    fused_linear: bool = True  # kept for config parity; XLA fuses bias adds
    sequence_parallel: bool = False
    use_ring_attention: bool = False  # context parallelism over the seq axis
    # stream incoming ring K/V blocks in chunks of this many tokens to bound
    # per-step score memory (None = whole block at once)
    ring_kv_chunk: Optional[int] = None
    # memory-efficient LM head: compute the training loss by scanning vocab
    # chunks of this size instead of materialising [b, s, vocab] logits
    vocab_chunk: Optional[int] = None
    use_qat: bool = False      # int8 fake-quant on linears (ops/quantization.py)
    qat_bits: int = 8          # weight fake-quant width (Quantization.weight_bits)
    qat_act_bits: int = 8      # activation width (Quantization.activation_bits)
    moe_num_experts: int = 0   # 0 = dense FFN; >0 = MoE (models/gpt/moe.py)
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    pp_degree: int = 1         # pipeline stages (reference pp_degree)
    pp_microbatches: int = 0   # 0 → defaults to pp_degree (ref accumulate_steps)
    virtual_pp_degree: int = 1  # interleaved chunks/device (ref virtual pp)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @property
    def ffn_dim(self) -> int:
        return self.ffn_hidden_size or 4 * self.hidden_size

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


def _flash_residuals_saveable(prim, *_, **__) -> bool:
    """Remat-policy predicate: save Pallas kernel outputs. The flash
    kernel is a ``custom_vjp`` whose primal outputs (attention out + the
    per-row logsumexp) ARE its backward residuals; remat inlines the vjp
    fwd rule, so the policy sees them as outputs of the ``pallas_call``
    primitive (verified — custom_vjp_call never reaches the policy, and
    the ``shard_map`` of the sharded path is transparent too). The stock
    dots policy rejects them (a Mosaic custom call is not a dot), which
    made the "dots" granularity rerun the whole forward flash kernel
    inside the backward — one more kernel pass per layer; with and
    without it: not measured on the chip (ROADMAP S10). Saving them
    costs ~17 MB/layer at GPT-345M 8 x 1024. Count asserted by
    ``tests/test_flash_attention.py::test_dots_policy_saves_flash_residuals``."""
    return getattr(prim, "name", "") == "pallas_call"


#: the remat-saveable intermediates routed through the ``remat_save_dtype``
#: cast — one name per matmul output the stock dots policy would save; the
#: ``save_only_these_names`` policy keys on exactly this set
RESIDUAL_NAMES = ("res_qkv", "res_attn_out", "res_mlp_wi", "res_mlp_wo")

#: consumed-layout transposes (docs/bandwidth_levers.md): per residual
#: name, the permutation applied at the SAVE point so the scan-stacked
#: buffer is written the way the backward reads it. Only ``res_qkv`` needs
#: one — [b, 3, s, n, d] → [3, b, s, n, d] makes the backward's q/k/v
#: split three contiguous leading slices (XLA folds the replayed inverse
#: transpose + slice into a plain slice) where the stock layout forces a
#: strided mid-axis gather per layer. The other three residuals are
#: already produced in the layout their consuming matmuls read
#: ([b, s, features], contracted over the trailing dim), so their
#: transform is identity.
RESIDUAL_CONSUMED_PERMS: dict[str, tuple[int, ...]] = {
    "res_qkv": (1, 0, 2, 3, 4),
}

#: logical specs re-constraining the saved (consumed-layout) values: the
#: scan stacks them into [layers, ...] buffers, and without an explicit
#: constraint GSPMD may re-shard the stacked buffer between the forward
#: write and the backward read — re-introducing exactly the copy the
#: transpose removed. Specs mirror the activation constraints the forward
#: applies after each save point.
RESIDUAL_CONSUMED_SPECS: dict[str, tuple] = {
    "res_qkv": (None, "batch", "act_seq", "act_heads", "act_kv"),
    "res_attn_out": ("batch", "act_seq", "act_embed"),
    "res_mlp_wi": ("batch", "act_seq", "mlp"),
    "res_mlp_wo": ("batch", "act_seq", "act_embed"),
}


def _transform_gate_active(cfg: GPTConfig) -> bool:
    """Shared activation gate for BOTH save-point transforms: the "dots"
    policy is the only consumer of the residual names, so outside
    use_recompute+dots the transforms would alter the forward for zero
    benefit; MoE stacks don't carry the names (MoEMlp's expert matmuls
    would silently lose their saveability under a names-only policy), so
    both levers stay off there too."""
    return (cfg.use_recompute and cfg.recompute_granularity == "dots"
            and cfg.moe_num_experts == 0)


def _residual_casts_active(cfg: GPTConfig) -> bool:
    """True when the named residual casts actually buy saved bytes."""
    return cfg.remat_save_dtype is not None and _transform_gate_active(cfg)


def _residual_layouts_active(cfg: GPTConfig) -> bool:
    """True when the consumed-layout transposes apply (exact math — the
    gate exists so the inert configs keep a byte-identical program)."""
    return cfg.remat_consumed_layout and _transform_gate_active(cfg)


def _residual_transforms_active(cfg: GPTConfig) -> bool:
    """Either save-point transform on → the names-keyed policy applies."""
    return _residual_casts_active(cfg) or _residual_layouts_active(cfg)


def _save_residual(x: jax.Array, name: str, cfg: GPTConfig) -> jax.Array:
    """Route a remat-saveable intermediate through the save-point
    transform pipeline: consumed-layout transpose → dtype cast → sharding
    constraint → ``checkpoint_name`` tag → inverse cast/transpose.

    One pipeline serves both levers (docs/bandwidth_levers.md): with the
    casts active (``_residual_casts_active``) the tagged value is the
    low-precision copy (``save_only_these_names`` saves it; the backward
    replays only the upcast) — the round-trip deliberately quantises the
    forward too, since saved-vs-recomputed values must agree across the
    remat boundary. With the layouts active
    (``_residual_layouts_active``) the tagged value is additionally
    transposed into its consumed layout and re-constrained, so the scan
    writes the stacked buffer the way the backward reads it; the forward
    continues from the inverse transpose (exact, layout-only).
    """
    casts = _residual_casts_active(cfg)
    layouts = _residual_layouts_active(cfg)
    if not casts and not layouts:
        return x
    orig = x.dtype
    perm = RESIDUAL_CONSUMED_PERMS.get(name) if layouts else None
    y = jnp.transpose(x, perm) if perm is not None else x
    if casts:
        y = y.astype(cfg.remat_save_dtype)
    if layouts:
        spec = RESIDUAL_CONSUMED_SPECS.get(name)
        if spec is not None and len(spec) == y.ndim:
            y = with_logical(y, spec)
    y = checkpoint_name(y, name).astype(orig)
    if perm is not None:
        inv = [0] * len(perm)
        for i, p in enumerate(perm):
            inv[p] = i
        y = jnp.transpose(y, tuple(inv))
    return y


def _dots_policy(cfg: GPTConfig):
    """The "dots" remat policy: matmul outputs + flash residuals.

    With either save-point transform active, the matmul outputs are saved
    through their named transformed copies (``_save_residual``) INSTEAD of
    the raw dot outputs — same remat structure, consumed-layout stacks
    and/or half the stacked-residual bytes at bf16."""
    if _residual_transforms_active(cfg):
        dots = jax.checkpoint_policies.save_only_these_names(*RESIDUAL_NAMES)
    else:
        dots = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if not cfg.use_flash_attention:
        return dots
    return jax.checkpoint_policies.save_from_both_policies(
        dots, _flash_residuals_saveable)


def _dense_init(cfg: GPTConfig):
    return nn.initializers.normal(stddev=cfg.initializer_range)


@struct.dataclass
class DecodeCache:
    """KV cache for autoregressive decode (reference Cache, ``single_model.py:77``).

    ``mask`` records which cached key positions are valid — left-padded
    prompt positions stay masked forever (reference left-pad handling,
    ``language_module.py:221-243``).
    """

    key: jax.Array    # [layers, batch, max_len, heads, head_dim]
    value: jax.Array  # [layers, batch, max_len, heads, head_dim]
    index: jax.Array  # [] int32 — number of tokens already cached
    mask: jax.Array   # [batch, max_len] bool — True where the key is real


def init_cache(cfg: GPTConfig, batch: int, max_len: int,
               dtype: Any = None) -> DecodeCache:
    """Allocate an empty decode cache for ``batch`` rows of ``max_len``."""
    dtype = dtype or cfg.dtype
    shape = (cfg.num_layers, batch, max_len, cfg.num_attention_heads, cfg.head_dim)
    return DecodeCache(key=jnp.zeros(shape, dtype), value=jnp.zeros(shape, dtype),
                       index=jnp.zeros((), jnp.int32),
                       mask=jnp.zeros((batch, max_len), bool))


class MultiHeadAttention(nn.Module):
    """Causal self-attention with fused qkv and optional flash-attention core.

    Reference: ``single_model.py:43-258`` / ``hybrid_model.py:69-349``.
    """

    cfg: GPTConfig

    @nn.compact
    def __call__(self, x: jax.Array, *, layer_cache: Optional[dict] = None,
                 deterministic: bool = True,
                 attention_mask: Optional[jax.Array] = None,
                 ) -> tuple[jax.Array, Optional[dict]]:
        cfg = self.cfg
        h, nh, hd = cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim

        qkv_kernel = self.param(
            "qkv_kernel",
            param_with_axes(_dense_init(cfg), ("embed", None, "heads", "kv")),
            (h, 3, nh, hd), cfg.param_dtype)
        qkv_bias = self.param(
            "qkv_bias", param_with_axes(nn.initializers.zeros, (None, "heads", "kv")),
            (3, nh, hd), cfg.param_dtype)
        out_kernel = self.param(
            "out_kernel", param_with_axes(_dense_init(cfg), ("heads", "kv", "embed")),
            (nh, hd, h), cfg.param_dtype)
        out_bias = self.param(
            "out_bias", param_with_axes(nn.initializers.zeros, ("embed",)),
            (h,), cfg.param_dtype)

        with device_scope("attn.proj"):
            x = x.astype(cfg.dtype)
            qkv_k = qkv_kernel.astype(cfg.dtype)
            if cfg.use_qat:
                # QAT (reference language_module.py:142-144): fake-quant the
                # matmul operands; per-channel scales over the input dim
                from fleetx_tpu.ops.quantization import fake_quant

                x = fake_quant(x, cfg.qat_act_bits)
                qkv_k = fake_quant(qkv_k, cfg.qat_bits, axis=0)
            qkv = jnp.einsum("bsh,hcnd->bcsnd", x, qkv_k)
            qkv = qkv + qkv_bias.astype(cfg.dtype)[:, None, :, :]
            if layer_cache is None:  # decode has no backward — skip the cast
                qkv = _save_residual(qkv, "res_qkv", cfg)
            q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]  # [b, s, n, d]
            q = with_logical(q, ("batch", "act_seq", "act_heads", "act_kv"))

        new_cache = None
        if layer_cache is not None:
            # decode: append this step's k/v at position cache['index'];
            # the key-validity mask keeps left-pad positions masked forever
            idx = layer_cache["index"]
            with device_scope("attn.cache"):
                step_mask = (attention_mask.astype(bool)
                             if attention_mask is not None
                             else jnp.ones(x.shape[:2], bool))
                ck = jax.lax.dynamic_update_slice_in_dim(
                    layer_cache["key"], k, idx, axis=1)
                cv = jax.lax.dynamic_update_slice_in_dim(
                    layer_cache["value"], v, idx, axis=1)
                cm = jax.lax.dynamic_update_slice_in_dim(
                    layer_cache["mask"], step_mask, idx, axis=1)
                # keep the rolling cache TP-sharded over heads through the
                # decode loop (SURVEY hard-part 5: kv-cache sharding under TP)
                ck = with_logical(ck, ("batch", None, "act_heads", "act_kv"))
                cv = with_logical(cv, ("batch", None, "act_heads", "act_kv"))
            new_cache = {"key": ck, "value": cv, "index": idx + x.shape[1],
                         "mask": cm}
            k, v = ck, cv
            attn_out = self._decode_attention(q, k, v, idx, cm)
        elif attention_mask is not None:
            attn_out = self._masked_attn(q, k, v, attention_mask, deterministic)
        else:
            attn_out = self._core_attn(q, k, v, deterministic)

        with device_scope("attn.proj"):
            out_k = out_kernel.astype(cfg.dtype)
            if cfg.use_qat:
                from fleetx_tpu.ops.quantization import fake_quant

                attn_out = fake_quant(attn_out, cfg.qat_act_bits)
                out_k = fake_quant(out_k, cfg.qat_bits, axis=(0, 1))
            out = jnp.einsum("bsnd,ndh->bsh", attn_out, out_k)
            out = out + out_bias.astype(cfg.dtype)
            if layer_cache is None:
                out = _save_residual(out, "res_attn_out", cfg)
        return out, new_cache

    @device_scope("attn.core")
    def _core_attn(self, q, k, v, deterministic: bool) -> jax.Array:
        """Causal attention core (reference ``core_attn`` + fused upper-tri
        softmax, ``hybrid_model.py:268-298``)."""
        cfg = self.cfg

        def plain(q, k, v):
            scores = jnp.einsum("bqnd,bknd->bnqk", q, k) / jnp.sqrt(cfg.head_dim).astype(q.dtype)
            s = q.shape[1]
            causal = jnp.tril(jnp.ones((s, s), bool))
            scores = jnp.where(causal, scores, jnp.finfo(scores.dtype).min)
            probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
            if cfg.attention_probs_dropout_prob > 0.0 and not deterministic:
                probs = nn.Dropout(cfg.attention_probs_dropout_prob)(
                    probs, deterministic=False)
            return jnp.einsum("bnqk,bknd->bqnd", probs, v)

        fn = plain
        if cfg.use_ring_attention:
            # context parallelism: K/V ring over the seq mesh axis
            # (ops/ring_attention.py — capability beyond the reference)
            from fleetx_tpu.ops import ring_attention as ra

            assert cfg.attention_probs_dropout_prob == 0.0 or deterministic, \
                "ring attention does not support attention dropout"
            fn = partial(ra.ring_attention, causal=True,
                         kv_chunk=cfg.ring_kv_chunk)
        elif cfg.use_flash_attention:
            from fleetx_tpu.ops import flash_attention
            from fleetx_tpu.parallel.mesh import current_mesh

            mesh = current_mesh()
            rate = 0.0 if deterministic else cfg.attention_probs_dropout_prob
            if flash_attention.supported(q, k) and \
                    flash_attention.sharded_supported(q, mesh) and (
                    rate == 0.0 or flash_attention.dropout_supported()):
                kwargs = dict(causal=True, fused_bwd=cfg.flash_fused_bwd)
                if rate > 0.0:
                    # in-kernel dropout: per-layer seed from the dropout rng
                    seed = jax.random.randint(
                        self.make_rng("dropout"), (1,), 0,
                        jnp.iinfo(jnp.int32).max, dtype=jnp.int32)
                    kwargs.update(dropout_rate=rate, dropout_seed=seed)
                # mesh-aware: run the kernel per-device (GSPMD cannot
                # partition the Mosaic custom call)
                fn = partial(flash_attention.flash_attention_sharded,
                             mesh=mesh, **kwargs)
            else:
                logger.info("flash attention does not admit q %s (dropout "
                            "%s): einsum attention", q.shape, rate)
        if cfg.use_recompute and cfg.recompute_granularity == "core_attn":
            fn = jax.checkpoint(fn)
        return fn(q, k, v)

    @device_scope("attn.core")
    def _masked_attn(self, q, k, v, attention_mask, deterministic) -> jax.Array:
        """Causal attention with an explicit key-padding mask (left-padded
        prompts; reference mask handling ``language_module.py:221-243``)."""
        cfg = self.cfg
        scores = jnp.einsum("bqnd,bknd->bnqk", q, k) / jnp.sqrt(cfg.head_dim).astype(q.dtype)
        s = q.shape[1]
        causal = jnp.tril(jnp.ones((s, s), bool))
        mask = causal[None] & attention_mask.astype(bool)[:, None, :]
        scores = jnp.where(mask[:, None], scores, jnp.finfo(scores.dtype).min)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
        if cfg.attention_probs_dropout_prob > 0.0 and not deterministic:
            probs = nn.Dropout(cfg.attention_probs_dropout_prob)(
                probs, deterministic=False)
        return jnp.einsum("bnqk,bknd->bqnd", probs, v)

    @staticmethod
    @device_scope("attn.core")
    def _decode_attention(q, k, v, cache_index, key_mask=None) -> jax.Array:
        """Single/few-token decode against the full cache with length masking."""
        scores = jnp.einsum("bqnd,bknd->bnqk", q, k) / jnp.sqrt(q.shape[-1]).astype(q.dtype)
        q_len, k_len = q.shape[1], k.shape[1]
        q_pos = cache_index + jnp.arange(q_len)[:, None]
        k_pos = jnp.arange(k_len)[None, :]
        mask = (k_pos <= q_pos)[None]  # causal + only-written-positions
        if key_mask is not None:
            mask = mask & key_mask.astype(bool)[:, None, :]
        scores = jnp.where(mask[:, None], scores, jnp.finfo(scores.dtype).min)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
        return jnp.einsum("bnqk,bknd->bqnd", probs, v)


class GPTMlp(nn.Module):
    """Dense 4h FFN with gelu (reference ``TransformerDecoderLayer`` linear1/2)."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self, x: jax.Array, save_residuals: bool = True) -> jax.Array:
        cfg = self.cfg
        wi = self.param("wi_kernel", param_with_axes(_dense_init(cfg), ("embed", "mlp")),
                        (cfg.hidden_size, cfg.ffn_dim), cfg.param_dtype)
        bi = self.param("wi_bias", param_with_axes(nn.initializers.zeros, ("mlp",)),
                        (cfg.ffn_dim,), cfg.param_dtype)
        wo = self.param("wo_kernel", param_with_axes(_dense_init(cfg), ("mlp", "embed")),
                        (cfg.ffn_dim, cfg.hidden_size), cfg.param_dtype)
        bo = self.param("wo_bias", param_with_axes(nn.initializers.zeros, ("embed",)),
                        (cfg.hidden_size,), cfg.param_dtype)
        x = x.astype(cfg.dtype)
        wi_k, wo_k = wi.astype(cfg.dtype), wo.astype(cfg.dtype)
        if cfg.use_qat:
            from fleetx_tpu.ops.quantization import fake_quant

            x = fake_quant(x, cfg.qat_act_bits)
            wi_k = fake_quant(wi_k, cfg.qat_bits, axis=0)
            wo_k = fake_quant(wo_k, cfg.qat_bits, axis=0)
        y = jnp.einsum("bsh,hm->bsm", x, wi_k) + bi.astype(cfg.dtype)
        if save_residuals:
            y = _save_residual(y, "res_mlp_wi", cfg)
        y = with_logical(y, ("batch", "act_seq", "mlp"))
        y = nn.gelu(y, approximate=True)
        if cfg.use_qat:
            from fleetx_tpu.ops.quantization import fake_quant

            y = fake_quant(y, cfg.qat_act_bits)
        out = jnp.einsum("bsm,mh->bsh", y, wo_k) + bo.astype(cfg.dtype)
        return _save_residual(out, "res_mlp_wo", cfg) if save_residuals else out


class LayerNorm(nn.Module):
    """Pre-norm layer norm computed in f32 (bf16-safe).

    With ``residual`` passed, the call folds the block residual add into
    the norm and returns ``(norm_out, s)`` where ``s = residual + x`` is
    the updated residual stream. Both forms dispatch to the fused Pallas
    kernel (ops/fused_norm.py) when ``cfg.fused_residual_norm`` is on and
    `fused_norm_supported` admits the shape; every rejected shape — and
    the knob off — runs the unfused jnp line below, with bitwise-identical
    f32 numerics either way (tests/test_zz_fusednorm.py).
    """
    cfg: GPTConfig

    @nn.compact
    def __call__(self, x: jax.Array, residual: Optional[jax.Array] = None):
        cfg = self.cfg
        scale = self.param("scale", param_with_axes(nn.initializers.ones, ("norm",)),
                           (cfg.hidden_size,), cfg.param_dtype)
        bias = self.param("bias", param_with_axes(nn.initializers.zeros, ("norm",)),
                          (cfg.hidden_size,), cfg.param_dtype)
        from fleetx_tpu.ops import fused_norm
        from fleetx_tpu.parallel.mesh import current_mesh

        if cfg.fused_residual_norm:
            # where the activations lie on the mesh — the constraint every
            # block ends on — so the kernel runs on each device's rows
            mesh = current_mesh()
            spec = nn.logical_to_mesh_axes(
                ("batch", "act_seq", "act_embed")) if x.ndim == 3 else None
            if fused_norm.fused_norm_supported(x, residual, mesh=mesh,
                                               spec=spec):
                out, s = fused_norm.fused_residual_norm(
                    x, scale, bias, residual=residual,
                    eps=cfg.layer_norm_epsilon, out_dtype=cfg.dtype,
                    mesh=mesh, spec=spec)
                return out if residual is None else (out, s)
            logger.info("fused norm does not admit x %s %s: unfused "
                        "LayerNorm", x.shape, x.dtype)
        s = x if residual is None else residual + x
        x32 = s.astype(jnp.float32)
        mean = x32.mean(-1, keepdims=True)
        var = ((x32 - mean) ** 2).mean(-1, keepdims=True)
        y = (x32 - mean) * jax.lax.rsqrt(var + cfg.layer_norm_epsilon)
        out = (y * scale + bias).astype(cfg.dtype)
        return out if residual is None else (out, s)


class TransformerDecoderLayer(nn.Module):
    """Pre-norm decoder block (reference ``hybrid_model.py:439-573``)."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self, x: jax.Array, layer_cache: Optional[dict] = None,
                 deterministic: bool = True,
                 attention_mask: Optional[jax.Array] = None,
                 ) -> tuple[jax.Array, Optional[dict]]:
        cfg = self.cfg
        layer_input = x
        residual = x
        with device_scope("norm"):
            y = LayerNorm(cfg, name="ln1")(x)

        attn = MultiHeadAttention(cfg, name="attn")
        if cfg.use_recompute and cfg.recompute_granularity == "full_attn" and layer_cache is None:
            # remat the whole attention call (reference hybrid_model.py:537-539)
            def attn_fn(mod, y):
                out, _ = mod(y, layer_cache=None, deterministic=deterministic,
                             attention_mask=attention_mask)
                return out
            y = nn.remat(attn_fn)(attn, y)
            new_cache = None
        else:
            y, new_cache = attn(y, layer_cache=layer_cache,
                                deterministic=deterministic,
                                attention_mask=attention_mask)

        if cfg.hidden_dropout_prob > 0.0 and not deterministic:
            y = nn.Dropout(cfg.hidden_dropout_prob)(y, deterministic=False)
        # ln2 folds the post-attention residual add: `x = residual + y`
        # rides inside the fused kernel (or the unfused fallback) and comes
        # back as the updated stream alongside the normed MLP input.
        with device_scope("norm"):
            y, x = LayerNorm(cfg, name="ln2")(y, residual=residual)

        residual = x
        if cfg.moe_num_experts > 0:
            from fleetx_tpu.models.gpt.moe import MoEMlp

            aux_gate = None
            if cfg.pp_degree > 1 and layer_cache is None:
                # Under the GPipe schedule, bubble blocks reach this layer
                # as exact zeros (the pipeline wrapper re-zeroes bubble
                # outputs, parallel/pipeline.py): gate their router
                # statistics out of the load-balance loss. Tested on the
                # LAYER input — the post-attention stream already carries
                # nonzero bias terms even for a zero input.
                aux_gate = (jnp.abs(layer_input).sum() > 0).astype(
                    jnp.float32)
            y = MoEMlp(cfg, name="mlp")(y, aux_gate=aux_gate)
        else:
            # decode (layer_cache set) has no backward — skip the residual
            # casts there, mirroring the attention-side gating above
            with device_scope("mlp"):
                y = GPTMlp(cfg, name="mlp")(
                    y, save_residuals=layer_cache is None)
        with device_scope("mlp"):   # the block's closing residual add
            if cfg.hidden_dropout_prob > 0.0 and not deterministic:
                y = nn.Dropout(cfg.hidden_dropout_prob)(y,
                                                        deterministic=False)
            x = residual + y
            x = with_logical(x, ("batch", "act_seq", "act_embed"))
        return x, new_cache


class GPTEmbeddings(nn.Module):
    """Token + learned position embeddings (reference ``single_model.py:340``)."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self, tokens: jax.Array, position_ids: jax.Array,
                 deterministic: bool = True) -> jax.Array:
        cfg = self.cfg
        wte = self.param("word_embeddings",
                         param_with_axes(_dense_init(cfg), ("vocab", "embed")),
                         (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype)
        wpe = self.param("position_embeddings",
                         param_with_axes(_dense_init(cfg), (None, "embed")),
                         (cfg.max_position_embeddings, cfg.hidden_size), cfg.param_dtype)
        with device_scope("embed"):
            x = wte.astype(cfg.dtype)[tokens] \
                + wpe.astype(cfg.dtype)[position_ids]
            if cfg.hidden_dropout_prob > 0.0 and not deterministic:
                x = nn.Dropout(cfg.hidden_dropout_prob)(x,
                                                        deterministic=False)
            # SP scatter point (reference hybrid_model.py:613-619)
            return with_logical(x, ("batch", "act_seq", "act_embed"))


class GPTModel(nn.Module):
    """Decoder stack; layers scanned for O(1) compile time and pipeline reuse."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self, tokens: jax.Array, position_ids: jax.Array | None = None,
                 cache: Optional[DecodeCache] = None,
                 deterministic: bool = True,
                 attention_mask: Optional[jax.Array] = None,
                 ) -> tuple[jax.Array, Optional[DecodeCache]]:
        cfg = self.cfg
        if position_ids is None:
            if attention_mask is not None and cache is not None:
                # left-padded prefill: positions count only real tokens
                position_ids = jnp.maximum(
                    jnp.cumsum(attention_mask.astype(jnp.int32), axis=1) - 1, 0)
            else:
                start = cache.index if cache is not None else 0
                position_ids = start + jnp.arange(tokens.shape[1])[None, :]
                position_ids = jnp.broadcast_to(position_ids, tokens.shape)

        x = GPTEmbeddings(cfg, name="embeddings")(tokens, position_ids, deterministic)

        layer = TransformerDecoderLayer
        use_remat = (cfg.use_recompute and cache is None and
                     cfg.recompute_granularity in ("full", "dots"))
        policy = None
        if use_remat:
            policy = (jax.checkpoint_policies.nothing_saveable
                      if cfg.recompute_granularity == "full" else
                      _dots_policy(cfg))
            # deterministic/attention_mask are control flags, not data — keep
            # them static under remat (with dropout>0 they'd otherwise be
            # traced and break `not deterministic`)
            layer = nn.remat(layer, prevent_cse=False, policy=policy,
                             static_argnums=(3, 4))

        if cfg.pp_degree > 1 and cache is None:
            # pipeline-parallel stack (reference GPTForPretrainingPipe,
            # hybrid_model.py:862-962 → parallel/pipeline.py). Flash attention
            # runs INSIDE the stages (reference fused attention in pipe,
            # hybrid_model.py:277): the stage vmap carries
            # spmd_axis_name="pipe", so the kernel's shard_map keeps the
            # Mosaic call per-device with the stage dim sharded over pipe.
            from fleetx_tpu.parallel.pipeline import (
                make_stage_stack, pipeline_apply)

            assert attention_mask is None, "pipeline mode is training-only"
            V = max(cfg.virtual_pp_degree, 1)
            chunks = cfg.pp_degree * V
            assert cfg.num_layers % chunks == 0
            # the RAW layer class goes in — the pipeline wraps it with a
            # fixed (x)->x signature and applies remat itself (a transformed
            # flax class cannot be re-subclassed)
            stages = make_stage_stack(
                TransformerDecoderLayer, cfg.pp_degree,
                cfg.num_layers // chunks, num_repeats=V,
                deterministic=deterministic, remat_policy=policy,
                remat=use_remat)(cfg, name="layers")
            with device_scope("stack"):
                x = pipeline_apply(stages, x, cfg.pp_degree,
                                   cfg.pp_microbatches or cfg.pp_degree,
                                   deterministic=deterministic, num_repeats=V)
            new_cache = None
        elif cfg.scan_layers:
            layer_caches = None
            if cache is not None:
                layer_caches = {
                    "key": cache.key, "value": cache.value,
                    "index": jnp.broadcast_to(cache.index, (cfg.num_layers,)),
                    "mask": jnp.broadcast_to(cache.mask,
                                             (cfg.num_layers,) + cache.mask.shape)}

            stack = nn.scan(
                layer,
                variable_axes={"params": 0, "losses": 0},
                split_rngs={"params": True, "dropout": True},
                in_axes=(0, nn.broadcast, nn.broadcast),
                out_axes=0,
                length=cfg.num_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
                # >1 unrolls that many layers into one scan body, so XLA
                # may schedule adjacent layers' writes into the stacked
                # residuals together, at compile-time cost; not measured
                # on the chip (ROADMAP S10)
                unroll=max(int(cfg.scan_unroll), 1),
            )(cfg, name="layers")
            with device_scope("stack"):
                x, new_caches = stack(x, layer_caches, deterministic,
                                      attention_mask)
            new_cache = None
            if cache is not None:
                new_cache = DecodeCache(key=new_caches["key"], value=new_caches["value"],
                                        index=new_caches["index"][0],
                                        mask=new_caches["mask"][0])
        else:
            new_k, new_v = [], []
            new_mask = cache.mask if cache is not None else None
            for i in range(cfg.num_layers):
                lc = None
                if cache is not None:
                    lc = {"key": cache.key[i], "value": cache.value[i],
                          "index": cache.index, "mask": cache.mask}
                x, nc = layer(cfg, name=f"layer_{i}")(x, layer_cache=lc,
                                                      deterministic=deterministic,
                                                      attention_mask=attention_mask)
                if nc is not None:
                    new_k.append(nc["key"])
                    new_v.append(nc["value"])
                    new_mask = nc["mask"]
            new_cache = None
            if cache is not None:
                new_cache = DecodeCache(key=jnp.stack(new_k), value=jnp.stack(new_v),
                                        index=cache.index + tokens.shape[1],
                                        mask=new_mask)

        with device_scope("head"):
            x = LayerNorm(cfg, name="ln_f")(x)
        return x, new_cache


class GPTForPretraining(nn.Module):
    """LM head with tied embeddings (reference ``GPTForPretraining``,
    ``single_model.py:577-618``; ``parallel_matmul`` logits ``hybrid_model.py:45-66``).

    With ``cfg.vocab_chunk`` set and ``labels`` passed, the call computes the
    masked LM loss directly through the memory-efficient chunked head (the
    full ``[batch, seq, vocab]`` logits tensor is never materialised) and
    returns the scalar loss instead of logits.
    """

    cfg: GPTConfig

    @nn.compact
    def __call__(self, tokens: jax.Array, position_ids: jax.Array | None = None,
                 cache: Optional[DecodeCache] = None, deterministic: bool = True,
                 attention_mask: jax.Array | None = None,
                 labels: jax.Array | None = None,
                 loss_mask: jax.Array | None = None):
        x, new_cache = GPTModel(self.cfg, name="gpt")(
            tokens, position_ids, cache, deterministic, attention_mask)
        wte = self.variables["params"]["gpt"]["embeddings"]["word_embeddings"]
        wte = getattr(wte, "unbox", lambda: wte)()
        if self.cfg.vocab_chunk and labels is not None and cache is None:
            with device_scope("head"):
                wte = wte.astype(self.cfg.dtype)
            losses = chunked_cross_entropy_per_token(
                x, wte, labels, int(self.cfg.vocab_chunk))
            with device_scope("loss"):
                mask = (jnp.ones_like(losses) if loss_mask is None
                        else loss_mask)
                return masked_mean(losses, mask)
        # SP gather point (reference hybrid_model.py:738-740) is implicit in the
        # act_seq→vocab logical re-layout below.
        with device_scope("head"):
            logits = jnp.einsum("bsh,vh->bsv", x, wte.astype(self.cfg.dtype))
            logits = with_logical(logits, ("batch", "act_seq", "act_vocab"))
        if cache is not None:
            return logits, new_cache
        return logits


def chunked_cross_entropy_per_token(x: jax.Array, wte: jax.Array,
                                    labels: jax.Array,
                                    vocab_chunk: int) -> jax.Array:
    """Token-level LM loss without materialising ``[b, s, V]`` logits.

    Splits the tied-embedding head over vocab chunks and computes each
    chunk's statistics (row max, sum-exp at that max, label logit)
    INDEPENDENTLY, then merges them — max of maxes, rescaled sum of
    sum-exps — into the exact logsumexp. Because no chunk depends on
    another, the static chunk loop is unrolled and XLA may overlap chunk
    ``k+1``'s head matmul (MXU) with chunk ``k``'s reductions (VPU)
    instead of serialising them the way a ``lax.scan`` accumulator chain
    must (chunked against whole logits: not measured on the chip, ROADMAP
    S10; each chunk's remat adds one head matmul pass). Each chunk is
    rematerialised, so
    peak memory stays one-ish ``[b, s, vocab_chunk]`` f32 block in
    forward AND backward — at GPT-345M bs8×seq1024 that replaces the
    ~1.65GB f32 logits (+ its gradient) with ~33MB blocks at chunk 1024.
    Exact (merging per-chunk (m, l) pairs is the same math as the online
    logsumexp). Falls back to the scan when the chunk count is large
    enough that unrolling would bloat the program.
    """
    V, _ = wte.shape
    # snap the chunk near-tight under the requested cap: the naive
    # ceil-divide padded the head matmul (8192 padded 50304 -> 57344, 14%
    # wasted FLOPs across all four fwd/bwd head passes). Shrink to the
    # smallest chunk with the same count, then re-align up to 128 lanes for
    # the MXU — never exceeding the requested chunk (it is a memory cap).
    cap = min(int(vocab_chunk), V)
    n_chunks = -(-V // cap)
    base = -(-V // n_chunks)  # smallest chunk with that count
    chunk = min(-(-base // 128) * 128, cap)
    n_chunks = -(-V // chunk)
    pad = n_chunks * chunk - V
    with device_scope("head"):
        wte_p = jnp.pad(wte, ((0, pad), (0, 0))) if pad else wte
        wte_ch = wte_p.reshape(n_chunks, chunk, wte.shape[1])

    @jax.checkpoint
    def one_chunk(ci, w):
        with device_scope("head"):
            logits = jnp.einsum("bsh,vh->bsv", x, w).astype(jnp.float32)
        with device_scope("loss"):
            if pad:
                ids = ci * chunk + jnp.arange(chunk)
                logits = jnp.where(ids < V, logits, _NEG_INF_F32)
            m = logits.max(axis=-1)
            l = jnp.exp(logits - m[..., None]).sum(axis=-1)
            local = jnp.clip(labels - ci * chunk, 0, chunk - 1)
            ll = jnp.take_along_axis(logits, local[..., None],
                                     axis=-1)[..., 0]
            in_ch = (labels >= ci * chunk) & (labels < (ci + 1) * chunk)
            return m, l, jnp.where(in_ch, ll, 0.0)

    if n_chunks <= 32:
        with device_scope("head"):
            chunks = [wte_ch[ci] for ci in range(n_chunks)]
        stats = [one_chunk(jnp.int32(ci), chunks[ci])
                 for ci in range(n_chunks)]
        with device_scope("loss"):
            m = functools.reduce(jnp.maximum, [s_[0] for s_ in stats])
            l = sum(s_[1] * jnp.exp(s_[0] - m) for s_ in stats)
            lab = sum(s_[2] for s_ in stats)  # label lands in exactly one chunk
            return m + jnp.log(l) - lab

    def fold(acc, xs):
        m, l, lab = acc
        ci, w = xs
        cm, cl, clab = one_chunk(ci, w)
        m_new = jnp.maximum(m, cm)
        l = l * jnp.exp(m - m_new) + cl * jnp.exp(cm - m_new)
        return (m_new, l, lab + clab), None

    b, s = labels.shape
    with device_scope("loss"):
        m0 = jnp.full((b, s), _NEG_INF_F32, jnp.float32)
        l0 = jnp.zeros((b, s), jnp.float32)
        lab0 = jnp.zeros((b, s), jnp.float32)
        (m, l, lab), _ = jax.lax.scan(
            fold, (m0, l0, lab0), (jnp.arange(n_chunks), wte_ch))
        return m + jnp.log(l) - lab


def cross_entropy_per_token(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Unreduced token-level LM loss (shared by training loss and the
    offline PPL eval, reference ``language_module.py:325-389``)."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    label_logits = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return logz - label_logits


def masked_mean(losses: jax.Array, loss_mask: jax.Array) -> jax.Array:
    """Mask-weighted mean shared by the full-logits and chunked LM losses."""
    loss_mask = loss_mask.astype(jnp.float32).reshape(losses.shape)
    return (losses * loss_mask).sum() / jnp.maximum(loss_mask.sum(), 1.0)


@device_scope("loss")
def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       loss_mask: jax.Array) -> jax.Array:
    """Masked LM loss (reference ``GPTPretrainingCriterion``,
    ``single_model.py:619-655``; ``ParallelCrossEntropy`` ``hybrid_model.py:820-827``
    — vocab-sharded logits are handled by GSPMD here)."""
    return masked_mean(cross_entropy_per_token(logits, labels), loss_mask)


# ------------------------- config zoo helpers -------------------------------

PRESETS = {
    # name: (layers, hidden, heads, ffn)  — reference configs/nlp/gpt/*.yaml
    "GPT-345M": (24, 1024, 16, 4096),
    "GPT-1.3B": (24, 2048, 16, 8192),
    "GPT-6.7B": (32, 4096, 32, 16384),
    "GPT-13B": (40, 5120, 40, 20480),
    "GPT-175B": (96, 12288, 96, 49152),
}


def config_from_dict(d: dict) -> GPTConfig:
    """Build a GPTConfig from a YAML ``Model:`` section."""
    known = {f.name for f in dataclasses.fields(GPTConfig)}
    kwargs = {k: v for k, v in d.items() if k in known and v is not None}
    dtype_map = {"bfloat16": jnp.bfloat16, "float32": jnp.float32, "float16": jnp.float16}
    if str(kwargs.get("grad_accum_dtype")).lower() == "native":
        # an empty YAML leaf means "use the fp32 default" (None values are
        # filtered above); the legacy accumulate-in-grad-dtype mode needs
        # an explicit spelling that survives that filter
        kwargs["grad_accum_dtype"] = None
    for key in ("dtype", "param_dtype", "remat_save_dtype",
                "grad_accum_dtype"):
        if isinstance(kwargs.get(key), str):
            kwargs[key] = dtype_map[kwargs[key]]
    return GPTConfig(**kwargs)
