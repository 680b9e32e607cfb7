"""Jitted paged-attention decode/prefill steps with static shapes.

The serving runtime's device side: two XLA programs, compiled ONCE per
engine, that the continuous-batching scheduler calls every step —

- ``prefill``: one chunk of ONE request's prompt (``[1, prefill_chunk]``,
  ragged tail masked) is forwarded, its K/V scattered into the request's
  pages, and the last valid position's logits/sampled token returned so
  the final chunk yields the first generated token (TTFT);
- ``decode``: one token for EVERY slot of the static ``[max_batch]``
  decode batch — inactive slots point at the null page and are masked, so
  requests join/leave the batch at step boundaries without changing any
  shape. Continuous batching therefore **never retraces**
  (``tests/test_zz_serving.py`` pins the jit cache size at 1).

**The last tokens never leave the device.** ``decode`` takes the previous
call's sampled tokens as they are, and beside them the newest ``prefill``
call's sampled token with the slot it belongs in (``merge_fresh``: the
request that left prefill in this tick joins inside the program). The host
therefore needs no token's VALUE to dispatch the next step, and
``ServingEngine.step`` dispatches step N+1 before it fetches step N
(``serving/engine.py``; docs/serving.md "The tick"). A sampling program
folds the host's draw count into the one base key itself
(``jax.random.fold_in``): no key is split by a dispatch of its own.

The forward re-implements the ``models/gpt/model.py`` decode math over the
RAW parameter pytree (scanned-layer layout) instead of ``model.apply``:
the dense ``DecodeCache`` threads a single scalar write index through the
whole batch, which cannot express per-request ragged lengths — the thing
continuous batching is. Math is kept line-for-line parallel (f32
layernorms, cfg-dtype matmuls, f32 softmax, gelu ``approximate=True``) so
greedy decode is token-identical to one-shot ``generation.generate``.

The KV pool (``[layers, pages, page_size, heads·head_dim]``, K and V)
stays ONE buffer from each program's input to its output: it rides in
the layer scan's CARRY beside the activations (the scan runs over the
layer parameters and the layer index), a layer writes its block's K/V
rows (``heads·head_dim`` contiguous values each) in place at ``[layer,
page, offset]``, attention reads the pool by layer index, and the donated
inputs alias the outputs. The pool is never
a scanned input or a stacked output — that form makes XLA slice each
layer (235 MB at the 345M serving geometry) out of the pool, copy it for
the scatter, write it back into a fresh stack and copy the whole stack
once more because a fresh buffer cannot alias the donated one: ~75 GB of
HBM traffic a call to change 0.2 MB (PERF.md, PR 28).
``tests/test_tpu_lowering.py`` pins the aliasing and the absence of any
pool- or layer-shaped temporary in the compiled programs.

The PARAMETERS are cast once, not once a call. The model's tree arrives
in ``param_dtype`` (float32 in every recipe, from ``model.init``, a
restored checkpoint or a LoRA merge); ``serving_params`` — called by
``ServingEngine.__init__``, and by anyone else who calls ``make_step_fns``
— makes the tree both programs take as their first argument: kernels,
their biases and both embedding tables in ``cfg.dtype``, the layer norms'
``scale`` and ``bias`` as they came (``_layer_norm`` multiplies them in
float32; casting them would be another result). The forward converts no
parameter and refuses, while tracing, a tree that would need it. Cast in
the programs instead, XLA hoists the casts out of the layer scan and runs
them over the whole ``[24, …]`` stacks on EVERY call: 2.1 GB of HBM
traffic, ~3.1 ms, to remake the same 0.7 GB (GPT-345M; PERF.md, PR 33).
Every leaf keeps its shape: compiled for the v5e with bfloat16 arguments,
neither program holds a ``convert``, ``copy`` or ``transpose`` of a weight
stack — the runtime stores ``qkv_kernel`` ``[24, 1024, 3, 16, 64]``
hidden-minor (``{1,4,3,2,0}``, tiled over ``64 × 1024``: unpadded, and the
layout the product reads), so the ``[…, 16, 64]`` tail that cost the pool
a transpose costs the weights nothing. One line remains in ``decode``: the
compiler prefetches the vocabulary matrix into on-chip memory
(``copy-done`` to ``S(1)``), in place of the product's read from HBM.
``tests/test_tpu_lowering.py`` pins all of it at GPT-345M's widths.

Decode attention has two compiled forms, chosen ONCE at
``make_step_fns`` time (so the jit caches still hold one entry each):
the ``ops/paged_attention.py`` Pallas kernel that walks block tables
in-kernel (scalar-prefetched layer index and page ids, online-softmax
f32 accumulation — no dense page view ever materialises), or — when
``paged_kernel_enabled`` rejects the geometry — the gathered view
``pool[layer, block_tables] → [B, pages_per_req·page_size, heads,
head_dim]`` (one gather with the layer folded into its indices) fused by
XLA. Prefill always takes the gather (its queries span a whole chunk,
not one token). Host-side machinery is identical on both paths, and
greedy decode is token-identical either way (``tests/test_zz_serving.py``
pins parity AND which path compiled).

Quantized decode (``ServingConfig.quantize_decode``): int8-style fake-quant
on the decode activations (``Quantization.activation_bits`` →
``GPTConfig.qat_act_bits`` — wired by PR 2 but consumed by no inference
path until now) and weights (``qat_bits``), mirroring the training QAT
placement in ``models/gpt/model.py``; drift is parity-bounded on the CPU
mesh by ``tests/test_zz_serving.py``.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from fleetx_tpu.observability.trace import device_scope
from fleetx_tpu.ops import paged_attention as PA
# the tail of every family's programs lives in ``serving/programs.py``
from fleetx_tpu.serving.programs import SamplingParams, merge_fresh, sample


def kernel_geometry(cfg: Any, *, page_size: int, pages_per_req: int,
                    pool_sharding: Optional[Any] = None) -> dict:
    """What ``ops/paged_attention.py`` is asked about ONE device's share of
    the pool (the kernel runs per shard: ``tensor`` splits the heads)."""
    tensor = 1 if pool_sharding is None else \
        pool_sharding.mesh.shape["tensor"]
    return dict(num_heads=cfg.num_attention_heads // tensor,
                head_dim=cfg.head_dim, page_size=page_size,
                pages_per_req=pages_per_req, dtype=cfg.dtype)


def kernel_refusal(cfg: Any, *, page_size: int, num_pages: int,
                   pages_per_req: int,
                   pool_sharding: Optional[Any] = None) -> str:
    """Why the Pallas page-walk kernel does not serve decode at this
    geometry, or "" when it does: the shape predicate refuses one shard's
    (heads, head_dim, page) tiling, or — under a multi-device mesh — the
    per-device ``shard_map`` wrapping does not apply. Static: consulted
    once per engine, and the result is baked into the decode program so the
    no-retrace pin is untouched."""
    if pool_sharding is not None and pool_sharding.mesh.size > 1:
        mesh = pool_sharding.mesh
        if not PA.paged_sharded_supported(
                mesh, num_heads=cfg.num_attention_heads, num_pages=num_pages):
            return "the mesh %s does not split %d pages over fsdp and %d " \
                   "heads over tensor alone" % (
                       dict(mesh.shape), num_pages, cfg.num_attention_heads)
    return PA.paged_attention_refusal(**kernel_geometry(
        cfg, page_size=page_size, pages_per_req=pages_per_req,
        pool_sharding=pool_sharding))


def token_sharding(mesh: Any) -> Any:
    """Where a mesh holds the sampled tokens (``[max_batch]``, ``[1]``):
    whole on every device."""
    from jax.sharding import NamedSharding

    from fleetx_tpu.parallel.rules import activation_spec

    return NamedSharding(mesh, activation_spec())


def _quant(x: jax.Array, bits: int, enabled: bool, axis=None) -> jax.Array:
    """Config-gated fake-quant (identity when the decode path is fp)."""
    if not enabled:
        return x
    from fleetx_tpu.ops.quantization import fake_quant

    return fake_quant(x, bits, axis=axis)


def _layer_norm(p: dict, x: jax.Array, cfg: Any) -> jax.Array:
    """f32 layernorm matching ``models/gpt/model.py:LayerNorm``."""
    x32 = x.astype(jnp.float32)
    mean = x32.mean(-1, keepdims=True)
    var = ((x32 - mean) ** 2).mean(-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + cfg.layer_norm_epsilon)
    return (y * p["scale"] + p["bias"]).astype(cfg.dtype)


#: the layer norms' groups: ``_layer_norm`` multiplies ``scale`` and
#: ``bias`` in float32, so their leaves stay in the dtype they come in
_F32_GROUPS = frozenset({"ln1", "ln2", "ln_f"})


def _unserved(params: Any, cfg: Any) -> list:
    """Flattening-order indices of the leaves the forward could not take
    as they are: every leaf outside the layer norms that is not in
    ``cfg.dtype``."""
    dtype = jnp.dtype(cfg.dtype)
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    return [i for i, (path, leaf) in enumerate(flat)
            if leaf.dtype != dtype
            and not _F32_GROUPS & {getattr(k, "key", None) for k in path}]


def serving_params(params: Any, cfg: Any) -> Any:
    """The model's (unboxed) parameter tree as the two programs take it:
    kernels, their biases and both embedding tables in ``cfg.dtype``, the
    layer norms' leaves untouched. The ONE place a weight changes dtype.

    One jitted call over the leaves that need it, so no float32 temporary
    outlives it; nothing is donated (the caller's tree stays whole) and a
    leaf already in ``cfg.dtype`` comes back as the object it was. The
    cast is elementwise, so sharding propagation hands every leaf back on
    the sharding it came with (``tests/test_zz_serving.py`` holds that on
    the partition rules' shardings)."""
    todo = _unserved(params, cfg)
    if not todo:
        return params
    leaves, treedef = jax.tree.flatten(params)
    cast = jax.jit(lambda xs: [x.astype(cfg.dtype) for x in xs])(
        [leaves[i] for i in todo])
    for i, leaf in zip(todo, cast):
        leaves[i] = leaf
    return treedef.unflatten(leaves)


def _paged_attention(q: jax.Array, kd: jax.Array, vd: jax.Array,
                     q_pos: jax.Array) -> jax.Array:
    """Decode attention over the gathered page view (mirrors
    ``MultiHeadAttention._decode_attention``).

    ``q`` ``[B, S, heads, hd]``, ``kd``/``vd`` ``[B, K, heads, hd]``
    (K = pages_per_req · page_size), ``q_pos`` ``[B, S]`` absolute token
    positions. Every key slot at a position ≤ the query's is a written
    prefix slot; everything else (unwritten tail, null-page filler) is
    masked to the dtype's min, which underflows to an exact 0 in the f32
    softmax — identical math to the dense cache's masked softmax.
    """
    hd = q.shape[-1]
    scores = jnp.einsum("bqnd,bknd->bnqk", q, kd) / \
        jnp.sqrt(hd).astype(q.dtype)
    k_pos = jnp.arange(kd.shape[1])
    mask = k_pos[None, None, :] <= q_pos[:, :, None]          # [B, S, K]
    scores = jnp.where(mask[:, None], scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32),
                           axis=-1).astype(q.dtype)
    return jnp.einsum("bnqk,bknd->bqnd", probs, vd)


def _forward(params: Any, cfg: Any, tokens: jax.Array, positions: jax.Array,
             pool_k: jax.Array, pool_v: jax.Array, block_tables: jax.Array,
             quantize: bool, paged_kernel: bool = False,
             mesh: Optional[Any] = None
             ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Forward a ``[B, S]`` token block through the paged decode stack.

    The pools are carried through the layer scan as one buffer each.
    Per layer: the block's K/V rows are scattered in place at ``[layer,
    page, offset]`` (by block table), then attention reads that layer of
    the pool by index — the Pallas page-walk kernel when ``paged_kernel``
    is set (decode only — ``S == 1``), the gathered page view otherwise.
    Returns ``(hidden [B, S, h], pool_k, pool_v)``.
    ``positions`` are absolute token positions (invalid slots must
    already be redirected to the null page via ``block_tables``-aware
    ``positions``/page math by the caller-built scatter indices below).
    ``params`` is the tree ``serving_params`` makes: no leaf is converted
    here, and a tree that would need it is refused while tracing.
    """
    unserved = _unserved(params, cfg)
    if unserved:
        raise TypeError(
            "the serving programs take the tree serving_params() makes: "
            f"{len(unserved)} leaves are not in {jnp.dtype(cfg.dtype).name}")
    B, S = tokens.shape
    ps = pool_k.shape[2]
    num_layers = pool_k.shape[0]
    gpt = params["gpt"]
    emb = gpt["embeddings"]

    wte, wpe = emb["word_embeddings"], emb["position_embeddings"]
    with device_scope("embed"):
        safe_pos = jnp.clip(positions, 0, cfg.max_position_embeddings - 1)
        x = wte[tokens] + wpe[safe_pos]

    # scatter targets, shared by every layer: page id + in-page offset per
    # (row, slot). Negative positions mark invalid slots → null page 0.
    with device_scope("attn.cache"):
        page_slot = jnp.clip(positions // ps, 0, block_tables.shape[1] - 1)
        pages = jnp.take_along_axis(block_tables, page_slot, axis=1)
        pages = jnp.where(positions >= 0, pages, 0)
        offs = jnp.clip(positions % ps, 0, ps - 1)
        q_pos = jnp.maximum(positions, 0)

    nh, hd = cfg.num_attention_heads, cfg.head_dim
    act_bits, w_bits = cfg.qat_act_bits, cfg.qat_bits

    def layer(carry, scanned):
        x, pool_k, pool_v = carry
        lp, l = scanned
        residual = x
        with device_scope("norm"):
            y = _layer_norm(lp["ln1"], x, cfg)

        with device_scope("attn.proj"):
            y_in = _quant(y, act_bits, quantize)
            qkv_k = _quant(lp["attn"]["qkv_kernel"], w_bits, quantize, axis=0)
            qkv = jnp.einsum("bsh,hcnd->bcsnd", y_in, qkv_k)
            qkv = qkv + lp["attn"]["qkv_bias"][:, None]
            q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]      # [B, S, nh, hd]

        # in place on the carried pool: a [B, S] scatter of nh·hd-wide rows
        with device_scope("attn.cache"):
            pool_k = pool_k.at[l, pages, offs].set(k.reshape(B, S, nh * hd))
            pool_v = pool_v.at[l, pages, offs].set(v.reshape(B, S, nh * hd))
        with device_scope("attn.core"):
            if paged_kernel and S == 1:
                # in-kernel block-table walk (ops/paged_attention.py): layer
                # l of the pool is read page-by-page via scalar-prefetched
                # ids — neither the layer nor the dense [B,
                # pages_per_req·page_size, nh, hd] view is ever
                # materialised. positions[:, 0] is each row's query position
                # (< 0 = inactive slot → all pages masked, exact-zero out).
                attn = PA.paged_attention_sharded(
                    q[:, 0], pool_k, pool_v, block_tables, positions[:, 0],
                    l, mesh=mesh)[:, None]
            else:
                # one gather, the layer folded into its indices
                kd = pool_k[l, block_tables].reshape(B, -1, nh, hd)
                vd = pool_v[l, block_tables].reshape(B, -1, nh, hd)
                attn = _paged_attention(q, kd, vd, q_pos)

        with device_scope("attn.proj"):
            attn = _quant(attn, act_bits, quantize)
            out_k = _quant(lp["attn"]["out_kernel"], w_bits, quantize,
                           axis=(0, 1))
            y = jnp.einsum("bsnd,ndh->bsh", attn, out_k)
            y = y + lp["attn"]["out_bias"]

        with device_scope("norm"):
            x = residual + y
            residual = x
            y = _layer_norm(lp["ln2"], x, cfg)
        with device_scope("mlp"):
            y_in = _quant(y, act_bits, quantize)
            wi = _quant(lp["mlp"]["wi_kernel"], w_bits, quantize, axis=0)
            y = jnp.einsum("bsh,hm->bsm", y_in, wi) + lp["mlp"]["wi_bias"]
            y = jax.nn.gelu(y, approximate=True)
            y = _quant(y, act_bits, quantize)
            wo = _quant(lp["mlp"]["wo_kernel"], w_bits, quantize, axis=0)
            y = jnp.einsum("bsm,mh->bsh", y, wo) + lp["mlp"]["wo_bias"]
            x = residual + y
        return (x, pool_k, pool_v), None

    with device_scope("stack"):
        (x, pool_k, pool_v), _ = jax.lax.scan(
            layer, (x, pool_k, pool_v),
            (gpt["layers"], jnp.arange(num_layers, dtype=jnp.int32)))
    with device_scope("head"):
        x = _layer_norm(gpt["ln_f"], x, cfg)
    return x, pool_k, pool_v


@device_scope("head")
def _logits(params: Any, cfg: Any, x_last: jax.Array) -> jax.Array:
    """Tied-embedding LM head on the selected positions → f32 ``[B, V]``."""
    wte = params["gpt"]["embeddings"]["word_embeddings"]
    return jnp.einsum("bh,vh->bv", x_last, wte).astype(jnp.float32)


def make_step_fns(cfg: Any, *, max_batch: int, pages_per_req: int,
                  prefill_chunk: int, sampling: SamplingParams,
                  quantize: bool = False,
                  pool_sharding: Optional[Any] = None,
                  paged_kernel: bool = False) -> dict:
    """Build the two jitted serving programs for one engine.

    Returns ``{"prefill": fn, "decode": fn}``; both donate the pool
    buffers (the engine rebinds them every call) and carry fully static
    shapes — ``max_batch``/``pages_per_req``/``prefill_chunk`` are baked
    in, so the jit caches hold exactly one entry each for the engine's
    lifetime. ``pool_sharding`` (a ``NamedSharding``) keeps the pools
    constrained to their mesh placement through every step.
    ``paged_kernel`` bakes the decode-attention path in (callers gate on
    ``paged_kernel_enabled`` — this function obeys, it doesn't decide).
    """
    mesh = pool_sharding.mesh if pool_sharding is not None else None

    def constrain(pool):
        if pool_sharding is None:
            return pool
        return jax.lax.with_sharding_constraint(pool, pool_sharding)

    def everywhere(toks):
        # the sampled tokens feed the next decode call as they are: on a
        # mesh they come out where ``token_sharding`` put the first ones
        if mesh is None:
            return toks
        return jax.lax.with_sharding_constraint(toks, token_sharding(mesh))

    def prefill(params, pool_k, pool_v, tokens, block_table, start, n_valid,
                rng, draw):
        """One prompt chunk for one request: ``tokens`` ``[1, C]`` with
        ``n_valid`` real entries starting at absolute position ``start``;
        returns the pools plus the last valid position's sampled token and
        f32 logits (meaningful on the request's final chunk)."""
        idx = jnp.arange(prefill_chunk)[None, :]
        positions = jnp.where(idx < n_valid, start + idx, -1)
        x, pool_k, pool_v = _forward(params, cfg, tokens, positions,
                                     pool_k, pool_v, block_table, quantize)
        with device_scope("head"):
            last = jnp.clip(n_valid - 1, 0, prefill_chunk - 1)
            x_last = jax.lax.dynamic_index_in_dim(x[0], last, axis=0,
                                                  keepdims=False)[None]
        logits = _logits(params, cfg, x_last)
        return (constrain(pool_k), constrain(pool_v),
                everywhere(sample(logits, rng, draw, sampling)), logits)

    def decode(params, pool_k, pool_v, tokens, fresh_slot, fresh_tok,
               block_tables, lens, rng, draw):
        """One decode step for the full static batch: ``tokens``/``lens``
        ``[max_batch]`` (inactive slots carry ``lens < 0`` and null-page
        block tables; what their ``tokens`` hold is never read past the
        masked row), ``tokens`` the previous call's sampled tokens with
        ``merge_fresh``'s one new row; returns pools + sampled tokens + f32
        logits."""
        tokens = merge_fresh(tokens, fresh_slot, fresh_tok)
        positions = jnp.where(lens >= 0, lens, -1)[:, None]
        x, pool_k, pool_v = _forward(params, cfg, tokens[:, None], positions,
                                     pool_k, pool_v, block_tables, quantize,
                                     paged_kernel=paged_kernel, mesh=mesh)
        logits = _logits(params, cfg, x[:, 0])
        return (constrain(pool_k), constrain(pool_v),
                everywhere(sample(logits, rng, draw, sampling)), logits)

    del max_batch, pages_per_req  # shapes arrive via the arrays themselves
    return {
        "prefill": jax.jit(prefill, donate_argnums=(1, 2)),
        "decode": jax.jit(decode, donate_argnums=(1, 2)),
    }
