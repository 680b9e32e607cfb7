"""LoRA adapter algebra: injection, masking, merge (docs/finetune.md).

Parameter-efficient fine-tuning following "Fine-Tuning and Serving Gemma
on Google Cloud TPU" (PAPERS.md): the pretrained base pytree stays
bitwise frozen while low-rank ``lora_a``/``lora_b`` leaves — injected as
SIBLINGS of the registry-named target kernels — carry all the learning.
For a target kernel ``W`` with input features ``in`` and output features
``out``, the adapter pair is

- ``A`` (``<kernel>_lora_a``): ``[*stack, *in, r]``, small normal init;
- ``B`` (``<kernel>_lora_b``): ``[*stack, r, *out]``, zero init,

and the effective kernel is ``W + (alpha / r) * A @ B`` — zero at step 0
(``B`` is zeros), so fine-tuning starts exactly at the base model. The
model code is untouched: kernels enter every matmul linearly, so folding
the delta into the kernel before ``model.apply`` is mathematically
identical to running adapters on the side, and autodiff routes gradients
to ``A``/``B`` through the fold.

Everything here is name-driven off the partition-rule registry
(``parallel/rules.py`` family ``gpt_lora``): the adapter leaf names are
what the rule table, the optimizer mask, the adapter-only checkpoint
codec (``finetune/checkpoint.py``) and shardcheck all key on, and the
flax boxing metadata for injected leaves is DERIVED from the registry
templates (:func:`adapter_axis_names`) so the parity gate in
``tests/test_zz_shardcheck.py`` pins both sides to one source of truth.

Scanned stacks ride along for free: a stacked target ``[L, *features]``
gets stacked adapters ``[L, *in, r]`` / ``[L, r, *out]`` and the fold is
a batched matmul over the leading stack dims.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax.core import meta

from fleetx_tpu.parallel import rules as rules_lib

__all__ = [
    "LORA_TARGETS", "ADAPTER_SUFFIXES", "is_adapter_name",
    "inject_adapters", "adapter_axis_names", "adapter_delta",
    "merge_adapters", "split_adapters", "combine_adapters", "adapter_mask",
    "lora_optimizer", "trainable_params_frac", "base_leaf_digests",
]

#: registry-named target matmuls → (feature_rank, n_in): how many trailing
#: dims are the kernel's feature axes, and how many of those are the
#: matmul's INPUT side (the rest are output). Leading dims beyond
#: feature_rank are scanned-stack dims (rules.STACK_AXES).
LORA_TARGETS: dict[str, tuple[int, int]] = {
    "attn/qkv_kernel": (4, 1),   # [h | 3, nh, hd]
    "attn/out_kernel": (3, 2),   # [nh, hd | h]
    "mlp/wi_kernel": (2, 1),     # [h | m]
    "mlp/wo_kernel": (2, 1),     # [m | h]
}

#: the leaf-name suffixes every consumer (rules, mask, codec) keys on
ADAPTER_SUFFIXES = ("_lora_a", "_lora_b")

#: init scale for A (B is zeros, so the starting delta is exactly 0)
_A_INIT_STDDEV = 0.02


def is_adapter_name(name: str) -> bool:
    """True when a slash-joined leaf path names an adapter leaf."""
    return name.endswith(ADAPTER_SUFFIXES)


def _unboxed_value(leaf: Any) -> Any:
    """A leaf's raw array, whether or not it is flax-boxed."""
    return leaf.unbox() if isinstance(leaf, meta.AxisMetadata) else leaf


def adapter_axis_names(family: str, name: str, ndim: int) -> tuple:
    """Full-rank logical axis names for one adapter leaf, derived from the
    family's registry rule (stack padding included) — the flax boxing
    metadata injection attaches so ``nn.get_partition_spec`` and the
    registry resolve identically (the shardcheck parity gate)."""
    matched = rules_lib._matches(family, name)
    if not matched:
        raise KeyError(
            f"no {family!r} rule matches adapter leaf {name!r} — add it to "
            f"PARTITION_RULES (parallel/rules.py)")
    return rules_lib._stack_padded(family, name, matched[0][2], ndim)


def inject_adapters(params: Any, rank: int, rng: jax.Array,
                    family: str = "gpt_lora",
                    targets: Optional[dict] = None) -> Any:
    """Add ``lora_a``/``lora_b`` siblings next to every target kernel.

    ``params`` may be boxed (``nn.Partitioned``, the engine's init tree)
    or raw; injected leaves are boxed iff their target is, with logical
    names derived from the registry (:func:`adapter_axis_names`). Pure
    jnp/`jax.random` ops, so the injection works under ``jax.eval_shape``
    — shardcheck audits the adapted abstract tree on CPU.
    """
    targets = targets or LORA_TARGETS
    counter = [0]

    def walk(node: Any, prefix: str) -> Any:
        if not isinstance(node, dict):
            return node
        out = {}
        for key, value in node.items():
            if isinstance(value, dict):
                out[key] = walk(value, f"{prefix}{key}/")
                continue
            out[key] = value
            full = f"{prefix}{key}"
            hit = next((t for t in targets
                        if full == t or full.endswith("/" + t)), None)
            if hit is None:
                continue
            feature_rank, n_in = targets[hit]
            kernel = _unboxed_value(value)
            shape = tuple(kernel.shape)
            n_stack = len(shape) - feature_rank
            assert 0 <= n_stack <= len(rules_lib.STACK_AXES), (full, shape)
            stack = shape[:n_stack]
            in_dims = shape[n_stack:n_stack + n_in]
            out_dims = shape[n_stack + n_in:]
            counter[0] += 1
            a = _A_INIT_STDDEV * jax.random.normal(
                jax.random.fold_in(rng, counter[0]),
                stack + in_dims + (int(rank),), kernel.dtype)
            b = jnp.zeros(stack + (int(rank),) + out_dims, kernel.dtype)
            for suffix, leaf in (("_lora_a", a), ("_lora_b", b)):
                leaf_key = key + suffix
                if isinstance(value, meta.AxisMetadata):
                    names = adapter_axis_names(
                        family, f"{prefix}{leaf_key}", leaf.ndim)
                    leaf = value.replace_boxed(leaf).replace(names=names)
                out[leaf_key] = leaf
        return out

    return walk(params, "")


def adapter_delta(a: jax.Array, b: jax.Array, kernel_shape: tuple) -> jax.Array:
    """``A @ B`` reshaped to the target kernel's shape.

    ``a`` is ``[*stack, *in, r]``, ``b`` is ``[*stack, r, *out]``; the
    stack depth is inferred from the ranks, the feature dims flatten into
    one matmul per stack entry, and the product unfolds back to
    ``kernel_shape`` — exact for every target regardless of scan/pp
    stacking.
    """
    n_stack = a.ndim + b.ndim - len(kernel_shape) - 2
    assert n_stack >= 0, (a.shape, b.shape, kernel_shape)
    r = a.shape[-1]
    stack = a.shape[:n_stack]
    af = a.reshape(stack + (-1, r))
    bf = b.reshape(stack + (r, -1))
    return jnp.matmul(af, bf).reshape(kernel_shape)


def merge_adapters(params: Any, alpha: float) -> Any:
    """Fold every adapter pair into its base kernel: ``W + (alpha/r)·A@B``.

    Returns a RAW (unboxed) tree in the base model's exact structure —
    the adapter leaves are consumed, so the result drops into
    ``model.apply``, the serving decode programs and the export path with
    no further plumbing. Used per-step by the fine-tune loss (gradients
    flow to A/B through the fold; the base enters as a frozen constant
    under the optimizer mask) and once at serving startup, where the
    merged weights pay nothing over the base model.
    """
    tree = meta.unbox(params)

    def walk(node: Any) -> Any:
        if not isinstance(node, dict):
            return node
        out = {}
        for key, value in node.items():
            if is_adapter_name(key):
                continue
            if isinstance(value, dict):
                out[key] = walk(value)
                continue
            a = node.get(key + "_lora_a")
            b = node.get(key + "_lora_b")
            if a is not None and b is not None:
                scale = jnp.asarray(float(alpha) / int(a.shape[-1]),
                                    value.dtype)
                delta = adapter_delta(a, b, tuple(value.shape))
                out[key] = value + scale * delta.astype(value.dtype)
            else:
                out[key] = value
        return out

    return walk(tree)


def split_adapters(params: Any) -> tuple[Any, dict]:
    """Split a fine-tune tree into ``(base_tree, adapters_by_name)``.

    The base tree keeps the model's structure (adapter leaves removed,
    kernels UNmerged); adapters come back as a flat slash-joined-name →
    array dict — the adapter-only checkpoint codec's storage unit."""
    tree = meta.unbox(params)
    adapters: dict = {}

    def walk(node: Any, prefix: str) -> Any:
        if not isinstance(node, dict):
            return node
        out = {}
        for key, value in node.items():
            full = f"{prefix}{key}"
            if is_adapter_name(key) and not isinstance(value, dict):
                adapters[full] = value
            elif isinstance(value, dict):
                out[key] = walk(value, full + "/")
            else:
                out[key] = value
        return out

    return walk(tree, ""), adapters


def combine_adapters(base_params: Any, adapters: dict) -> Any:
    """Graft flat-named adapter leaves back into a base tree — the inverse
    of :func:`split_adapters`, used by the adapter-checkpoint restore.
    Navigates each name through fresh copies of the nested dicts; a name
    whose scope the base tree lacks is a structural drift and raises."""
    def copy(node: Any) -> Any:
        if not isinstance(node, dict):
            return node
        return {k: copy(v) for k, v in node.items()}

    out = copy(meta.unbox(base_params))
    for name, leaf in adapters.items():
        parts = name.split("/")
        node = out
        for part in parts[:-1]:
            child = node.get(part)
            if not isinstance(child, dict):
                raise KeyError(
                    f"adapter leaf {name!r} does not fit the base tree — "
                    f"missing scope {part!r}")
            node = child
        node[parts[-1]] = leaf
    return out


def adapter_mask(tree: Any) -> Any:
    """Bool pytree over ``tree``: True exactly on adapter leaves.

    THE one trainability mask (docs/finetune.md): the optimizer wrap
    (:func:`lora_optimizer`) and the ``trainable_params_frac`` gauge both
    consume it, so what the optimizer updates and what the telemetry
    reports trainable can never disagree. Works on params, grads or
    updates alike — it keys on tree paths only. Flax metadata boxes count
    as LEAVES here, so ``optax.masked``'s ``MaskedNode`` replaces the
    whole box: the optimizer-state tree then carries MaskedNode at the
    same tree depth the sharding resolver sees after ``meta.unbox``, and
    the engine's out_shardings prefix-match holds."""
    def flag(kp, _leaf) -> bool:
        path = "/".join(rules_lib._keystr(k) for k in kp)
        return any(s in path for s in ADAPTER_SUFFIXES)

    return jax.tree_util.tree_map_with_path(
        flag, tree, is_leaf=lambda x: isinstance(x, meta.AxisMetadata))


def _frozen_mask(tree: Any) -> Any:
    """The mask's complement: True on every non-adapter (frozen) leaf."""
    return jax.tree.map(lambda m: not m, adapter_mask(tree))


def lora_optimizer(inner: Any) -> Any:
    """Mask an optimizer so ONLY adapter leaves ever update.

    ``optax.masked(inner, adapter_mask)`` runs the real transformation on
    the adapter leaves (its state — Adam moments — exists only there, so
    the optimizer state is adapter-sized too); the complementary
    ``set_to_zero`` turns every frozen leaf's update into an exact zero,
    and ``optax.apply_updates``' ``p + 0`` keeps the base pytree bitwise
    frozen (pinned by the fingerprint audit in tests/test_zz_finetune.py).
    """
    import optax

    return optax.chain(
        optax.masked(inner, adapter_mask),
        optax.masked(optax.set_to_zero(), _frozen_mask),
    )


def trainable_params_frac(params: Any) -> float:
    """Trainable (adapter) parameter count over the total — the
    ``trainable_params_frac`` gauge of ``finetune/recipe.py``."""
    mask_leaves = jax.tree.leaves(adapter_mask(meta.unbox(params)))
    leaves = jax.tree.leaves(meta.unbox(params))
    total = sum(int(np.prod(l.shape)) for l in leaves)
    trainable = sum(int(np.prod(l.shape))
                    for l, m in zip(leaves, mask_leaves) if m)
    return trainable / max(total, 1)


def base_leaf_digests(params: Any) -> dict:
    """Per-leaf content digests of the BASE (non-adapter) leaves, keyed by
    slash-joined name — the frozen-base identity the adapter checkpoint
    stamps at save and re-verifies at restore, so a drifted base is
    refused naming the exact leaf (docs/finetune.md "Drift refusal")."""
    from fleetx_tpu.resilience import integrity

    out = {}
    for name, leaf in rules_lib.tree_leaf_names(meta.unbox(params)):
        if not is_adapter_name(name):
            out[name] = integrity.digest_array(jax.device_get(leaf))
    return out
