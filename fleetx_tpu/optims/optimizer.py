"""Optimizers: AdamW with Megatron-style decay masking + global-norm clip.

Re-designs the reference optimizer layer (``ppfleetx/optims/optimizer.py:91-112``
FusedAdamW over fused buffers; grad clip built at ``optims/__init__.py:49-53``).
On TPU there is nothing to hand-fuse — XLA fuses the update elementwise ops —
so the interesting parts are:

- weight-decay masking by parameter *name*: params whose path contains
  ``bias`` or a norm layer get no decay (reference ``optimizer.py:100-105``);
- global-norm clipping across the whole (possibly sharded) grad pytree —
  under pjit the norm reduction runs as XLA collectives over the mesh;
- multi-precision Adam: f32 master moments even for bf16 params;
- single-pass global norm (docs/zero_sharding.md): the norm is an O(params)
  reduction on the step's critical path, and the stock
  ``optax.clip_by_global_norm`` recomputes what the engine already measured
  for the ``grad_norm`` metric.  ``clip_by_precomputed_norm`` accepts the
  norm as an optax extra arg so the caller threads ONE reduction through
  metric + clip; ``adamw(fused_clip=True)`` goes further and owns the norm
  itself, returning ``(updates, opt_state, grad_norm)`` from ``update``;
- leaves moved by the step's load and not by Adam (``LOAD_STEPPED``: a
  sparse-expert router's selection bias): their "gradient" is the experts'
  load less its mean (``models/mla_moe/moe.py`` hands it over as the
  leaf's cotangent), their update ``-rate * sign`` of it, and they stay
  out of the global norm that the clip and the ``grad_norm`` metric use.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import optax

NO_DECAY_SUBSTRINGS = ("bias", "norm", "layernorm")
NO_DECAY_EXACT = ("ln", "ln1", "ln2", "ln_f")


#: last path key of a leaf whose update is ``-rate * sign(load excess)``
LOAD_STEPPED = "selection_bias"


def _keys(path: tuple) -> list:
    return [str(getattr(p, "key", getattr(p, "name", p))) for p in path]


def is_load_stepped_path(path: tuple) -> bool:
    """True for a leaf the step's load moves (its last key is
    ``LOAD_STEPPED``), wherever it sits in params or optimizer state."""
    return bool(path) and _keys(path)[-1] == LOAD_STEPPED


def global_norm(grads: Any) -> jax.Array:
    """``optax.global_norm`` over the leaves that carry gradients: a
    load-stepped leaf's cotangent is a count of rows, not a gradient."""
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    return optax.global_norm([g for path, g in flat
                              if not is_load_stepped_path(path)])


def load_step(inner: optax.GradientTransformation,
              rate: float) -> optax.GradientTransformationExtraArgs:
    """``inner`` for every leaf but the load-stepped ones, whose update is
    ``-rate * sign(incoming)``: an expert that took more than the mean
    load has its selection bias lowered by ``rate``, one that took less
    raised (the auxiliary-loss-free balancing of Wang et al. 2024)."""
    inner = optax.with_extra_args_support(inner)

    def update(grads, state, params=None, **extra):
        updates, state = inner.update(grads, state, params, **extra)
        updates = jax.tree_util.tree_map_with_path(
            lambda path, u, g: (-rate * jnp.sign(g)).astype(u.dtype)
            if is_load_stepped_path(path) else u, updates, grads)
        return updates, state

    return optax.GradientTransformationExtraArgs(inner.init, update)


def is_no_decay_path(path: tuple) -> bool:
    """True if a param path should be excluded from weight decay.

    Mirrors the reference rule — name contains "bias" or "norm"
    (``optimizer.py:100-105``) — applied to flax param tree paths. Norm params
    are named ``scale``/``bias`` under ``ln*`` modules here.
    """
    for k in (k.lower() for k in _keys(path)):
        if any(tok in k for tok in NO_DECAY_SUBSTRINGS) or k in NO_DECAY_EXACT:
            return True
    return False


def decay_mask(params: Any) -> Any:
    """Pytree of bools: True where weight decay applies."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)
    mask = [not is_no_decay_path(path) for path, _ in flat]
    return jax.tree_util.tree_unflatten(treedef, mask)


def clip_by_precomputed_norm(max_norm: float) -> optax.GradientTransformationExtraArgs:
    """``optax.clip_by_global_norm`` that can reuse a norm computed upstream.

    The caller passes the already-reduced global norm as the ``grad_norm``
    extra arg (``optax.chain`` forwards extra args to every member), so the
    jitted step carries exactly ONE norm reduction shared by the
    ``grad_norm`` metric and the clip.  Without the extra arg the norm is
    computed here — standalone use keeps stock semantics.
    """

    def init(params):
        del params
        return optax.EmptyState()

    def update(updates, state, params=None, *, grad_norm=None, **extra):
        """Clip by ``grad_norm`` when threaded in, else compute the norm."""
        del params, extra
        g_norm = global_norm(updates) if grad_norm is None else grad_norm
        # stock optax semantics: scale only when the norm exceeds the cap,
        # propagating NaN norms into the updates (the engine's finite-guard
        # then skips the step)
        trigger = jnp.squeeze(g_norm < max_norm)

        def clip_fn(t):
            return jax.lax.select(
                trigger, t, (t / g_norm.astype(t.dtype)) * max_norm)

        return jax.tree.map(clip_fn, updates), state

    return optax.GradientTransformationExtraArgs(init, update)


class FusedClipOptimizer:
    """Update path that owns the global norm: ``update`` computes it once,
    clips with it, and returns it — ``(updates, opt_state, grad_norm)``.

    Not an ``optax.GradientTransformation`` (the return arity differs);
    the engine detects the ``fused_clip`` attribute and skips its own
    ``optax.global_norm`` pass entirely.
    """

    fused_clip = True

    def __init__(self, inner: optax.GradientTransformation):
        self._inner = optax.with_extra_args_support(inner)

    def init(self, params):
        return self._inner.init(params)

    def update(self, grads, opt_state, params=None):
        """One norm reduction: clip with it, return it with the updates."""
        grad_norm = global_norm(grads)
        updates, new_state = self._inner.update(
            grads, opt_state, params, grad_norm=grad_norm)
        return updates, new_state, grad_norm


def adamw(learning_rate, *, beta1: float = 0.9, beta2: float = 0.999,
          epsilon: float = 1e-8, weight_decay: float = 0.01,
          grad_clip: float | None = 1.0,
          multi_precision: bool = True, fused_clip: bool = False,
          selection_bias_rate: float = 0.001):
    """AdamW + global-norm clip + name-based decay mask.

    The decay mask is computed lazily from the param tree at ``init`` time via
    ``optax.masked`` with a callable mask, so the same transformation works for
    any model family.  ``fused_clip=True`` returns a ``FusedClipOptimizer``
    whose ``update`` is ``(updates, opt_state, grad_norm)`` — the single-pass
    norm owned by the optimizer instead of threaded in by the caller.
    ``selection_bias_rate`` is the step of the load-stepped leaves
    (``load_step``); a tree that has none is untouched by it.
    """
    chain = []
    if grad_clip is not None and grad_clip > 0:
        chain.append(clip_by_precomputed_norm(grad_clip))
    chain.append(optax.scale_by_adam(
        b1=beta1, b2=beta2, eps=epsilon,
        mu_dtype=jnp.float32 if multi_precision else None))
    if weight_decay:
        chain.append(optax.add_decayed_weights(weight_decay, mask=decay_mask))
    chain.append(optax.scale_by_learning_rate(learning_rate))
    tx = load_step(optax.chain(*chain), selection_bias_rate)
    return FusedClipOptimizer(tx) if fused_clip else tx


def sgd(learning_rate, *, momentum: float = 0.9,
        grad_clip: float | None = None, fused_clip: bool = False):
    """Plain SGD with optional momentum (reference Momentum optimizer)."""
    chain = []
    if grad_clip is not None and grad_clip > 0:
        chain.append(clip_by_precomputed_norm(grad_clip))
    chain.append(optax.sgd(learning_rate, momentum=momentum))
    tx = optax.chain(*chain)
    return FusedClipOptimizer(tx) if fused_clip else tx


OPTIMIZERS = {"FusedAdamW": adamw, "AdamW": adamw, "adamw": adamw,
              "Momentum": sgd, "sgd": sgd}


def build_optimizer(cfg: dict, lr_schedule) -> optax.GradientTransformation:
    """Config-driven optimizer factory (reference ``optims/__init__.py:44-62``).

    Accepts the reference YAML keys: ``name``, ``beta1/beta2/epsilon``,
    ``weight_decay``, ``grad_clip.clip_norm``, ``multi_precision``.
    """
    cfg = dict(cfg or {})
    name = cfg.get("name", "AdamW")
    fn = OPTIMIZERS.get(name)
    if fn is None:
        raise ValueError(f"unknown optimizer {name!r}")
    clip = cfg.get("grad_clip")
    clip_norm = None
    fused = bool(cfg.get("fused_clip"))
    if isinstance(clip, dict):
        clip_norm = float(clip.get("clip_norm", 1.0))
        fused = bool(clip.get("fused", fused))
    elif clip is not None:
        clip_norm = float(clip)
    if fn is adamw:
        return adamw(
            lr_schedule,
            beta1=float(cfg.get("beta1", 0.9)),
            beta2=float(cfg.get("beta2", 0.999)),
            epsilon=float(cfg.get("epsilon", 1e-8)),
            weight_decay=float(cfg.get("weight_decay", 0.01)),
            grad_clip=clip_norm,
            multi_precision=bool(cfg.get("multi_precision", True)),
            fused_clip=fused,
            selection_bias_rate=float(cfg.get("selection_bias_rate", 0.001)),
        )
    return sgd(lr_schedule, momentum=float(cfg.get("momentum", 0.9)),
               grad_clip=clip_norm, fused_clip=fused)
