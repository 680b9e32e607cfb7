"""Plain reference: global-norm clip, AdamW with a decay mask, and the
Megatron warm-up + cosine schedule, as Loshchilov & Hutter (2019) and the
recipe's ``Optimizer`` section state them. float32; imports nothing of the
program."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def learning_rate(opt: dict, count: int) -> float:
    """The rate of update number ``count`` (0 for the first)."""
    warm, decay = int(opt["warmup_steps"]), max(int(opt["decay_steps"]), 1)
    if count < warm:
        return opt["max_lr"] * count / max(warm, 1)
    progress = min(max((count - warm) / max(decay - warm, 1), 0.0), 1.0)
    return opt["min_lr"] + 0.5 * (opt["max_lr"] - opt["min_lr"]) * (
        1.0 + math.cos(math.pi * progress))


def global_norm(tree: dict):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in tree.values()))


def clip(grads: dict, max_norm: float) -> dict:
    """The gradient as the optimizer gets it."""
    norm = global_norm(grads)
    scale = jnp.where(norm < max_norm, 1.0, max_norm / norm)
    return {k: g * scale for k, g in grads.items()}


@jax.jit
def _update(w, mu, nu, grads, decayed, lr, b1, b2, eps, wd, count):
    new_w, new_mu, new_nu = {}, {}, {}
    for k in w:
        m = b1 * mu[k] + (1.0 - b1) * grads[k]
        v = b2 * nu[k] + (1.0 - b2) * jnp.square(grads[k])
        step = (m / (1.0 - b1 ** count)) / (
            jnp.sqrt(v / (1.0 - b2 ** count)) + eps)
        step = step + wd * decayed[k] * w[k]
        new_w[k], new_mu[k], new_nu[k] = w[k] - lr * step, m, v
    return new_w, new_mu, new_nu


def init(w: dict) -> tuple:
    """Zero first and second moments."""
    zeros = {k: jnp.zeros_like(v) for k, v in w.items()}
    return zeros, dict(zeros)


def step(w: dict, state: tuple, grads: dict, opt: dict, kinds: dict,
         count: int) -> tuple:
    """Update number ``count`` (1 for the first) of ``w`` by clipped
    ``grads``; returns ``(w, state, clipped_grads)``."""
    mu, nu = state
    clipped = clip(grads, float(opt["clip_norm"]))
    decayed = {k: jnp.float32(kinds[k] == "matrix") for k in w}
    new_w, mu, nu = _update(
        w, mu, nu, clipped, decayed,
        jnp.float32(learning_rate(opt, count - 1)), jnp.float32(opt["beta1"]),
        jnp.float32(opt["beta2"]), jnp.float32(opt["epsilon"]),
        jnp.float32(opt["weight_decay"]), jnp.float32(count))
    return new_w, (mu, nu), clipped
