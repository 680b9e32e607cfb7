"""Plain reference: ``adamw_ref``'s optimizer (global-norm clip, AdamW with
a decay mask, warm-up + cosine schedule) for every leaf but those of kind
``selection_bias``, which are moved by the load and not by Adam:

    b <- b - rate * sign(load - mean load)

(Wang et al. 2024, "Auxiliary-Loss-Free Load Balancing", as DeepSeek-V3
section 2.1.2 uses it; ``rate`` is ``selection_bias_rate`` of the
configuration's ``train.optimizer``). The reference's ``loss_and_grads``
puts ``load - mean load`` where such a leaf's gradient would be. Those
leaves are left out of the clip's norm and of the clipped gradients.

float32 arithmetic on the device, leaf by leaf. What differs from
``adamw_ref`` is where the numbers rest between steps: the two moments and
the clipped gradients that ``step`` returns are kept in HOST memory.
``check.train_reference`` holds the seeded weights, the current weights, a
gradient and (one step long) the clipped gradient at once; with both
moments on the device too that is seven float32 copies of this
configuration's 680 M parameters, 19 GB on a 16 GB chip. Imports nothing
of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

LOAD_STEPPED = "selection_bias"


def learning_rate(opt: dict, count: int) -> float:
    """The rate of update number ``count`` (0 for the first)."""
    warm, decay = int(opt["warmup_steps"]), max(int(opt["decay_steps"]), 1)
    if count < warm:
        return opt["max_lr"] * count / max(warm, 1)
    progress = min(max((count - warm) / max(decay - warm, 1), 0.0), 1.0)
    return opt["min_lr"] + 0.5 * (opt["max_lr"] - opt["min_lr"]) * (
        1.0 + math.cos(math.pi * progress))


@jax.jit
def _square_sum(g):
    return jnp.sum(jnp.square(g))


@jax.jit
def _adam_leaf(w, mu, nu, g, scale, decayed, lr, b1, b2, eps, wd, count):
    g = g * scale
    m = b1 * mu + (1.0 - b1) * g
    v = b2 * nu + (1.0 - b2) * jnp.square(g)
    step = (m / (1.0 - b1 ** count)) / (jnp.sqrt(v / (1.0 - b2 ** count))
                                        + eps)
    return w - lr * (step + wd * decayed * w), m, v, g


def init(w: dict) -> tuple:
    """Zero first and second moments, in host memory."""
    return ({k: np.zeros(v.shape, np.float32) for k, v in w.items()},
            {k: np.zeros(v.shape, np.float32) for k, v in w.items()})


def step(w: dict, state: tuple, grads: dict, opt: dict, kinds: dict,
         count: int) -> tuple:
    """Update number ``count`` (1 for the first) of ``w``; returns ``(w,
    state, clipped_grads)``, the last without the load-stepped leaves."""
    mu, nu = state
    adam = [k for k in w if kinds[k] != LOAD_STEPPED]
    norm = jnp.sqrt(sum(_square_sum(grads[k]) for k in adam))
    max_norm = float(opt["clip_norm"])
    scale = jnp.where(norm < max_norm, 1.0, max_norm / norm)
    f32 = jnp.float32
    lr = f32(learning_rate(opt, count - 1))
    new_w, new_mu, new_nu, clipped = {}, {}, {}, {}
    for k in w:
        if kinds[k] == LOAD_STEPPED:
            new_w[k] = w[k] - f32(opt["selection_bias_rate"]) * jnp.sign(
                grads[k])
            new_mu[k], new_nu[k] = mu[k], nu[k]
            continue
        new_w[k], m, v, g = _adam_leaf(
            w[k], mu[k], nu[k], grads[k], scale,
            f32(kinds[k] == "matrix"), lr, f32(opt["beta1"]),
            f32(opt["beta2"]), f32(opt["epsilon"]), f32(opt["weight_decay"]),
            f32(count))
        new_mu[k], new_nu[k], clipped[k] = (np.asarray(m), np.asarray(v),
                                            np.asarray(g))
    return new_w, (new_mu, new_nu), clipped
