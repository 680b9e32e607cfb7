"""The selective-scan mixer (Mamba-1, arXiv:2312.00752) as a model's layer
holds it: ONE definition for every family that has one
(``models/samba_y``, ``models/ssm_mqa``). What walks it through a slot's
state and tail is ``serving/programs.py:scan_mixer``; the recurrence itself
is ``ops/selective_scan.py``.

A layer's leaves (``lp``, one layer of a stack): ``in`` [h, 2 inner],
``taps`` [d_conv, inner], ``conv_bias`` [inner], ``x`` [inner, dt_rank + 2
N], ``dt`` [dt_rank, inner], ``dt_bias`` [inner], ``A_log`` [N, inner]
(STATE-major: ``ops/selective_scan.py`` has the reason), ``D`` [inner],
``out`` [inner, h]. The equations, ``u`` the block's normed input::

    [x; z] = W_in u                      (ASSUMED order: x first)
    x_c    = silu(conv(x) + b_c)         (causal, depth-wise, d_conv taps)
    [δ; B; C] = W_x x_c
    Δ      = softplus(W_Δ δ + b_Δ)
    A      = −exp(A_log)
    h_t    = exp(Δ_t A) ⊙ h_{t−1} + (Δ_t x_{c,t}) B_tᵀ
    y_t    = h_t C_t + D ⊙ x_{c,t}
    out    = W_out(y ⊙ silu(z))

**The inner norms are data.** A layer whose leaves hold ``dt_norm``,
``b_norm`` and ``c_norm`` (weights of ``dt_rank``, ``N`` and ``N`` numbers)
norms the three parts of ``W_x x_c`` before they are used — ``δ ← rms(δ) ·
w_δ``, ``B ← rms(B) · w_B``, ``C ← rms(C) · w_C``, float32, ``eps`` the
caller's — and a layer without them does nothing there: the same function,
the same compiled work as before the leaves existed. The block's own norm
(a LayerNorm in one family, an RMS norm in the other) is the caller's: the
mixer takes ``u`` already normed.

``cfg`` is read for two numbers, ``dt_rank`` and ``d_state``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# the depth-wise taps over a tail: the short-convolution family's, as they are
from fleetx_tpu.models.conv_moe.model import (conv_sequence,  # noqa: F401
                                              conv_taps)
from fleetx_tpu.observability.trace import device_scope
from fleetx_tpu.ops import selective_scan as SS

#: the scan's leaves a serving program keeps in float32 (the inner norms'
#: weights among them)
F32_LEAVES = frozenset({"A_log", "D", "dt_bias", "conv_bias", "dt_norm",
                        "b_norm", "c_norm"})
#: the inner norms' leaves, in the order of ``[δ; B; C]``
INNER_NORMS = ("dt_norm", "b_norm", "c_norm")


def leaf_shapes(L: int, h: int, cfg, inner_norms: bool = False) -> dict:
    """The mixer's leaves as shapes, ``L`` layers stacked."""
    di, n, r = cfg.d_inner, cfg.d_state, cfg.dt_rank
    leaves = {"in": (L, h, 2 * di), "taps": (L, cfg.d_conv, di),
              "conv_bias": (L, di), "x": (L, di, r + 2 * n),
              "dt": (L, r, di), "dt_bias": (L, di), "A_log": (L, n, di),
              "D": (L, di), "out": (L, di, h)}
    if inner_norms:
        leaves.update({"dt_norm": (L, r), "b_norm": (L, n),
                       "c_norm": (L, n)})
    return leaves


def init_leaf(names: set, shape: tuple, noise: jax.Array):
    """Mamba-1's own start for the mixer's vectors, or None for a leaf that
    is drawn like any other matrix: ``A_log = log(1 … N)`` a channel, ``D =
    1``, a step bias whose softplus is spread log-uniformly over [1e-3,
    1e-1], taps of 1 / sqrt(taps) + 0.1 N(0, 1), unit inner norms.
    ``names``: the leaf's path; ``noise``: its N(0, 1) draw."""
    if "A_log" in names:
        return jnp.broadcast_to(jnp.log(jnp.arange(
            1, shape[1] + 1, dtype=jnp.float32))[None, :, None], shape)
    if "D" in names or names & set(INNER_NORMS):
        return jnp.ones(shape)
    if "dt_bias" in names:
        # the normal draw's quantile is uniform: steps log-uniform
        u = 0.5 * (1.0 + jax.lax.erf(noise / math.sqrt(2.0)))
        step = jnp.exp(u * (math.log(1e-1) - math.log(1e-3))
                       + math.log(1e-3))
        return step + jnp.log(-jnp.expm1(-step))            # softplus⁻¹
    if "taps" in names:
        return 1.0 / math.sqrt(shape[1]) + 0.1 * noise
    return None


def ssm_in(u: jax.Array, lp: dict) -> tuple:
    """``u`` [rows, h] -> ``(x, z)`` [rows, inner] each (ASSUMED: ``x``
    first), in ``u``'s dtype."""
    xz = jnp.einsum("sh,hc->sc", u, lp["in"])
    half = xz.shape[-1] // 2
    return xz[:, :half], xz[:, half:]


def conv_act(c: jax.Array, lp: dict, dtype) -> jax.Array:
    """``silu(conv + b_c)`` in ``dtype``: what the scan and its three
    products read."""
    return jax.nn.silu(c + lp["conv_bias"]).astype(dtype)


def _rms(v: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return v * jax.lax.rsqrt(jnp.square(v).mean(-1, keepdims=True) + eps) \
        * scale


def ssm_params(xc: jax.Array, lp: dict, cfg, eps: float = 0.0) -> tuple:
    """``xc`` [rows, inner] -> ``(Δ [rows, inner], B, C [rows, N])``
    float32: ``[δ; B; C] = W_x xc``, each part normed where the layer holds
    the inner norms' weights (``eps`` theirs), ``Δ = softplus(W_Δ δ +
    b_Δ)``."""
    r, n = cfg.dt_rank, cfg.d_state
    dbc = jnp.einsum("sc,cr->sr", xc, lp["x"],
                     preferred_element_type=jnp.float32)
    step, b, c = dbc[:, :r], dbc[:, r:r + n], dbc[:, r + n:]
    if "dt_norm" in lp:
        with device_scope("ssm.norm"):
            step, b, c = (_rms(v, lp[name], eps)
                          for v, name in zip((step, b, c), INNER_NORMS))
    delta = jnp.einsum("sr,rc->sc", step.astype(xc.dtype), lp["dt"],
                       preferred_element_type=jnp.float32)
    return jax.nn.softplus(delta + lp["dt_bias"]), b, c


def ssm_decay(lp: dict) -> jax.Array:
    """``A = −exp(A_log)`` [N, inner] float32."""
    return -jnp.exp(lp["A_log"])


def ssm_out(y: jax.Array, z: jax.Array, lp: dict) -> jax.Array:
    """``W_out(y ⊙ silu(z))``: ``y`` float32 (with the ``D`` skip), the
    gate in float32, the product in ``z``'s dtype."""
    g = (y * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype)
    return jnp.einsum("sc,ch->sh", g, lp["out"])


def mix_sequence(u: jax.Array, lp: dict, cfg, dtype, eps: float = 0.0
                 ) -> tuple:
    """One whole sequence from its first token, no cache: ``u`` [S, h]
    (normed) -> ``(the mixer's output [S, h], y [S, inner] float32)``;
    ``y`` the scan's output with the ``D`` skip. The scan is
    ``ops/selective_scan.py``'s plain form."""
    xs, z = ssm_in(u, lp)
    ext = jnp.concatenate([jnp.zeros((cfg.d_conv - 1, xs.shape[1]),
                                     xs.dtype), xs])
    xc = conv_act(conv_sequence(ext, lp["taps"]), lp, dtype)
    delta, b, c = ssm_params(xc, lp, cfg, eps)
    y, _ = SS.scan_rule(xc, delta, ssm_decay(lp), b, c, lp["D"], jnp.zeros(
        (cfg.d_state, cfg.d_inner), jnp.float32))
    return ssm_out(y, z, lp), y
