"""Mesh-aware flash attention: per-device kernel execution under dp/tp.

The Pallas kernel is a custom call GSPMD cannot partition;
``flash_attention_sharded`` runs it inside a shard_map manual over every
mesh axis.
Interpret mode makes this testable on the CPU mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fleetx_tpu.ops import flash_attention as fa
from fleetx_tpu.parallel.mesh import build_mesh


def _qkv(b=4, s=256, n=4, d=64, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, s, n, d), jnp.float32)
    return mk(), mk(), mk()


def test_sharded_matches_reference_dp_tp(devices8):
    q, k, v = _qkv()
    assert fa.supported(q, k)
    want = fa.reference_attention(q, k, v, causal=True)

    mesh = build_mesh({"dp_degree": 2, "mp_degree": 2, "fsdp_degree": 2},
                      devices=devices8)
    assert fa.sharded_supported(q, mesh)
    with mesh:
        got = jax.jit(lambda q, k, v: fa.flash_attention_sharded(
            q, k, v, causal=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_sharded_gradients_match(devices8):
    q, k, v = _qkv(b=2, s=256, n=2, d=64, seed=1)

    def loss_ref(q):
        return fa.reference_attention(q, k, v, causal=True).sum()

    g_ref = jax.grad(loss_ref)(q)

    mesh = build_mesh({"dp_degree": 2, "mp_degree": 2}, devices=devices8[:4])
    with mesh:
        g = jax.jit(jax.grad(lambda q: fa.flash_attention_sharded(
            q, k, v, causal=True).sum()))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=5e-3, atol=5e-3)


def test_falls_back_off_mesh():
    q, k, v = _qkv(b=1, s=256, n=1, d=64)
    out = fa.flash_attention_sharded(q, k, v, causal=True, mesh=None)
    want = fa.reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_seq_sharded_mesh_not_claimed(devices8):
    q, _, _ = _qkv()
    mesh = build_mesh({"seq_degree": 2}, devices=devices8[:2])
    assert not fa.sharded_supported(q, mesh)
