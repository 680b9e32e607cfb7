"""Ring attention (context parallelism over the seq axis) — parity tests.

Capability beyond the reference (SURVEY.md §5: no ring/context parallel
anywhere in FleetX); verified against unsharded attention and end-to-end
through the engine on a seq2 mesh.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fleetx_tpu.core.engine import EagerEngine
from fleetx_tpu.core.module import GPTModule
from fleetx_tpu.ops import flash_attention as fa
from fleetx_tpu.ops.ring_attention import ring_attention
from fleetx_tpu.optims.lr_scheduler import build_lr_scheduler
from fleetx_tpu.optims.optimizer import build_optimizer
from fleetx_tpu.parallel.mesh import build_mesh
from fleetx_tpu.parallel.sharding import make_axis_rules


@pytest.mark.parametrize("ring", [2, 4, 8])
def test_ring_matches_reference_attention(devices8, ring):
    rng = np.random.RandomState(0)
    b, s, n, d = 2, 32, 4, 16
    q = jnp.asarray(rng.randn(b, s, n, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, n, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, n, d), jnp.float32)
    want = fa.reference_attention(q, k, v, causal=True)

    mesh = build_mesh({"seq_degree": ring}, devices=devices8[:ring])
    with mesh:
        got = jax.jit(lambda q, k, v: ring_attention(q, k, v, causal=True))(
            q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_gradients_match(devices8):
    rng = np.random.RandomState(1)
    b, s, n, d = 1, 16, 2, 8
    q = jnp.asarray(rng.randn(b, s, n, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, n, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, n, d), jnp.float32)

    def loss_ref(q, k, v):
        return fa.reference_attention(q, k, v, causal=True).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)

    mesh = build_mesh({"seq_degree": 4}, devices=devices8[:4])
    with mesh:
        g_ring = jax.jit(jax.grad(
            lambda q, k, v: ring_attention(q, k, v, causal=True).sum(),
            argnums=(0, 1, 2)))(q, k, v)
    for a, c in zip(g_ref, g_ring):
        np.testing.assert_allclose(np.asarray(c), np.asarray(a),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("ring", [2, 4])
def test_flash_ring_matches_reference(devices8, ring):
    """Flash-composed ring (VERDICT r3 #9): per-block Pallas kernels +
    global-lse backward reproduce reference attention values AND grads.
    Shapes chosen so the local block (128/dev) satisfies the kernel
    contract, i.e. the auto-selection really takes the flash path."""
    from fleetx_tpu.ops.ring_attention import flash_ring_supported

    rng = np.random.RandomState(0)
    b, s, n, d = 2, 128 * ring, 2, 64
    q = jnp.asarray(rng.randn(b, s, n, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, n, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, n, d), jnp.float32)
    want = fa.reference_attention(q, k, v, causal=True)

    mesh = build_mesh({"seq_degree": ring}, devices=devices8[:ring])
    assert flash_ring_supported(q, mesh)
    with mesh:
        got = jax.jit(lambda q, k, v: ring_attention(
            q, k, v, causal=True, use_flash=True))(q, k, v)
        g_ring = jax.jit(jax.grad(
            lambda q, k, v: (ring_attention(q, k, v, causal=True,
                                            use_flash=True) ** 2).sum(),
            argnums=(0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    g_ref = jax.grad(
        lambda q, k, v: (fa.reference_attention(q, k, v, causal=True) ** 2
                         ).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, c in zip(g_ref, g_ring):
        np.testing.assert_allclose(np.asarray(c), np.asarray(a),
                                   rtol=1e-3, atol=2e-4)


VOCAB, SEQ, BATCH = 128, 32, 8


def _cfg(**model_overrides):
    model = dict(vocab_size=VOCAB, hidden_size=64, num_layers=2,
                 num_attention_heads=4, max_position_embeddings=SEQ,
                 hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
                 use_flash_attention=False, dtype="float32",
                 param_dtype="float32")
    model.update(model_overrides)
    return {"Model": model,
            "Engine": {"max_steps": 3, "logging_freq": 1},
            "Global": {"seed": 7}}


def _batches(n, seed=0):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        tokens = rng.randint(0, VOCAB, size=(BATCH, SEQ)).astype(np.int32)
        out.append({
            "tokens": tokens,
            "position_ids": np.broadcast_to(np.arange(SEQ, dtype=np.int32),
                                            (BATCH, SEQ)).copy(),
            "labels": np.roll(tokens, -1, axis=1),
            "loss_mask": np.ones((BATCH, SEQ), np.float32)})
    return out


def _run(cfg, mesh, n=3):
    module = GPTModule(cfg)
    lr = build_lr_scheduler({"name": "cosine", "max_lr": 1e-3, "min_lr": 1e-4,
                             "warmup_steps": 2, "decay_steps": 100})
    opt = build_optimizer({"name": "AdamW", "weight_decay": 0.01,
                           "grad_clip": {"clip_norm": 1.0}}, lr)
    eng = EagerEngine(cfg, module, optimizer=opt, lr_schedule=lr, mesh=mesh)
    eng.max_steps = n
    return eng.fit(_batches(n))


def test_engine_loss_parity_ring_seq_parallel(devices8):
    """seq2 × dp4 ring-attention training reproduces the 1-device curve."""
    ref = _run(_cfg(), build_mesh({}, devices=devices8[:1]))

    cfg = _cfg(use_ring_attention=True)
    cfg["Distributed"] = {"seq_degree": 2, "dp_degree": 4}
    mesh = build_mesh(cfg["Distributed"], devices=devices8)
    got = _run(cfg, mesh)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("chunk", [4, 8])
def test_ring_kv_chunk_streaming_matches_unchunked(devices8, chunk):
    """Chunked K/V streaming (bounded score memory for long context) is the
    exact same math — values AND gradients."""
    rng = np.random.RandomState(2)
    b, s, n, d = 2, 64, 2, 8
    q = jnp.asarray(rng.randn(b, s, n, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, n, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, n, d), jnp.float32)
    want = fa.reference_attention(q, k, v, causal=True)

    mesh = build_mesh({"seq_degree": 4}, devices=devices8[:4])
    with mesh:
        got = jax.jit(lambda q, k, v: ring_attention(
            q, k, v, causal=True, kv_chunk=chunk))(q, k, v)

        def loss_chunked(q, k, v):
            return (ring_attention(q, k, v, causal=True,
                                   kv_chunk=chunk) ** 2).sum()

        grads = jax.jit(jax.grad(loss_chunked, argnums=(0, 1, 2)))(q, k, v)

    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    def loss_ref(q, k, v):
        return (fa.reference_attention(q, k, v, causal=True) ** 2).sum()

    want_grads = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(grads, want_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-4)


def test_ring_kv_chunk_must_divide_block(devices8):
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(1, 32, 2, 8), jnp.float32)
    mesh = build_mesh({"seq_degree": 4}, devices=devices8[:4])
    with mesh:
        with pytest.raises(ValueError, match="must divide"):
            jax.jit(lambda q: ring_attention(q, q, q, causal=True,
                                             kv_chunk=3))(x)
