"""Fused single-pass flash backward + consumed-layout scan residuals
(docs/bandwidth_levers.md): one fused backward sweep where the split path
runs a dq and a dkv kernel, and the scan-stacked residuals saved in the
layout the backward reads.

Everything here runs in Pallas interpret mode on the CPU mesh: kernel
grad parity fused vs split vs naive, fallback-predicate units, the
save-point transform pipeline's layout/byte evidence via
``saved_residuals``, fit-loop loss parity with both levers on, and config
round-trips.

zz-sorted per the tier-1 convention so the timeout-bound gate keeps its
seed dots.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fleetx_tpu.models.gpt.model import (GPTConfig, GPTForPretraining,
                                         RESIDUAL_CONSUMED_PERMS,
                                         RESIDUAL_NAMES, config_from_dict,
                                         cross_entropy_loss)
from fleetx_tpu.ops import flash_attention as FA

pytestmark = pytest.mark.flashbwd

VOCAB, SEQ, BATCH = 128, 128, 2


def _qkv(b=1, s=256, n=2, d=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, s, n, d), dtype) for k in ks)


def _grads(fn, *args):
    return jax.grad(fn, argnums=(0, 1, 2))(*args)


# ------------------------------------------------ kernel-level grad parity


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("seq", [256, 384])
def test_fused_matches_split_and_reference(causal, seq):
    """The fused sweep must agree with the split dq/dkv pair essentially
    bitwise (same f32 tile math, different schedule) and with naive
    attention within the existing flash tolerance. 384 exercises the
    128-block fallback grid."""
    q, k, v = _qkv(s=seq)
    assert FA.fused_backward_supported(q, k, causal=causal)

    def loss(fused):
        return lambda q, k, v: (FA.flash_attention(
            q, k, v, causal=causal, fused_bwd=fused) ** 2).sum()

    g_fused = _grads(loss(True), q, k, v)
    g_split = _grads(loss(False), q, k, v)
    g_ref = _grads(lambda q, k, v: (FA.reference_attention(
        q, k, v, causal=causal) ** 2).sum(), q, k, v)
    for a, b in zip(g_fused, g_split):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
    for a, b in zip(g_fused, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_fused_bf16_matches_split():
    q, k, v = _qkv(s=256, dtype=jnp.bfloat16, seed=3)

    def loss(fused):
        return lambda q: (FA.flash_attention(
            q, k, v, causal=True, fused_bwd=fused).astype(jnp.float32)
            ** 2).sum()

    g_fused = jax.grad(loss(True))(q)
    g_split = jax.grad(loss(False))(q)
    np.testing.assert_allclose(np.asarray(g_fused, np.float32),
                               np.asarray(g_split, np.float32),
                               rtol=2e-2, atol=2e-2)


# ------------------------------------------------ fallback predicate units


def test_fused_predicate_rejects_unsupported_shapes():
    ok = jnp.zeros((1, 256, 2, 64))
    assert FA.fused_backward_supported(ok, ok)
    # non-tiling sequence: base supported() already refuses
    assert not FA.fused_backward_supported(jnp.zeros((1, 100, 2, 64)))
    # wide heads degrade to the split kernels (their per-block scratch
    # stays bounded where the fused dq accumulator would not)
    wide = jnp.zeros((1, 256, 2, 256))
    assert FA.supported(wide, wide)
    assert not FA.fused_backward_supported(wide, wide)
    # full-sequence dq scratch over budget: seq 16384 at head_dim 128 is
    # ~8.9 MiB of f32 — past _FUSED_DQ_SCRATCH_BYTES
    long = jnp.zeros((1, 16384, 1, 128))
    assert FA.supported(long, long)
    assert not FA.fused_backward_supported(long, long)
    # a 64-wide head pads to 128 lanes in VMEM: seq 15360 is 4 MiB by
    # element count, but the v5e Mosaic compile refuses it (17.25 of 16 MiB)
    padded = jnp.zeros((1, 15360, 1, 64))
    assert FA.supported(padded, padded)
    assert not FA.fused_backward_supported(padded, padded)
    # an explicit non-tiling block override refuses like supported()
    assert not FA.fused_backward_supported(ok, ok, block_q=96)


def test_fused_dropout_branch_traces():
    """The in-kernel dropout branch can't EXECUTE off-TPU (no interpret
    lowering for the TPU PRNG), but it can be TRACED — which is enough to
    catch Python-level breakage in the branch (a review pass found an
    undefined name there that no executing test could reach)."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (1, 256, 2, 64)) for kk in ks)
    seed = jnp.ones((1,), jnp.int32)
    for fused, want in ((True, 2), (False, 3)):
        jx = jax.make_jaxpr(jax.grad(lambda q: (FA.flash_attention(
            q, k, v, causal=True, dropout_rate=0.1, dropout_seed=seed,
            fused_bwd=fused) ** 2).sum()))(q)
        assert str(jx).count("pallas_call") == want


def test_unsupported_shape_dispatches_split_despite_flag():
    """fused_bwd=True on a predicate-rejected shape must compile the
    split kernels (3 backward-capable pallas_calls in the grad program),
    never silence or a failing fused launch."""
    def count(q, k, v, fused):
        f = lambda q: (FA.flash_attention(q, k, v, causal=True,  # noqa: E731
                                          fused_bwd=fused) ** 2).sum()
        return str(jax.make_jaxpr(jax.grad(f))(q)).count("pallas_call")

    wide = _qkv(s=256, d=256, seed=1)
    assert count(*wide, fused=True) == 3   # fwd + dq + dkv: split fallback
    ok = _qkv(s=256, d=64, seed=1)
    assert count(*ok, fused=True) == 2     # fwd + ONE fused backward sweep
    assert count(*ok, fused=False) == 3


# ------------------------------------------------ model-level composition


def _model(**overrides):
    kw = dict(vocab_size=VOCAB, hidden_size=128, num_layers=2,
              num_attention_heads=2, max_position_embeddings=SEQ,
              hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
              use_flash_attention=True, dtype=jnp.float32,
              param_dtype=jnp.float32, use_recompute=True,
              recompute_granularity="dots")
    kw.update(overrides)
    return GPTForPretraining(GPTConfig(**kw))


def _loss_and_grads(model, seed=0):
    rng = np.random.RandomState(seed)
    tokens = jnp.asarray(rng.randint(0, VOCAB, size=(BATCH, SEQ)), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(SEQ), (BATCH, SEQ))
    labels = jnp.asarray(rng.randint(0, VOCAB, size=(BATCH, SEQ)), jnp.int32)
    params = model.init({"params": jax.random.PRNGKey(0)}, tokens, pos,
                        deterministic=True)["params"]

    def loss_fn(p):
        logits = model.apply({"params": p}, tokens, pos, deterministic=True)
        return cross_entropy_loss(logits, labels,
                                  jnp.ones((BATCH, SEQ), jnp.float32))

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    return float(loss), grads, loss_fn, params


@pytest.mark.parametrize("granularity", ["dots", "full"])
def test_model_grads_fused_vs_split(granularity):
    """Fused vs split backward through the remat'd scan stack: the
    forward is identical, so losses match exactly and grads within the
    kernels' mutual tolerance — under both remat granularities."""
    l_f, g_f, _, _ = _loss_and_grads(
        _model(recompute_granularity=granularity, flash_fused_bwd=True))
    l_s, g_s, _, _ = _loss_and_grads(
        _model(recompute_granularity=granularity, flash_fused_bwd=False))
    assert l_f == l_s
    for a, b in zip(jax.tree.leaves(g_f), jax.tree.leaves(g_s)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_model_grads_fused_with_bf16_savedtype():
    """Both tentpole levers + the PR 3 bf16 save-dtype compose: one
    save-point transform pipeline, drift bounded like the PR 3 tests."""
    l_ref, g_ref, _, _ = _loss_and_grads(
        _model(flash_fused_bwd=False, remat_consumed_layout=False))
    l_all, g_all, _, _ = _loss_and_grads(
        _model(flash_fused_bwd=True, remat_consumed_layout=True,
               remat_save_dtype=jnp.bfloat16))
    assert np.isfinite(l_all)
    assert abs(l_all - l_ref) < 5e-3
    n_ref = sum(float(jnp.sum(jnp.square(g)))
                for g in jax.tree.leaves(g_ref)) ** 0.5
    n_all = sum(float(jnp.sum(jnp.square(g)))
                for g in jax.tree.leaves(g_all)) ** 0.5
    np.testing.assert_allclose(n_all, n_ref, rtol=5e-2)


# ------------------------------------------- consumed-layout residuals


def test_consumed_layout_is_exact():
    """The layout lever is transposes only — loss and grads identical
    bitwise with it on or off (unlike the bf16 cast, which quantises)."""
    l_on, g_on, _, _ = _loss_and_grads(_model(remat_consumed_layout=True,
                                              use_flash_attention=False))
    l_off, g_off, _, _ = _loss_and_grads(_model(remat_consumed_layout=False,
                                                use_flash_attention=False))
    assert l_on == l_off
    for a, b in zip(jax.tree.leaves(g_on), jax.tree.leaves(g_off)):
        assert jnp.array_equal(a, b)


def test_consumed_layout_saved_residuals():
    """The scan-stacked qkv residual must be WRITTEN consumed-layout:
    [layers, 3, b, s, n, d] (q/k/v split = contiguous leading slices)
    instead of the produced [layers, b, 3, s, n, d] — same bytes (the
    lever is free), different orientation. The named tags must be in the
    grad program even with no dtype cast (the names-keyed policy is what
    makes the scan stack the transformed copies)."""
    try:
        from jax._src.ad_checkpoint import saved_residuals
    except ImportError:
        pytest.skip("saved_residuals private API unavailable")

    def qkv_stacks(loss_fn, params):
        res = [a for a, _ in saved_residuals(loss_fn, params)
               if len(a.shape) == 6]
        return ([tuple(a.shape) for a in res],
                sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in res))

    _, _, loss_on, p_on = _loss_and_grads(
        _model(remat_consumed_layout=True, use_flash_attention=False))
    _, _, loss_off, p_off = _loss_and_grads(
        _model(remat_consumed_layout=False, use_flash_attention=False))

    jaxpr = str(jax.make_jaxpr(jax.grad(loss_on))(p_on))
    for name in RESIDUAL_NAMES:
        assert name in jaxpr, f"named save point {name} missing"

    on_shapes, on_bytes = qkv_stacks(loss_on, p_on)
    off_shapes, off_bytes = qkv_stacks(loss_off, p_off)
    # consumed layout: [layers, 3, batch, seq, heads, head_dim] — the
    # q/k/v split is a contiguous leading slice and each slice already
    # has the [b, s, n, d] shape the attention backward reads
    consumed = (2, 3, BATCH, SEQ, 2, 64)
    assert consumed in on_shapes, on_shapes
    # the stock policy saves the einsum's raw dot output instead — a
    # seq-last order no consumer reads directly (the backward's first
    # act is the re-copy this lever deletes)
    assert consumed not in off_shapes, off_shapes
    # transposes move no bytes: the stacked qkv buffer costs the same
    # either way (the lever is free — unlike the bf16 cast, which halves)
    assert on_bytes == off_bytes


def test_consumed_perm_is_an_involution_inverse():
    """The save-point pipeline inverts every registered permutation."""
    for name, perm in RESIDUAL_CONSUMED_PERMS.items():
        assert name in RESIDUAL_NAMES
        inv = [0] * len(perm)
        for i, p in enumerate(perm):
            inv[p] = i
        assert tuple(perm[j] for j in inv) == tuple(range(len(perm)))


def test_transforms_inert_off_gate():
    """Outside use_recompute+dots (and on MoE stacks) the save-point
    pipeline must leave the program untouched — no named tags."""
    m = _model(use_recompute=False, use_flash_attention=False)
    _, _, loss_fn, params = _loss_and_grads(m)
    jaxpr = str(jax.make_jaxpr(jax.grad(loss_fn))(params))
    for name in RESIDUAL_NAMES:
        assert name not in jaxpr


# ------------------------------------------------------ fit-loop parity


def test_fit_losscurve_parity_with_levers_on(devices8):
    """Acceptance: a CPU-mesh fit curve with BOTH tentpole levers on
    (+ the bf16 save-dtype composed) matches the split/produced-layout
    baseline within the PR 3 drift bound. The model shape admits the
    flash kernel (seq 128, head_dim 64) so the fused backward really
    compiles into the step."""
    from fleetx_tpu.core.engine import EagerEngine
    from fleetx_tpu.core.module import GPTModule
    from fleetx_tpu.optims.lr_scheduler import build_lr_scheduler
    from fleetx_tpu.optims.optimizer import build_optimizer
    from fleetx_tpu.parallel.mesh import build_mesh

    def run(model_overrides, n=3):
        model = dict(vocab_size=VOCAB, hidden_size=128, num_layers=2,
                     num_attention_heads=2, max_position_embeddings=SEQ,
                     hidden_dropout_prob=0.0,
                     attention_probs_dropout_prob=0.0,
                     use_flash_attention=True, use_recompute=True,
                     recompute_granularity="dots", dtype="float32",
                     param_dtype="float32")
        model.update(model_overrides)
        cfg = {"Model": model,
               "Engine": {"max_steps": n, "logging_freq": 1, "eval_freq": 0},
               "Global": {"seed": 7}}
        mesh = build_mesh({}, devices=devices8[:1])
        module = GPTModule(cfg)
        lr = build_lr_scheduler({"max_lr": 1e-3, "warmup_steps": 2,
                                 "decay_steps": 100})
        opt = build_optimizer({"name": "AdamW"}, lr)
        eng = EagerEngine(cfg, module, optimizer=opt, lr_schedule=lr,
                          mesh=mesh)
        eng.max_steps = n
        rng = np.random.RandomState(0)
        batches = []
        for _ in range(n):
            tokens = rng.randint(0, VOCAB, size=(BATCH, SEQ)).astype(np.int32)
            batches.append({
                "tokens": tokens,
                "position_ids": np.broadcast_to(
                    np.arange(SEQ, dtype=np.int32), (BATCH, SEQ)).copy(),
                "labels": rng.randint(
                    0, VOCAB, size=(BATCH, SEQ)).astype(np.int32),
                "loss_mask": np.ones((BATCH, SEQ), np.float32)})
        return eng.fit(batches)

    base = run(dict(flash_fused_bwd=False, remat_consumed_layout=False))
    levers = run(dict(flash_fused_bwd=True, remat_consumed_layout=True,
                      remat_save_dtype="bfloat16"))
    assert len(base) == len(levers) == 3
    np.testing.assert_allclose(levers, base, rtol=5e-3, atol=5e-3)


# --------------------------------------------------- config round-trips


def test_config_roundtrip_new_knobs(tmp_path):
    cfg = config_from_dict({"flash_fused_bwd": False,
                            "remat_consumed_layout": False})
    assert cfg.flash_fused_bwd is False
    assert cfg.remat_consumed_layout is False
    assert GPTConfig().flash_fused_bwd is True
    assert GPTConfig().remat_consumed_layout is True

    from fleetx_tpu.core.module import GPTModule
    from fleetx_tpu.utils.config import get_config

    cfg_file = tmp_path / "cfg.yaml"
    cfg_file.write_text(
        "Global:\n  local_batch_size: 4\n"
        "Model:\n"
        "  vocab_size: 128\n  hidden_size: 64\n  num_layers: 2\n"
        "  num_attention_heads: 4\n  max_position_embeddings: 32\n"
        "  use_recompute: true\n  recompute_granularity: dots\n"
        "  flash_fused_bwd: false\n  remat_consumed_layout: false\n")
    model_cfg = GPTModule(get_config(str(cfg_file), num_devices=1)).model_cfg
    assert model_cfg.flash_fused_bwd is False
    assert model_cfg.remat_consumed_layout is False


def test_config_zoo_base_carries_the_knobs():
    import os

    from fleetx_tpu.utils.config import get_config

    base = os.path.join(os.path.dirname(__file__), "..", "fleetx_tpu",
                        "configs", "nlp", "gpt",
                        "pretrain_gpt_345M_single_card.yaml")
    cfg = get_config(base, num_devices=1)
    assert cfg["Model"]["flash_fused_bwd"] is True
    assert cfg["Model"]["remat_consumed_layout"] is True
