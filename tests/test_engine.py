"""Engine end-to-end: sharded train loop, loss decrease, dp/tp/fsdp parity.

This is the multi-device correctness evidence the reference never had
(SURVEY.md §4): the same tiny GPT trained on a 1-device mesh and an 8-device
dp×tensor×fsdp mesh must produce the same loss sequence.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fleetx_tpu.core.engine import EagerEngine
from fleetx_tpu.core.module import GPTModule
from fleetx_tpu.optims.lr_scheduler import build_lr_scheduler
from fleetx_tpu.optims.optimizer import build_optimizer
from fleetx_tpu.parallel.mesh import build_mesh

VOCAB = 128
SEQ = 32
BATCH = 8


def tiny_cfg(**model_overrides):
    model = dict(
        vocab_size=VOCAB, hidden_size=64, num_layers=2, num_attention_heads=4,
        max_position_embeddings=SEQ, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0, use_flash_attention=False,
        dtype="float32", param_dtype="float32")
    model.update(model_overrides)
    return {
        "Model": model,
        "Engine": {"max_steps": 5, "logging_freq": 1, "eval_freq": 0},
        "Global": {"seed": 7},
    }


def make_batches(n, seed=0, batch=BATCH):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        tokens = rng.randint(0, VOCAB, size=(batch, SEQ)).astype(np.int32)
        out.append({
            "tokens": tokens,
            "position_ids": np.broadcast_to(np.arange(SEQ, dtype=np.int32),
                                            (batch, SEQ)).copy(),
            "labels": rng.randint(0, VOCAB, size=(batch, SEQ)).astype(np.int32),
            "loss_mask": np.ones((batch, SEQ), np.float32),
        })
    return out


def build_engine(cfg, mesh, max_lr=1e-3):
    module = GPTModule(cfg)
    lr = build_lr_scheduler({"name": "cosine", "max_lr": max_lr, "min_lr": 1e-4,
                             "warmup_steps": 2, "decay_steps": 100})
    opt = build_optimizer({"name": "AdamW", "weight_decay": 0.01,
                           "grad_clip": {"clip_norm": 1.0}}, lr)
    return EagerEngine(cfg, module, optimizer=opt, lr_schedule=lr, mesh=mesh)


def run_losses(cfg, mesh, n_steps, seed=0):
    eng = build_engine(cfg, mesh)
    cfg["Engine"]["max_steps"] = n_steps
    eng.max_steps = n_steps
    return eng.fit(make_batches(n_steps, seed=seed))


def test_train_loss_starts_at_log_vocab_and_decreases(devices8):
    mesh = build_mesh({}, devices=devices8[:1])
    eng = build_engine(tiny_cfg(), mesh)
    eng.max_steps = 8
    # one learnable batch repeated: loss must fall as the model memorizes it
    b = make_batches(1, seed=3)[0]
    b["labels"] = np.roll(b["tokens"], -1, axis=1)
    losses = eng.fit([b] * 8)
    assert len(losses) == 8
    # untrained model ≈ uniform over vocab: first loss ~ log(VOCAB)
    assert abs(losses[0] - np.log(VOCAB)) < 0.5, losses
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0] - 0.1, losses


def test_sharded_loss_parity_dp_tp_fsdp(devices8):
    """dp2 × tensor2 × fsdp2 must reproduce the single-device loss curve."""
    cfg = tiny_cfg()
    mesh1 = build_mesh({}, devices=devices8[:1])
    ref = run_losses(cfg, mesh1, 4)

    cfg8 = tiny_cfg()
    cfg8["Distributed"] = {"dp_degree": 2, "mp_degree": 2, "fsdp_degree": 2}
    mesh8 = build_mesh(cfg8["Distributed"], devices=devices8)
    got = run_losses(cfg8, mesh8, 4)

    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_sharded_loss_parity_sequence_parallel(devices8):
    """Megatron-SP (act_seq over tensor axis) keeps loss parity."""
    cfg = tiny_cfg()
    mesh1 = build_mesh({}, devices=devices8[:1])
    ref = run_losses(cfg, mesh1, 3)

    cfg_sp = tiny_cfg(sequence_parallel=True)
    cfg_sp["Distributed"] = {"mp_degree": 4, "dp_degree": 2,
                             "sequence_parallel": True}
    mesh8 = build_mesh(cfg_sp["Distributed"], devices=devices8)
    got = run_losses(cfg_sp, mesh8, 3)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_zero_stage2_shards_optimizer_state(devices8):
    cfg = tiny_cfg()
    cfg["Distributed"] = {"fsdp_degree": 4, "dp_degree": 2,
                          "sharding": {"sharding_stage": 2}}
    mesh = build_mesh(cfg["Distributed"], devices=devices8)
    eng = build_engine(cfg, mesh)
    eng.prepare(make_batches(1)[0])

    def spec_axes(arr):
        axes = set()
        for entry in arr.sharding.spec:
            if isinstance(entry, (tuple, list)):
                axes.update(entry)
            elif entry is not None:
                axes.add(entry)
        return axes

    opt_axes = [spec_axes(l) for l in jax.tree.leaves(eng.state.opt_state)]
    assert any("fsdp" in a for a in opt_axes), \
        f"no optimizer-state leaf sharded over fsdp: {opt_axes}"
    # params stay replicated at stage 2 (no fsdp in their specs)
    for leaf in jax.tree.leaves(eng.state.params):
        assert "fsdp" not in spec_axes(leaf)


def test_grad_accumulation_matches_big_batch(devices8):
    mesh = build_mesh({}, devices=devices8[:1])
    cfg_a = tiny_cfg()
    ref = run_losses(cfg_a, mesh, 3)

    cfg_b = tiny_cfg()
    cfg_b["Engine"]["accumulate_steps"] = 4
    got = run_losses(cfg_b, mesh, 3)
    # average-of-micro-losses == big-batch loss for the mean CE with equal
    # masks; allow small fp reassociation slack
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_predict_returns_logits(devices8):
    cfg = tiny_cfg()
    cfg["Distributed"] = {"dp_degree": 4, "mp_degree": 2}
    mesh = build_mesh(cfg["Distributed"], devices=devices8)
    eng = build_engine(cfg, mesh)
    b = make_batches(1)[0]
    eng.prepare(b)
    outs = eng.predict([b, b], max_batches=2)
    assert len(outs) == 2
    assert outs[0].shape == (BATCH, SEQ, VOCAB)
    assert np.isfinite(outs[0]).all()


def test_fp16_scaler_runs_and_is_finite(devices8):
    mesh = build_mesh({}, devices=devices8[:1])
    cfg = tiny_cfg(dtype="float16")
    cfg["Engine"]["mix_precision"] = {"use_pure_fp16": True, "scale_loss": 1024}
    losses = run_losses(cfg, mesh, 3)
    assert all(np.isfinite(losses))


def test_fp16_overflow_skips_step_and_backs_off_scale(devices8):
    """An absurd initial loss scale overflows the scaled grads: every update
    in a one-shot pass must be skipped (state.step frozen at 0) while the
    scale halves per overflow (reference GradScaler). A second engine with a
    sane scale must reach max_steps over the same stream."""
    mesh = build_mesh({}, devices=devices8[:1])
    cfg = tiny_cfg(dtype="float16")
    cfg["Engine"]["mix_precision"] = {"use_pure_fp16": True,
                                      "scale_loss": 2.0 ** 125}
    eng = build_engine(cfg, mesh)
    eng.max_steps = 10
    batches = make_batches(10)
    eng.prepare(batches[0])
    assert float(jax.device_get(eng.state.scaler.loss_scale)) == 2.0 ** 125
    eng.fit(iter(batches))  # one-shot: exactly 10 batches, all overflowing
    final_step = int(jax.device_get(eng.state.step))
    final_scale = float(jax.device_get(eng.state.scaler.loss_scale))
    assert final_step == 0, final_step          # every update skipped
    assert final_scale == 2.0 ** 115, final_scale  # halved once per batch
    # params untouched and finite despite the overflow burst
    for leaf in jax.tree.leaves(eng.state.params):
        assert np.isfinite(np.asarray(jax.device_get(leaf))).all()

    # with a list (re-iterable) loader, fit keeps feeding batches until
    # max_steps OPTIMIZER steps complete — the scale recovers into range
    eng2 = build_engine(cfg, mesh)
    eng2.max_steps = 5
    eng2.fit(batches)
    assert int(jax.device_get(eng2.state.step)) == 5
    assert float(jax.device_get(eng2.state.scaler.loss_scale)) < 2.0 ** 125


def test_prng_impl_rbg(devices8):
    """Global.prng_impl switches the dropout/init PRNG family (throughput
    option for TPU; threefry stays the default)."""
    cfg = tiny_cfg(hidden_dropout_prob=0.1)
    cfg["Global"]["prng_impl"] = "rbg"
    mesh = build_mesh({}, devices=devices8[:1])
    eng = build_engine(cfg, mesh)
    eng.max_steps = 2
    losses = eng.fit(make_batches(2))
    assert len(losses) == 2 and all(np.isfinite(losses)), losses


def test_epoch_mode_respects_epoch_num_and_logs_epochs(devices8):
    """run_mode=epoch (ViT-style): stop after epoch_num passes over the
    loader and report the real epoch index (VERDICT r4 #7 — `fit` used to
    ignore epoch_num and log `epoch: 0` forever)."""
    cfg = tiny_cfg()
    cfg["Engine"].update(run_mode="epoch", max_steps=1000)
    mesh = build_mesh({}, devices=devices8[:1])
    eng = build_engine(cfg, mesh)
    eng.max_steps = 1000
    seen = []
    orig = eng.module.training_step_end
    eng.module.training_step_end = lambda log: (seen.append(log["epoch"]),
                                                orig(log))[-1]
    losses = eng.fit(make_batches(4, seed=5), epoch_num=3)
    # 3 epochs x 4 batches, NOT 1000 steps
    assert len(losses) == 12, len(losses)
    assert seen == [0] * 4 + [1] * 4 + [2] * 4, seen
    assert eng._epoch == 3


def test_step_mode_loops_loader_past_epoch_num(devices8):
    """run_mode=step (GPT pretrain, the default): epoch_num does NOT bound
    the run — the loader re-iterates until max_steps."""
    cfg = tiny_cfg()
    cfg["Engine"]["max_steps"] = 6
    mesh = build_mesh({}, devices=devices8[:1])
    eng = build_engine(cfg, mesh)
    losses = eng.fit(make_batches(2, seed=6), epoch_num=1)
    assert len(losses) == 6  # 3 passes over the 2-batch loader


def test_epoch_survives_checkpoint_roundtrip(devices8, tmp_path):
    """The epoch reached is saved and restored (resume starts at the
    checkpointed epoch, not 0)."""
    cfg = tiny_cfg()
    cfg["Engine"].update(run_mode="epoch", max_steps=1000,
                         save_load={"save_steps": 1000,
                                    "output_dir": str(tmp_path)})
    mesh = build_mesh({}, devices=devices8[:1])
    eng = build_engine(cfg, mesh)
    eng.max_steps = 1000
    eng.fit(make_batches(2, seed=7), epoch_num=2)
    assert eng._epoch == 2
    eng.save()

    eng2 = build_engine(cfg, mesh)
    eng2.prepare(make_batches(1, seed=7)[0])
    assert eng2.load(str(tmp_path))
    assert eng2._start_epoch == 2
    # resuming a finished epoch-mode run must train ZERO further steps
    # (the first loader pass is not exempt from the epoch_num bound)
    eng2.max_steps = 1000
    losses = eng2.fit(make_batches(2, seed=7), epoch_num=2)
    assert not losses, losses


def test_offload_boundary_advice(caplog):
    """ZeRO offload is a fit-enabler (its cost: not measured on the chip,
    ROADMAP S10); `offload_is_needed` states the boundary and the
    engine warns when a config that fits HBM turns it on anyway
    (VERDICT r4 weak #3)."""
    from fleetx_tpu.parallel.auto_layout import offload_is_needed

    gpt345m = dict(hidden_size=1024, num_layers=24, num_attention_heads=16,
                   ffn_hidden_size=4096, vocab_size=50304,
                   max_position_embeddings=1024)
    gpt67b = dict(hidden_size=4096, num_layers=32, num_attention_heads=32,
                  ffn_hidden_size=16384, vocab_size=50304,
                  max_position_embeddings=1024)
    # 345M fits a 16G chip easily -> offload unjustified
    assert not offload_is_needed(gpt345m, {}, micro_batch=8,
                                 recompute="dots")
    # 6.7B unsharded (120GB fixed state) cannot fit -> offload justified
    assert offload_is_needed(gpt67b, {}, micro_batch=1, recompute="full")
    # ...but 16-way ZeRO-3 brings it back under budget (stage 3 shards the
    # weights too; at stage 2 they stay replicated and offload can't help)
    assert not offload_is_needed(
        gpt67b, {"fsdp_degree": 16, "sharding": {"sharding_stage": 3}},
        micro_batch=1, recompute="full", hbm_gb=32.0)
    assert offload_is_needed(
        gpt67b, {"fsdp_degree": 16, "sharding": {"sharding_stage": 2}},
        micro_batch=1, recompute="full", hbm_gb=32.0)

    # engine-side warning on the unjustified config (the CPU backend then
    # also disables the feature, warning separately — both must fire).
    # the fleetx logger does not propagate, so hook caplog's handler on
    from fleetx_tpu.utils.log import logger as fx_logger

    cfg = tiny_cfg()
    cfg["Distributed"] = {"dp_degree": 1,
                          "sharding": {"sharding_stage": 1,
                                       "sharding_offload": True}}
    mesh = build_mesh(cfg["Distributed"], devices=jax.devices()[:1])
    fx_logger.addHandler(caplog.handler)
    try:
        build_engine(cfg, mesh)
    finally:
        fx_logger.removeHandler(caplog.handler)
    text = " ".join(r.message for r in caplog.records)
    assert "fits HBM without it" in text, text
    assert "requires a TPU backend" in text, text
