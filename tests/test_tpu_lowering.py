"""Every program the chip runs lowers for the TPU — checked from the CPU.

Tier-1 runs the Pallas kernels in interpret mode, which emits plain HLO
that GSPMD partitions freely, so a kernel (or a mesh wrapper around one)
that JAX refuses to lower for a TPU passes every other test. Here the ONE
interpret switch (``fleetx_tpu.ops.interpret``) is forced off and the
train step — on one device and under each four-device layout — and the
serving decode program are cross-lowered for ``lowering_platforms=("tpu",)``
on the virtual CPU mesh. That runs JAX's own Pallas→Mosaic lowering and
its rule that a Mosaic call under a mesh sits in a ``shard_map`` manual
over every axis; the expected kernels must then be in the text by name.
It is not the Mosaic compiler: what only the chip (or an ahead-of-time
compile for its topology) can refuse — VMEM, tiling — is ``chip_smoke.py``.
"""

import jax
import numpy as np
import pytest

from fleetx_tpu import ops
from fleetx_tpu.core.engine import EagerEngine
from fleetx_tpu.core.module import GPTModule
from fleetx_tpu.optims.lr_scheduler import build_lr_scheduler
from fleetx_tpu.optims.optimizer import build_optimizer
from fleetx_tpu.parallel.mesh import build_mesh
from fleetx_tpu.utils.env import mosaic_kernels

# kernel-admitted shapes: lane-aligned hidden, 64-wide heads, and a sequence
# whose per-device block is one 128-row flash tile
VOCAB, HIDDEN, HEADS, LAYERS = 512, 128, 2, 2

FLASH = {"flash_fwd", "flash_bwd_fused"}
RING = {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
NORM = {"fused_norm_fwd", "fused_norm_bwd"}

TRAIN_CASES = {
    "one_device": (1, {}, {}, 128, FLASH | NORM),
    "dp2_mp2_sp": (4, {"dp_degree": 2, "mp_degree": 2,
                       "sequence_parallel": True},
                   {"sequence_parallel": True}, 128, FLASH | NORM),
    "fsdp4_stage2": (4, {"fsdp_degree": 4, "sharding": {
        "sharding_stage": 2, "sharding_degree": 4}}, {}, 128, FLASH | NORM),
    "dp2_seq2_ring": (4, {"dp_degree": 2, "seq_degree": 2},
                      {"use_ring_attention": True,
                       "attention_probs_dropout_prob": 0.0}, 256,
                      RING | NORM),
}


def _lower_for_tpu(monkeypatch, jitted, *args) -> dict:
    """Cross-lower ``jitted`` for the TPU with interpret forced off; the
    Mosaic kernels in the program, by name."""
    monkeypatch.setattr(ops, "interpret", lambda: False)
    lowered = jitted.trace(*args).lower(lowering_platforms=("tpu",))
    return mosaic_kernels(lowered.as_text())


@pytest.mark.parametrize("case", list(TRAIN_CASES))
def test_train_step_lowers_for_tpu(devices8, monkeypatch, case):
    n_dev, dist, model_over, seq, want = TRAIN_CASES[case]
    model = dict(vocab_size=VOCAB, hidden_size=HIDDEN, num_layers=LAYERS,
                 num_attention_heads=HEADS, max_position_embeddings=seq,
                 use_recompute=True, recompute_granularity="dots")
    model.update(model_over)
    cfg = {"Model": model, "Distributed": dist, "Global": {"seed": 7},
           "Engine": {"max_steps": 1}}
    mesh = build_mesh(dist, devices=devices8[:n_dev])
    lr = build_lr_scheduler({"max_lr": 1e-3, "warmup_steps": 2,
                             "decay_steps": 100})
    engine = EagerEngine(cfg, GPTModule(cfg), lr_schedule=lr, mesh=mesh,
                         optimizer=build_optimizer({"name": "AdamW"}, lr))
    batch_size = 2 * n_dev
    tokens = np.zeros((batch_size, seq), np.int32)
    batch = {"tokens": tokens, "position_ids": tokens, "labels": tokens,
             "loss_mask": np.ones((batch_size, seq), np.float32)}
    engine.prepare(batch)
    with engine._ctx():
        found = _lower_for_tpu(monkeypatch, engine._train_step, engine.state,
                               engine.shard_batch(batch))
    assert set(found) == want, found


@pytest.mark.parametrize("dist", [None, {"fsdp_degree": 2, "mp_degree": 2}],
                         ids=["one_device", "fsdp2_mp2"])
def test_serving_decode_lowers_for_tpu(devices8, monkeypatch, dist):
    import jax.numpy as jnp

    from fleetx_tpu.models.gpt.model import (GPTForPretraining,
                                             config_from_dict)
    from fleetx_tpu.serving.engine import ServingConfig, ServingEngine

    model_cfg = config_from_dict(dict(
        vocab_size=VOCAB, hidden_size=HIDDEN, num_layers=LAYERS,
        num_attention_heads=HEADS, max_position_embeddings=64))
    params = GPTForPretraining(model_cfg).init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32),
        None, deterministic=True)["params"]
    mesh = build_mesh(dist, devices=devices8[:4]) if dist else None
    engine = ServingEngine(model_cfg, params, ServingConfig(
        max_batch=4, page_size=16, num_pages=34, max_seq_len=64,
        prefill_chunk=16), mesh=mesh)
    assert engine.paged_kernel_active
    found = _lower_for_tpu(
        monkeypatch, engine._fns["decode"], engine.params, engine.pool_k,
        engine.pool_v, engine._last_tokens, engine._block_tables,
        engine._lens, engine._next_rng())
    assert set(found) == {"paged_decode"}, found
