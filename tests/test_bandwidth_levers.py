"""Backward-bandwidth levers (docs/bandwidth_levers.md): bf16 remat
residuals, scan-unroll wiring, and device-side input double buffering.

The levers aim at the writes of scan-stacked remat residuals in the
backward layer scan; none has an A/B line in the ledger (ROADMAP S10).
These tests pin the *semantics* on the CPU mesh — loss parity within
tolerance, residual dtypes, config plumbing, and prefetch
ordering/sharding/shutdown — so an on-chip pair only has to measure.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fleetx_tpu.data.prefetch import DevicePrefetcher
from fleetx_tpu.models.gpt.model import (GPTConfig, GPTForPretraining,
                                         RESIDUAL_NAMES, config_from_dict,
                                         cross_entropy_loss)

VOCAB, SEQ, BATCH = 128, 32, 4


def tiny_model(**overrides):
    kw = dict(vocab_size=VOCAB, hidden_size=64, num_layers=2,
              num_attention_heads=4, max_position_embeddings=SEQ,
              hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
              use_flash_attention=False, dtype=jnp.float32,
              param_dtype=jnp.float32, use_recompute=True,
              recompute_granularity="dots")
    kw.update(overrides)
    return GPTForPretraining(GPTConfig(**kw))


def loss_and_gradnorm(model, seed=0):
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
    gnorm = sum(float(jnp.sum(jnp.square(g)))
                for g in jax.tree.leaves(grads)) ** 0.5
    return float(loss), gnorm, loss_fn, params


# ------------------------------------------------------ bf16 remat residuals


@pytest.mark.parametrize("granularity", ["dots", "full"])
def test_bf16_residual_loss_parity(granularity):
    """remat_save_dtype=bfloat16 must stay within a small, bounded drift of
    the f32-residual baseline — the cast quantises the forward intermediates
    (saved and recomputed values must agree across the remat boundary), so
    exact equality is not expected, divergence is a bug."""
    l32, g32, _, _ = loss_and_gradnorm(
        tiny_model(recompute_granularity=granularity))
    l16, g16, _, _ = loss_and_gradnorm(
        tiny_model(recompute_granularity=granularity,
                   remat_save_dtype=jnp.bfloat16))
    assert np.isfinite(l16) and np.isfinite(g16)
    # measured drift ~3e-5 on a loss of ~4.87; bound with margin
    assert abs(l32 - l16) < 5e-3, (l32, l16)
    np.testing.assert_allclose(g16, g32, rtol=5e-2)


def test_bf16_residuals_are_saved_in_bf16():
    """The policy must save the named CAST values (bf16), not the f32 dot
    outputs — the whole point of the bandwidth diet."""
    try:
        from jax._src.ad_checkpoint import saved_residuals
    except ImportError:
        saved_residuals = None

    _, _, loss16, params16 = loss_and_gradnorm(
        tiny_model(remat_save_dtype=jnp.bfloat16))
    _, _, loss32, params32 = loss_and_gradnorm(tiny_model())

    # the named casts are present in the grad program at all
    jaxpr = str(jax.make_jaxpr(jax.grad(loss16))(params16))
    for name in RESIDUAL_NAMES:
        assert name in jaxpr, f"named cast {name} missing from the program"

    if saved_residuals is None:  # private API moved — the jaxpr check stands
        return
    res16 = saved_residuals(loss16, params16)
    res32 = saved_residuals(loss32, params32)
    n_bf16 = sum(1 for aval, _ in res16 if aval.dtype == jnp.bfloat16)
    assert n_bf16 >= 3, f"expected bf16 saved residuals, got {n_bf16}"
    assert not any(aval.dtype == jnp.bfloat16 for aval, _ in res32), \
        "f32 baseline unexpectedly saves bf16 residuals"
    # the diet shrinks total saved bytes (f32 stacks became bf16 stacks)
    bytes_of = lambda res: sum(  # noqa: E731 - local helper
        int(np.prod(a.shape)) * a.dtype.itemsize for a, _ in res)
    assert bytes_of(res16) < bytes_of(res32)


# ------------------------------------------------------- scan-unroll wiring


def test_scan_unroll_is_numerically_inert():
    """unroll>1 re-schedules the scan body; values must not change."""
    l1, g1, _, _ = loss_and_gradnorm(tiny_model(scan_unroll=1))
    l2, g2, _, _ = loss_and_gradnorm(tiny_model(scan_unroll=2))
    np.testing.assert_allclose(l2, l1, rtol=1e-6)
    np.testing.assert_allclose(g2, g1, rtol=1e-5)


def test_yaml_roundtrip_for_new_knobs(tmp_path):
    """Model.scan_unroll / Model.remat_save_dtype / Engine.prefetch_to_device
    flow YAML → get_config → GPTConfig (keeps FX006's both-direction
    dead-key check green: every key is consumed by real code)."""
    from fleetx_tpu.core.module import GPTModule
    from fleetx_tpu.utils.config import get_config

    cfg_file = tmp_path / "cfg.yaml"
    cfg_file.write_text(
        "Global:\n  local_batch_size: 4\n"
        "Engine:\n  prefetch_to_device: 1\n"
        "Model:\n"
        "  vocab_size: 128\n  hidden_size: 64\n  num_layers: 2\n"
        "  num_attention_heads: 4\n  max_position_embeddings: 32\n"
        "  scan_unroll: 4\n  remat_save_dtype: bfloat16\n"
        "  use_recompute: true\n  recompute_granularity: dots\n")
    cfg = get_config(str(cfg_file), num_devices=1)
    assert int(cfg["Engine"]["prefetch_to_device"]) == 1
    model_cfg = GPTModule(cfg).model_cfg
    assert model_cfg.scan_unroll == 4
    assert model_cfg.remat_save_dtype == jnp.bfloat16


def test_config_zoo_base_carries_the_knobs():
    """The shipped base recipe wires all three levers explicitly."""
    import os

    from fleetx_tpu.utils.config import get_config

    base = os.path.join(os.path.dirname(__file__), "..", "fleetx_tpu",
                        "configs", "nlp", "gpt",
                        "pretrain_gpt_345M_single_card.yaml")
    cfg = get_config(base, num_devices=1)
    assert "scan_unroll" in cfg["Model"]
    assert "remat_save_dtype" in cfg["Model"]
    assert int(cfg["Engine"]["prefetch_to_device"]) >= 0
    # the empty-YAML remat_save_dtype leaf must parse as "unset"
    assert config_from_dict(dict(cfg["Model"])).remat_save_dtype is None


# ------------------------------------------- device-side double buffering


def _mesh_shard_fn(devices):
    from fleetx_tpu.core.engine.eager_engine import batch_sharding
    from fleetx_tpu.parallel.mesh import build_mesh

    mesh = build_mesh({"dp_degree": len(devices)}, devices=devices)
    bs = batch_sharding(mesh)
    return bs, lambda b: jax.tree.map(
        lambda x: jax.device_put(np.asarray(x), bs), b)


def _prefetch_threads():
    return [t for t in threading.enumerate()
            if t.name == "fleetx-device-prefetch" and t.is_alive()]


def test_prefetcher_preserves_order_and_sharding(devices8):
    bs, shard_fn = _mesh_shard_fn(devices8)
    batches = [{"x": np.full((8, 4), i, np.int32)} for i in range(6)]
    pf = DevicePrefetcher(iter(batches), shard_fn, depth=2)
    out = list(pf)
    assert [int(b["x"][0, 0]) for b in out] == list(range(6))
    for b in out:
        assert b["x"].sharding.is_equivalent_to(bs, ndim=2)
    # exhausted iterator keeps raising StopIteration (no hang, no restart)
    with pytest.raises(StopIteration):
        next(pf)


def test_prefetcher_propagates_producer_exception(devices8):
    _, shard_fn = _mesh_shard_fn(devices8)

    def gen():
        yield {"x": np.zeros((8, 4), np.int32)}
        raise RuntimeError("loader blew up")

    pf = DevicePrefetcher(gen(), shard_fn, depth=1)
    next(pf)
    with pytest.raises(RuntimeError, match="loader blew up"):
        next(pf)


def test_prefetcher_close_releases_producer(devices8):
    _, shard_fn = _mesh_shard_fn(devices8)

    def endless():
        i = 0
        while True:
            yield {"x": np.full((8, 4), i, np.int32)}
            i += 1

    pf = DevicePrefetcher(endless(), shard_fn, depth=1)
    next(pf)
    assert _prefetch_threads()
    pf.close()
    deadline = time.monotonic() + 5.0
    while _prefetch_threads() and time.monotonic() < deadline:
        time.sleep(0.05)
    assert not _prefetch_threads(), "producer thread leaked after close()"


def test_all_three_levers_on_cpu_mesh_loss_parity(devices8):
    """Acceptance criterion: remat_save_dtype=bfloat16 + scan_unroll +
    device prefetch together keep loss parity with the f32/serial baseline
    on the CPU mesh, within the bf16-residual drift bound."""
    from tests.test_engine import build_engine, make_batches, tiny_cfg
    from fleetx_tpu.parallel.mesh import build_mesh

    mesh = build_mesh({}, devices=devices8[:1])
    base = tiny_cfg(use_recompute=True, recompute_granularity="dots")
    ref_engine = build_engine(base, mesh)
    ref_engine.max_steps = 3
    ref = ref_engine.fit(make_batches(3))

    lev = tiny_cfg(use_recompute=True, recompute_granularity="dots",
                   remat_save_dtype="bfloat16", scan_unroll=2)
    lev["Engine"]["prefetch_to_device"] = 2
    lev_engine = build_engine(lev, mesh)
    assert lev_engine.prefetch_to_device == 2
    lev_engine.max_steps = 3
    got = lev_engine.fit(make_batches(3))

    assert len(got) == len(ref) == 3
    np.testing.assert_allclose(got, ref, rtol=5e-3, atol=5e-3)
    assert not _prefetch_threads()


def test_prefetch_does_not_advance_epoch_ahead_of_consumption(devices8):
    """The producer thread runs the batch generator up to `depth` batches
    ahead; the CONSUMER owns self._epoch, so logged epochs and checkpoint
    meta must match the serial run exactly (review finding: a mid-window
    save used to persist an epoch the loop had not reached)."""
    from tests.test_engine import build_engine, make_batches, tiny_cfg
    from fleetx_tpu.parallel.mesh import build_mesh

    mesh = build_mesh({}, devices=devices8[:1])

    def run(prefetch):
        cfg = tiny_cfg()
        cfg["Engine"].update(run_mode="epoch", max_steps=1000,
                             prefetch_to_device=prefetch)
        eng = build_engine(cfg, mesh)
        eng.max_steps = 1000
        seen = []
        orig = eng.module.training_step_end
        eng.module.training_step_end = lambda log: (
            seen.append(log["epoch"]), orig(log))[-1]
        eng.fit(make_batches(3, seed=11), epoch_num=2)
        return seen, eng._epoch

    serial_epochs, serial_final = run(0)
    prefetch_epochs, prefetch_final = run(2)
    assert prefetch_epochs == serial_epochs == [0] * 3 + [1] * 3
    assert prefetch_final == serial_final == 2
