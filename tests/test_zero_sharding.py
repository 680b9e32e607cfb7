"""ZeRO-2 gradient sharding + single-pass fused update (docs/zero_sharding.md).

Pins the stage-2 semantics VERDICT r5 #4 called missing: gradients (and the
grad-accumulation scan carry) carry an ``fsdp``-sharded spec inside the
jitted step at stage 2 while stage 1 leaves them replicated; loss parity
stage 0 vs stage 2 holds with and without accumulation; and the step runs
exactly ONE global-norm reduction shared by the ``grad_norm`` metric and
the clip (fused or threaded).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from fleetx_tpu.core.engine import EagerEngine
from fleetx_tpu.core.module import GPTModule
from fleetx_tpu.optims.lr_scheduler import build_lr_scheduler
from fleetx_tpu.optims.optimizer import adamw, build_optimizer
from fleetx_tpu.parallel.mesh import build_mesh
from fleetx_tpu.parallel.sharding import zero_grad_specs

pytestmark = pytest.mark.zero

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
        out.append({
            "tokens": rng.randint(0, VOCAB, size=(batch, SEQ)).astype(np.int32),
            "position_ids": np.broadcast_to(np.arange(SEQ, dtype=np.int32),
                                            (batch, SEQ)).copy(),
            "labels": rng.randint(0, VOCAB, size=(batch, SEQ)).astype(np.int32),
            "loss_mask": np.ones((batch, SEQ), np.float32),
        })
    return out


def build_engine(cfg, mesh, fused_clip=False):
    module = GPTModule(cfg)
    lr = build_lr_scheduler({"name": "cosine", "max_lr": 1e-3, "min_lr": 1e-4,
                             "warmup_steps": 2, "decay_steps": 100})
    opt = build_optimizer({"name": "AdamW", "weight_decay": 0.01,
                           "grad_clip": {"clip_norm": 1.0,
                                         "fused": fused_clip}}, lr)
    return EagerEngine(cfg, module, optimizer=opt, lr_schedule=lr, mesh=mesh)


def run_losses(cfg, mesh, n_steps, seed=0, fused_clip=False):
    eng = build_engine(cfg, mesh, fused_clip=fused_clip)
    eng.max_steps = n_steps
    return eng.fit(make_batches(n_steps, seed=seed))


def stage_cfg(stage, accum=1, **model_overrides):
    cfg = tiny_cfg(**model_overrides)
    cfg["Distributed"] = {"fsdp_degree": 4, "dp_degree": 2,
                          "sharding": {"sharding_stage": stage}}
    if accum > 1:
        cfg["Engine"]["accumulate_steps"] = accum
    return cfg


def spec_axes(spec):
    axes = set()
    for entry in spec:
        for a in (entry if isinstance(entry, (tuple, list)) else (entry,)):
            if a is not None:
                axes.add(a)
    return axes


def constraint_specs(jaxpr, depth=0):
    """(depth, spec_str) of every sharding_constraint eqn, recursing into
    sub-jaxprs (scan/cond bodies) — the on-trace truth of where the grad
    constraints landed."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sharding_constraint":
            out.append((depth, str(eqn.params.get("sharding"))))
        for v in eqn.params.values():
            items = v if isinstance(v, (list, tuple)) else (v,)
            for item in items:
                sub = getattr(item, "jaxpr", None)
                if sub is not None:
                    out.extend(constraint_specs(sub, depth + 1))
    return out


# ---------------------------------------------------------------- helper unit

def test_zero_grad_specs_helper(devices8):
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = build_mesh({"fsdp_degree": 4, "dp_degree": 2}, devices=devices8)
    tree = {
        "w": jax.ShapeDtypeStruct((128, 64), jnp.float32),
        "scalar": jax.ShapeDtypeStruct((), jnp.float32),
        "tiny": jax.ShapeDtypeStruct((2,), jnp.float32),
        "tp": jax.ShapeDtypeStruct((8, 64), jnp.float32),
    }
    existing = {
        "w": NamedSharding(mesh, P()),
        "scalar": NamedSharding(mesh, P()),
        "tiny": NamedSharding(mesh, P()),
        # tensor-parallel leaf: dim0 taken — fsdp must land on a FREE dim
        "tp": NamedSharding(mesh, P("tensor")),
    }
    specs = zero_grad_specs(tree, mesh, param_shardings=existing)
    assert specs["w"].spec == P("fsdp")
    assert specs["scalar"].spec == P()          # nothing to shard
    assert specs["tiny"].spec == P()            # 2 % 4 != 0 — replicated
    assert specs["tp"].spec == P("tensor", "fsdp")  # keeps the tp dim

    # a 1-sized fsdp axis degenerates to the existing specs
    mesh1 = build_mesh({}, devices=devices8[:1])
    specs1 = zero_grad_specs(tree, mesh1)
    assert all(spec_axes(s.spec) == set() for s in jax.tree.leaves(specs1))


# ------------------------------------------------- on-mesh stage-2 semantics

def test_stage2_constrains_grads_and_scan_carry(devices8):
    """Stage 2: the grad pytree AND the accumulation scan carry carry
    fsdp-sharded specs inside the jitted train_step (the per-microbatch
    placement that lets the reduce-scatter overlap the next microbatch's
    backward); stage 1 leaves them unconstrained."""
    cfg = stage_cfg(2, accum=2)
    mesh = build_mesh(cfg["Distributed"], devices=devices8)
    eng = build_engine(cfg, mesh)
    b = make_batches(1)[0]
    eng.prepare(b)
    assert eng._grad_shardings is not None
    grad_axes = [spec_axes(s.spec)
                 for s in jax.tree.leaves(eng._grad_shardings)]
    assert any("fsdp" in a for a in grad_axes), grad_axes

    traced = eng._train_step.trace(eng.state, eng.shard_batch(b))
    cons = constraint_specs(traced.jaxpr.jaxpr)
    fsdp_cons = [c for c in cons if "fsdp" in c[1]]
    assert fsdp_cons, "no fsdp sharding constraints in the traced step"
    depths = {d for d, _ in fsdp_cons}
    # depth 0: the first microbatch's grads + the post-scan tree;
    # depth >= 1: the per-microbatch grads and carry INSIDE the scan body
    assert 0 in depths and any(d >= 1 for d in depths), depths

    # stage 1 (same mesh shape): optimizer state sharded, grads untouched
    cfg1 = stage_cfg(1, accum=2)
    mesh1 = build_mesh(cfg1["Distributed"], devices=devices8)
    eng1 = build_engine(cfg1, mesh1)
    eng1.prepare(b)
    assert eng1._grad_shardings is None
    traced1 = eng1._train_step.trace(eng1.state, eng1.shard_batch(b))
    cons1 = constraint_specs(traced1.jaxpr.jaxpr)
    assert not [c for c in cons1 if "fsdp" in c[1]], cons1


def test_stage2_loss_parity_no_accum(devices8):
    cfg = tiny_cfg()
    mesh1 = build_mesh({}, devices=devices8[:1])
    ref = run_losses(cfg, mesh1, 4)
    cfg2 = stage_cfg(2)
    mesh8 = build_mesh(cfg2["Distributed"], devices=devices8)
    got = run_losses(cfg2, mesh8, 4)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_stage2_loss_parity_with_accum(devices8):
    cfg = tiny_cfg()
    cfg["Engine"]["accumulate_steps"] = 4
    mesh1 = build_mesh({}, devices=devices8[:1])
    ref = run_losses(cfg, mesh1, 3)
    cfg2 = stage_cfg(2, accum=4)
    mesh8 = build_mesh(cfg2["Distributed"], devices=devices8)
    got = run_losses(cfg2, mesh8, 3)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_grad_accum_dtype_bf16_drift_bounded(devices8):
    """bf16 accumulation carry: halves the live accumulator bytes; loss
    drift vs the fp32 carry stays within the same envelope PR 3 allowed
    its bf16 remat residuals."""
    mesh = build_mesh({}, devices=devices8[:1])
    cfg32 = tiny_cfg()
    cfg32["Engine"]["accumulate_steps"] = 4
    ref = run_losses(cfg32, mesh, 3)
    cfg16 = tiny_cfg(grad_accum_dtype="bfloat16")
    cfg16["Engine"]["accumulate_steps"] = 4
    got = run_losses(cfg16, mesh, 3)
    np.testing.assert_allclose(got, ref, rtol=5e-3, atol=5e-3)
    # the knob actually landed on the config
    assert GPTModule(cfg16).model_cfg.grad_accum_dtype == jnp.bfloat16
    # "native" spells the legacy accumulate-in-grad-dtype mode (a null YAML
    # leaf is filtered before the dataclass, so it could not mean this)
    assert GPTModule(
        tiny_cfg(grad_accum_dtype="native")).model_cfg.grad_accum_dtype is None


# ------------------------------------------------------- single-pass norm

def _count_norm_reductions(monkeypatch, eng, batch):
    """Trace the jitted train_step with optax.global_norm wrapped by a
    counter — every norm reduction the step would compile is one call at
    trace time."""
    calls = []
    orig = optax.global_norm

    def counting(tree):
        calls.append(1)
        return orig(tree)

    import optax._src.linear_algebra as la

    monkeypatch.setattr(optax, "global_norm", counting)
    monkeypatch.setattr(la, "global_norm", counting)
    eng._build_step_fns()  # rebuild closures over the patched optax
    eng._train_step.trace(eng.state, eng.shard_batch(batch))
    return sum(calls)


def test_exactly_one_global_norm_threaded(devices8, monkeypatch):
    """Default (non-fused) path: the engine computes the norm once and
    threads it into the chain's clip as an optax extra arg — the old
    duplicate (train_step's metric + clip_by_global_norm's recompute) is
    gone."""
    cfg = tiny_cfg()
    mesh = build_mesh({}, devices=devices8[:1])
    eng = build_engine(cfg, mesh)
    b = make_batches(1)[0]
    eng.prepare(b)
    assert _count_norm_reductions(monkeypatch, eng, b) == 1


def test_exactly_one_global_norm_fused(devices8, monkeypatch):
    """fused_clip: the optimizer owns the single norm and returns it."""
    cfg = tiny_cfg()
    mesh = build_mesh({}, devices=devices8[:1])
    eng = build_engine(cfg, mesh, fused_clip=True)
    b = make_batches(1)[0]
    eng.prepare(b)
    assert getattr(eng.optimizer, "fused_clip", False)
    assert _count_norm_reductions(monkeypatch, eng, b) == 1


def test_fused_clip_matches_unfused():
    """adamw(fused_clip=True) produces the identical updates/opt-state and
    returns the same norm the unfused chain would have clipped with."""
    params = {"w": jnp.array([[3.0, -4.0]]), "b": jnp.array([12.0])}
    grads = jax.tree.map(lambda p: p * 2.0, params)  # norm 26
    plain = adamw(1e-2, grad_clip=1.0)
    fused = adamw(1e-2, grad_clip=1.0, fused_clip=True)
    s0p, s0f = plain.init(params), fused.init(params)
    up, sp = plain.update(grads, s0p, params)
    uf, sf, norm = fused.update(grads, s0f, params)
    jax.tree.map(np.testing.assert_allclose, up, uf)
    jax.tree.map(np.testing.assert_allclose, sp, sf)
    np.testing.assert_allclose(norm, optax.global_norm(grads), rtol=1e-6)


def test_fused_training_parity(devices8):
    """End-to-end: fused_clip on/off trains the identical loss curve."""
    mesh = build_mesh({}, devices=devices8[:1])
    ref = run_losses(tiny_cfg(), mesh, 3)
    got = run_losses(tiny_cfg(), mesh, 3, fused_clip=True)
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)


def test_clip_by_precomputed_norm_matches_optax():
    """Standalone (no extra arg) and threaded use both reproduce stock
    optax.clip_by_global_norm — including the above-threshold scaling."""
    from fleetx_tpu.optims.optimizer import clip_by_precomputed_norm

    updates = {"w": jnp.array([3.0, -4.0]) * 10}  # norm 50
    stock = optax.clip_by_global_norm(1.0)
    mine = clip_by_precomputed_norm(1.0)
    u_ref, _ = stock.update(updates, stock.init(updates))
    u_standalone, _ = mine.update(updates, mine.init(updates))
    u_threaded, _ = mine.update(updates, mine.init(updates),
                                grad_norm=optax.global_norm(updates))
    jax.tree.map(np.testing.assert_allclose, u_standalone, u_ref)
    jax.tree.map(np.testing.assert_allclose, u_threaded, u_ref)


# ------------------------------------------- microbatch-cap semantics (w#5)

def test_accum_indivisible_batch_raises(devices8):
    """A real training batch that does not divide accumulate_steps is a
    config error — the step must raise a clear ValueError instead of
    training a different schedule than configured."""
    cfg = tiny_cfg()
    cfg["Engine"]["accumulate_steps"] = 3
    mesh = build_mesh({}, devices=devices8[:1])
    eng = build_engine(cfg, mesh)
    eng.max_steps = 1
    with pytest.raises(ValueError, match="not divisible by accumulate_steps"):
        eng.fit(make_batches(1))  # batch 8 % accum 3


def test_effective_microbatches_cap_logs(caplog):
    """Proxy-batch capping still works but is LOUD; an uncapped call stays
    silent."""
    from fleetx_tpu.parallel.pipeline import effective_microbatches
    from fleetx_tpu.utils.log import logger as fx_logger

    fx_logger.addHandler(caplog.handler)
    try:
        assert effective_microbatches(8, 2) == 2  # proxy batch: cap + warn
        text = " ".join(r.message for r in caplog.records)
        assert "caps pp_microbatches" in text, text
        caplog.clear()
        assert effective_microbatches(4, 8) == 4  # real batch: no cap
        assert effective_microbatches(4, 16) == 4
        assert not caplog.records
    finally:
        fx_logger.removeHandler(caplog.handler)


# ------------------------------------------------- memory model / planner

def test_auto_layout_stage2_grad_term():
    """The stage-2 grad-bytes term makes stage 2 memory-distinct from
    stage 1 (VERDICT r5 #4): at GPT-1.3B / fsdp8 / 16G the offload
    boundary moves past the stage-2 config while stage 1 still needs it."""
    from fleetx_tpu.parallel.auto_layout import (estimate_memory_terms,
                                                 offload_is_needed)

    gpt13b = dict(hidden_size=2048, num_layers=24, num_attention_heads=16,
                  ffn_hidden_size=8192, vocab_size=50304,
                  max_position_embeddings=1024)
    terms = estimate_memory_terms(gpt13b, micro_batch=4, recompute="full")
    assert set(terms) == {"moments", "grads", "weights", "act"}
    # the grad buffer is the f32 4 bytes/param stage 2 shards
    assert terms["grads"] == pytest.approx(terms["moments"] / 2.0)

    deg = {"fsdp_degree": 8}
    assert offload_is_needed(
        gpt13b, {**deg, "sharding": {"sharding_stage": 1}},
        micro_batch=4, recompute="full", hbm_gb=16.0)
    assert not offload_is_needed(
        gpt13b, {**deg, "sharding": {"sharding_stage": 2}},
        micro_batch=4, recompute="full", hbm_gb=16.0)

    # bf16 accumulation carry halves the grad term
    bf16 = dict(gpt13b, grad_accum_dtype="bfloat16")
    terms16 = estimate_memory_terms(bf16, micro_batch=4, recompute="full")
    assert terms16["grads"] == pytest.approx(terms["grads"] / 2.0)


# --------------------------------------------------- config plumbing

def test_yaml_roundtrip_for_zero_knobs(tmp_path):
    """Model.grad_accum_dtype / Optimizer.grad_clip.fused flow
    YAML → get_config → GPTConfig / build_optimizer (keeps FX006's
    both-direction dead-key check green)."""
    from fleetx_tpu.utils.config import get_config

    cfg_file = tmp_path / "cfg.yaml"
    cfg_file.write_text(
        "Global:\n  local_batch_size: 4\n"
        "Model:\n"
        "  vocab_size: 128\n  hidden_size: 64\n  num_layers: 2\n"
        "  num_attention_heads: 4\n  max_position_embeddings: 32\n"
        "  grad_accum_dtype: bfloat16\n"
        "Optimizer:\n"
        "  name: AdamW\n"
        "  grad_clip:\n    clip_norm: 1.0\n    fused: true\n")
    cfg = get_config(str(cfg_file), num_devices=1)
    assert GPTModule(cfg).model_cfg.grad_accum_dtype == jnp.bfloat16
    opt = build_optimizer(dict(cfg["Optimizer"]), 1e-3)
    assert getattr(opt, "fused_clip", False)

    # the shipped base recipe carries both knobs with safe defaults
    import os

    base = os.path.join(os.path.dirname(__file__), "..", "fleetx_tpu",
                        "configs", "nlp", "gpt", "pretrain_gpt_base.yaml")
    base_cfg = get_config(base, num_devices=1)
    assert str(base_cfg["Model"]["grad_accum_dtype"]) == "float32"
    assert base_cfg["Optimizer"]["grad_clip"]["fused"] is False
