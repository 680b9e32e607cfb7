"""Parameter-efficient fine-tuning: LoRA adapters end to end.

Covers the ISSUE-15 acceptance surface (docs/finetune.md):

- adapter algebra units: injection shapes/boxing, exact ``B@A`` fold,
  merged == base at init (B zeros), the shared trainability mask and the
  masked optimizer freezing every non-adapter leaf;
- THE end-to-end recipe on the CPU mesh: pretrain checkpoint → LoRA
  fine-tune (loss strictly decreasing, base pytree bitwise frozen — the
  per-leaf digest audit — with only adapter leaves changing) → adapter-
  only artifact (<5% of base payload bytes, manifest-verified) → merged
  serving decode token-identical to unmerged base+adapter reference
  generation, int8 decode within the established drift bound;
- drift refusal: a drifted base or registry fingerprint refuses with a
  NAMED error, corrupt adapter bytes refuse on digests, never a silent
  merge;
- consumer integration: the engine resolves ``gpt_lora`` shardings
  through the registry, ``tools/serve.py``'s builder merges the adapter
  artifact, and the shipped finetune recipe parses + audits clean.

File sorts zz-last per the tier-1 gate convention (ROADMAP.md).
"""

import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from flax.core import meta

from fleetx_tpu.core import checkpoint as ckpt_lib
from fleetx_tpu.core.engine import EagerEngine
from fleetx_tpu.core.module import GPTModule
from fleetx_tpu.finetune import checkpoint as ft_ckpt
from fleetx_tpu.finetune import lora
from fleetx_tpu.finetune import recipe as ft_recipe
from fleetx_tpu.finetune.checkpoint import AdapterDriftError
from fleetx_tpu.finetune.module import LoRAGPTModule
from fleetx_tpu.models.gpt import generation as G
from fleetx_tpu.models.gpt.model import GPTForPretraining, config_from_dict
from fleetx_tpu.optims.lr_scheduler import build_lr_scheduler
from fleetx_tpu.optims.optimizer import build_optimizer
from fleetx_tpu.parallel import rules as R
from fleetx_tpu.parallel import shardcheck as SC
from fleetx_tpu.resilience.integrity import CheckpointIntegrityError
from fleetx_tpu.serving import ServingConfig, ServingEngine

pytestmark = pytest.mark.finetune

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = dict(vocab_size=128, hidden_size=64, num_layers=2,
            num_attention_heads=4, max_position_embeddings=32,
            use_flash_attention=False, hidden_dropout_prob=0.0,
            attention_probs_dropout_prob=0.0, dtype="float32",
            param_dtype="float32")
EOS = 96
RANK, ALPHA = 4, 8.0


def _batch(rng, bs=8, s=32):
    toks = rng.randint(0, 127, size=(bs, s + 1)).astype(np.int32)
    return {"tokens": toks[:, :-1],
            "position_ids": np.broadcast_to(
                np.arange(s, dtype=np.int32), (bs, s)).copy(),
            "labels": toks[:, 1:],
            "loss_mask": np.ones((bs, s), np.float32)}


def _engine(cfg, module, max_lr):
    lr = build_lr_scheduler({"max_lr": max_lr, "warmup_steps": 0,
                             "decay_steps": 100})
    opt = build_optimizer({"name": "AdamW"}, lr)
    if isinstance(module, LoRAGPTModule):
        opt = lora.lora_optimizer(opt)
    return EagerEngine(cfg, module, optimizer=opt, lr_schedule=lr)


@pytest.fixture(scope="module")
def pipeline(tmp_path_factory):
    """ONE pretrain → fine-tune → adapter run shared by the suite."""
    tmp = tmp_path_factory.mktemp("lora")
    base_dir = str(tmp / "base")
    ad_dir = str(tmp / "adapter")
    rng = np.random.RandomState(0)

    cfg = {"Model": dict(TINY),
           "Engine": {"max_steps": 3, "logging_freq": 1,
                      "save_load": {"output_dir": base_dir}},
           "Global": {"seed": 7}}
    eng = _engine(cfg, GPTModule(cfg), 1e-3)
    pre_batch = _batch(rng)
    pre_losses = eng.fit(iter([pre_batch] * 3))
    eng.save()

    cfg2 = {"Model": dict(TINY, module="LoRAGPTModule"),
            "FineTune": {"base_ckpt": base_dir, "adapter_dir": ad_dir,
                         "lora": {"rank": RANK, "alpha": ALPHA}},
            "Engine": {"max_steps": 4, "logging_freq": 1,
                       "save_load": {"output_dir": str(tmp / "ft")}},
            "Global": {"seed": 11}}
    module2 = LoRAGPTModule(cfg2)
    eng2 = _engine(cfg2, module2, 5e-3)
    ft_batch = _batch(rng)
    ft_recipe.prepare_finetune(eng2, ft_batch, base_dir)
    before = lora.base_leaf_digests(eng2.state.params)
    # host copies NOW — the donated train step deletes these buffers
    _, adapters0 = lora.split_adapters(eng2.state.params)
    adapters0 = {k: np.array(jax.device_get(v))
                 for k, v in adapters0.items()}
    losses, path = ft_recipe.finetune(
        eng2, iter([ft_batch] * 4), sample_batch=ft_batch,
        base_dir=base_dir, adapter_dir=ad_dir)
    after = lora.base_leaf_digests(eng2.state.params)
    return dict(base_dir=base_dir, ad_dir=ad_dir, path=path,
                pre_losses=pre_losses, losses=losses, engine=eng2,
                module=module2, before=before, after=after,
                adapters0=adapters0)


# ================================================================ algebra

def test_inject_merge_roundtrip_and_delta_exact():
    cfg = config_from_dict(TINY)
    model = GPTForPretraining(cfg)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, 8), jnp.int32), None,
                        deterministic=True)["params"]
    adapted = lora.inject_adapters(params, rank=RANK,
                                   rng=jax.random.PRNGKey(1))
    names = [n for n, _ in R.tree_leaf_names(meta.unbox(adapted))]
    lora_names = sorted(n for n in names if lora.is_adapter_name(n))
    assert len(lora_names) == 8  # 4 targets x (A, B), scan-stacked
    # B starts at zeros → the merged model IS the base model
    merged = lora.merge_adapters(adapted, alpha=ALPHA)
    for (n, a), b in zip(R.tree_leaf_names(merged),
                         jax.tree.leaves(meta.unbox(params))):
        assert np.array_equal(np.asarray(a), np.asarray(b)), n
    # nonzero B: the fold equals the hand-written stacked einsum
    tree = meta.unbox(adapted)
    attn = tree["gpt"]["layers"]["attn"]
    a = np.asarray(attn["qkv_kernel_lora_a"])        # [L, h, r]
    b = np.asarray(np.random.RandomState(0).randn(
        *attn["qkv_kernel_lora_b"].shape).astype(np.float32))
    attn["qkv_kernel_lora_b"] = jnp.asarray(b)
    got = lora.merge_adapters(tree, alpha=ALPHA)
    want = np.asarray(attn["qkv_kernel"]) + (ALPHA / RANK) * np.einsum(
        "lhr,lrcnd->lhcnd", a, b)
    assert np.allclose(
        np.asarray(got["gpt"]["layers"]["attn"]["qkv_kernel"]), want,
        atol=1e-5)
    # injected leaves are boxed with the registry-derived logical names
    boxed = adapted["gpt"]["layers"]["attn"]["qkv_kernel_lora_b"]
    assert tuple(boxed.names) == ("layers", None, None, "heads", "kv")


def test_mask_is_shared_and_optimizer_freezes_base():
    import optax

    cfg = config_from_dict(TINY)
    model = GPTForPretraining(cfg)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, 8), jnp.int32), None,
                        deterministic=True)["params"]
    adapted = lora.inject_adapters(params, rank=RANK,
                                   rng=jax.random.PRNGKey(1))
    tx = lora.lora_optimizer(optax.sgd(0.1))
    state = tx.init(adapted)
    grads = jax.tree.map(jnp.ones_like, adapted)
    updates, _ = tx.update(grads, state, adapted)
    flat = dict(R.tree_leaf_names(meta.unbox(updates)))
    for name, u in flat.items():
        peak = float(np.abs(np.asarray(u)).max())
        if lora.is_adapter_name(name):
            assert peak > 0.0, name
        else:
            assert peak == 0.0, name
    # the gauge consumes the SAME mask: frac == adapter count / total
    leaves = R.tree_leaf_names(meta.unbox(adapted))
    total = sum(int(np.prod(l.shape)) for _, l in leaves)
    trainable = sum(int(np.prod(l.shape)) for n, l in leaves
                    if lora.is_adapter_name(n))
    assert lora.trainable_params_frac(adapted) == \
        pytest.approx(trainable / total)
    assert 0.0 < lora.trainable_params_frac(adapted) < 0.15


# ========================================================== e2e recipe

def test_finetune_loss_strictly_decreases(pipeline):
    losses = pipeline["losses"]
    assert len(losses) == 4
    assert all(b < a for a, b in zip(losses, losses[1:])), losses


def test_base_bitwise_frozen_only_adapters_move(pipeline):
    before, after = pipeline["before"], pipeline["after"]
    assert set(before) == set(after)
    for name in before:
        assert before[name]["crc32"] == after[name]["crc32"], name
    ft_recipe.assert_base_frozen(before, after)  # the recipe's own audit
    # ...and the adapters DID learn: B left its zero init
    _, adapters = lora.split_adapters(pipeline["engine"].state.params)
    moved = [n for n in adapters
             if not np.array_equal(np.asarray(adapters[n]),
                                   pipeline["adapters0"][n])]
    assert any(n.endswith("_lora_b") for n in moved), moved


def test_frozen_base_audit_refuses_naming_leaf(pipeline):
    drifted = dict(pipeline["after"])
    name = sorted(drifted)[0]
    drifted[name] = dict(drifted[name], crc32=(
        int(drifted[name]["crc32"]) ^ 1))
    with pytest.raises(RuntimeError, match="frozen-base violation"):
        ft_recipe.assert_base_frozen(drifted, pipeline["after"])


def test_adapter_artifact_tiny_and_verified(pipeline):
    path = pipeline["path"]
    adapter_nbytes = ft_ckpt.adapter_bytes(path)
    base_step = ckpt_lib.latest_step(pipeline["base_dir"])
    base_payload = 0
    base_path = os.path.join(pipeline["base_dir"], f"step_{base_step}")
    for root, _, names in os.walk(base_path):
        base_payload += sum(os.path.getsize(os.path.join(root, n))
                            for n in names
                            if n not in ("fleetx_meta.json",
                                         "fleetx_integrity.json"))
    assert adapter_nbytes > 0
    # acceptance: adapter-only checkpoint < 5% of base bytes (the base
    # payload is the full TrainState: params + Adam moments)
    assert adapter_nbytes < 0.05 * base_payload, \
        (adapter_nbytes, base_payload)
    # tools/verify_ckpt.py audits adapter artifacts unmodified, exit 0
    spec = importlib.util.spec_from_file_location(
        "verify_ckpt_ft", os.path.join(REPO, "tools", "verify_ckpt.py"))
    vck = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(vck)
    for directory in (pipeline["ad_dir"], pipeline["base_dir"]):
        report = vck.audit_directory(directory)
        assert report["ok"], report
    assert vck.main([pipeline["ad_dir"]]) == 0
    # the artifact meta stamps the provenance contract
    with open(os.path.join(path, "fleetx_meta.json")) as f:
        meta_d = json.load(f)
    assert meta_d["artifact"] == "lora_adapter"
    assert meta_d["spec_registry"] == R.family_fingerprint("gpt_lora")
    assert meta_d["base_leaves"]


def _one_shot(model, params, prompts, max_new):
    gen_cfg = G.GenerationConfig(max_new_tokens=max_new, do_sample=False,
                                 eos_token_id=EOS, pad_token_id=0)
    tokens, mask = G.left_pad(prompts, 0)
    return np.asarray(G.generate(model, params, gen_cfg,
                                 jnp.asarray(tokens), jnp.asarray(mask),
                                 jax.random.PRNGKey(1)))


def test_merged_serving_token_identical_to_reference(pipeline):
    """The headline hop: artifact-restored merged weights served through
    the paged runtime decode token-identically to UNMERGED base+adapter
    reference generation (in-memory fold + one-shot dense-cache path)."""
    cfg = config_from_dict(TINY)
    model = GPTForPretraining(cfg)
    base_params = ckpt_lib.load_params(pipeline["base_dir"])  # verified
    merged = ft_ckpt.apply_adapter_checkpoint(base_params,
                                              pipeline["ad_dir"])
    reference = lora.merge_adapters(pipeline["engine"].state.params,
                                    alpha=ALPHA)
    prompts = [[5, 9, 23, 41], [7, 3, 11]]
    want = _one_shot(model, reference, prompts, 6)
    eng = ServingEngine(
        cfg, merged,
        ServingConfig(max_batch=2, page_size=4, num_pages=33,
                      max_seq_len=32, prefill_chunk=4),
        eos_token_id=EOS)
    reqs = [eng.submit(p, 6, request_id=f"m{i}")
            for i, p in enumerate(prompts)]
    eng.run_until_drained()
    for req, row in zip(reqs, want):
        got, ref = req.tokens, [int(t) for t in row]
        assert got == ref[:len(got)], (req.id, got, ref)
        assert len(got) == len(ref) or got[-1] == EOS


def test_merged_int8_decode_within_drift_bound(pipeline):
    """int8-activation decode of the MERGED fine-tuned weights stays
    within the established serving drift bound (tests/test_zz_serving.py
    stance: 5% relative on first-chunk logits)."""
    base_params = ckpt_lib.load_params(pipeline["base_dir"])
    merged = ft_ckpt.apply_adapter_checkpoint(base_params,
                                              pipeline["ad_dir"])
    qcfg = config_from_dict(dict(TINY, qat_act_bits=8))
    prompt = [5, 9, 23, 41]

    def run(quantize):
        eng = ServingEngine(
            qcfg, merged,
            ServingConfig(max_batch=1, page_size=4, num_pages=17,
                          max_seq_len=32, prefill_chunk=8,
                          quantize_decode=quantize),
            eos_token_id=EOS)
        req = eng.submit(prompt, 6, request_id="q")
        eng.run_until_drained()
        table = np.zeros((1, eng.pages_per_req), np.int32)
        table[0, :2] = [1, 2]
        tokens = np.zeros((1, 8), np.int32)
        tokens[0, :4] = prompt
        _, _, _, logits = eng._fns["prefill"](
            eng.params, eng.pool_k, eng.pool_v, tokens, table,
            np.int32(0), np.int32(4), jax.random.PRNGKey(0), np.uint32(0))
        return req.tokens, np.asarray(logits)[0]

    fp_tokens, fp_logits = run(False)
    q_tokens, q_logits = run(True)
    drift = np.abs(q_logits - fp_logits).max() / \
        max(np.abs(fp_logits).max(), 1e-9)
    assert drift < 0.05, f"int8 decode of merged weights drifted {drift:.4f}"
    agree = sum(a == b for a, b in zip(fp_tokens, q_tokens))
    assert agree >= len(fp_tokens) // 2, (fp_tokens, q_tokens)


# ======================================================== drift refusal

def test_adapter_refused_on_base_drift_names_leaf(pipeline):
    base_params = ckpt_lib.load_params(pipeline["base_dir"])
    drifted = jax.tree.map(lambda x: x, base_params)
    drifted["gpt"]["embeddings"]["word_embeddings"] = (
        np.asarray(drifted["gpt"]["embeddings"]["word_embeddings"]) + 1e-3)
    with pytest.raises(AdapterDriftError,
                       match="word_embeddings.*drifted"):
        ft_ckpt.apply_adapter_checkpoint(drifted, pipeline["ad_dir"])


def test_adapter_refused_on_registry_drift(pipeline, monkeypatch):
    base_params = ckpt_lib.load_params(pipeline["base_dir"])
    # an UNRELATED family's edit must NOT refuse (the stamp is the
    # artifact's own per-family fingerprint, not the global registry)
    monkeypatch.setitem(R.PARTITION_RULES, "ernie",
                        R.PARTITION_RULES["ernie"][:-1])
    adapters, _ = ft_ckpt.load_adapter(pipeline["ad_dir"],
                                       base_params=base_params)
    assert adapters
    # ...but the gpt_lora table's own drift refuses loudly
    monkeypatch.setitem(R.PARTITION_RULES, "gpt_lora",
                        R.PARTITION_RULES["gpt_lora"][:-1])
    with pytest.raises(AdapterDriftError, match="rule table"):
        ft_ckpt.apply_adapter_checkpoint(base_params, pipeline["ad_dir"])


def test_adapter_refused_on_corrupt_payload(pipeline, tmp_path):
    import shutil

    step = ckpt_lib.latest_step(pipeline["ad_dir"])
    src = os.path.join(pipeline["ad_dir"], f"step_{step}")
    dst_dir = str(tmp_path / "corrupt")
    dst = os.path.join(dst_dir, f"step_{step}")
    shutil.copytree(src, dst)
    payload = os.path.join(dst, "state.npz")
    with open(payload, "r+b") as f:
        f.seek(os.path.getsize(payload) // 2)
        byte = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([byte[0] ^ 0xFF]))
    with pytest.raises(CheckpointIntegrityError):
        ft_ckpt.load_adapter(dst_dir)
    # a manifest-less artifact is equally refused (never trusted blindly)
    os.remove(os.path.join(dst, "fleetx_integrity.json"))
    with pytest.raises(CheckpointIntegrityError, match="manifest"):
        ft_ckpt.load_adapter(dst_dir)


def test_graft_refuses_partial_base(pipeline):
    """A checkpoint missing a base leaf must refuse BEFORE training — a
    silently random leaf would fine-tune (and stamp digests) against a
    base the declared checkpoint cannot reproduce."""
    partial = jax.tree.map(lambda x: x, ckpt_lib.load_params(
        pipeline["base_dir"]))
    del partial["gpt"]["ln_f"]
    with pytest.raises(ValueError, match="absent from the pretrain"):
        ft_recipe.graft_base_params(pipeline["engine"], partial)


# ================================================== consumer integration

def test_engine_resolves_gpt_lora_through_registry(devices8, tmp_path):
    from fleetx_tpu.parallel.mesh import build_mesh

    cfg = {"Model": dict(TINY, module="LoRAGPTModule"),
           "FineTune": {"lora": {"rank": RANK, "alpha": ALPHA}},
           "Engine": {"max_steps": 1,
                      "save_load": {"output_dir": str(tmp_path)}},
           "Distributed": {"mp_degree": 2, "dp_degree": 4},
           "Global": {"seed": 3}}
    module = LoRAGPTModule(cfg)
    assert module.spec_family == "gpt_lora"
    mesh = build_mesh(cfg["Distributed"], devices=devices8)
    lr = build_lr_scheduler({"max_lr": 1e-3, "warmup_steps": 0,
                             "decay_steps": 100})
    opt = lora.lora_optimizer(build_optimizer({"name": "AdamW"}, lr))
    eng = EagerEngine(cfg, module, optimizer=opt, lr_schedule=lr,
                      mesh=mesh)
    eng.prepare(_batch(np.random.RandomState(0)))
    flat = dict(R.tree_leaf_names(eng.state_shardings.params))
    assert tuple(flat["gpt/layers/attn/qkv_kernel_lora_b"].spec) == \
        (None, None, None, "tensor")
    assert tuple(flat["gpt/layers/attn/qkv_kernel_lora_a"].spec) == ()
    assert tuple(flat["gpt/layers/mlp/wi_kernel_lora_b"].spec) == \
        (None, None, "tensor")
    # adapter Adam moments resolve by the SAME rules; frozen leaves carry
    # no optimizer state at all (MaskedNode)
    opt_specs = {n: s for n, s in R.tree_leaf_names(eng.state_shardings)
                 if n.startswith("opt_state") and "lora_b" in n}
    assert opt_specs
    assert not any("word_embeddings" in n
                   for n, _ in R.tree_leaf_names(eng.state_shardings)
                   if n.startswith("opt_state"))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_serve_builder_merges_adapter_artifact(pipeline, dtype):
    """The replica's tree is the merged one, in the dtype the programs
    consume: the adapter is folded in float32 BEFORE the engine's one
    cast (``serving_params`` — the identity at the float32 recipe)."""
    from fleetx_tpu.serving.decode import serving_params

    spec = importlib.util.spec_from_file_location(
        "serve_cli_ft", os.path.join(REPO, "tools", "serve.py"))
    serve = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(serve)
    cfg = {"Model": dict(TINY, dtype=dtype),
           "Serving": {"max_batch": 2, "page_size": 4, "num_pages": 33,
                       "max_seq_len": 32, "prefill_chunk": 4,
                       "ckpt_dir": pipeline["base_dir"],
                       "adapter_dir": pipeline["ad_dir"]},
           "Generation": {"decode_strategy": "greedy_search",
                          "eos_token_id": EOS},
           "Global": {"seed": 0}}
    eng = serve._build_engine(cfg)
    base_params = ckpt_lib.load_params(pipeline["base_dir"])
    merged = ft_ckpt.apply_adapter_checkpoint(base_params,
                                              pipeline["ad_dir"])
    assert all(a.dtype == jnp.float32 for a in jax.tree.leaves(merged))
    want = serving_params(meta.unbox(merged), config_from_dict(cfg["Model"]))
    for (n, a), b in zip(R.tree_leaf_names(eng.params),
                         jax.tree.leaves(want)):
        assert a.dtype == b.dtype, n
        assert np.array_equal(np.asarray(a), np.asarray(b)), n
    assert eng.params["gpt"]["layers"]["mlp"]["wi_kernel"].dtype == \
        jnp.dtype(dtype)


def test_finetune_zoo_config_parses_and_audits_clean():
    rel = "fleetx_tpu/configs/nlp/gpt/finetune_gpt_345M_lora.yaml"
    report = SC.audit_config(REPO, rel)
    assert report["family"] == "gpt_lora"
    assert report["issues"] == [], report["issues"]
    # every gpt_lora rule is exercised by this one config (no dead rules)
    n_rules = len(R.PARTITION_RULES["gpt_lora"])
    assert report["used_rules"]["gpt_lora"] == set(range(n_rules))
    from fleetx_tpu.utils import config as config_mod

    cfg = config_mod.parse_config(os.path.join(REPO, rel))
    sc = ServingConfig.from_dict(dict(cfg.get("Serving") or {}))
    assert sc.adapter_dir and sc.ckpt_dir and sc.quantize_decode


def test_trainable_frac_gauge_exported(pipeline):
    from fleetx_tpu.observability.metrics import get_registry

    value = get_registry().gauge("trainable_params_frac").value
    assert value is not None and 0.0 < float(value) < 0.15
