"""Shardcheck: the partition-rule registry + its static auditor.

Covers the ISSUE-13 acceptance surface (docs/static_analysis.md
"Shardcheck"):

- registry unit tests: first-match-wins, scalar short-circuit, canonical
  no-trailing-None specs, stack padding (scan/pp/vpp), mesh-axis conflict
  resolution, ambiguity/divisibility/replicated-large detection, the
  shared ZeRO helpers and derived one-liners;
- the per-family COVERAGE + PARITY gate: every family's real param tree
  fully matched, and the registry specs bit-identical (canonicalised) to
  the flax logical annotations the model code carries — neither side can
  drift;
- the whole-zoo gate: `python tools/shardcheck.py --all-configs` exits 0,
  and one in-process audit run proves injected dead rules / unexercised
  families are named;
- the seeded-drift test: a mutated rule fails shardcheck naming the leaf
  and the consuming config;
- FX013 fixtures: hand-wired tables and literal-axis PartitionSpecs
  outside parallel/rules.py are findings (noqa-able), rules.py exempt;
- consumer integration: engine prepare resolves through the registry,
  checkpoint metas stamp the registry fingerprint, load_params restores
  registry-sharded, lint.py --changed-only treats config edits as
  project-scope triggers.

File sorts zz-last per the tier-1 gate convention (ROADMAP.md).
"""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import flax.linen as nn

from fleetx_tpu.parallel import rules as R
from fleetx_tpu.parallel import shardcheck as SC
from fleetx_tpu.parallel.mesh import build_mesh

pytestmark = pytest.mark.shardcheck

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = dict(vocab_size=128, hidden_size=64, num_layers=2,
            num_attention_heads=4, max_position_embeddings=32,
            use_flash_attention=False, dtype="float32",
            param_dtype="float32")
TOK = {"tokens": np.zeros((1, 32), np.int32),
       "position_ids": np.zeros((1, 32), np.int32)}


def _leaves(module, batch):
    from flax.core import meta

    abstract = jax.eval_shape(
        lambda r: module.init_variables(r, batch), jax.random.PRNGKey(0))
    return abstract, R.tree_leaf_names(meta.unbox(abstract))


# ================================================================ registry

def test_first_match_wins_and_scalars(monkeypatch):
    monkeypatch.setitem(R.PARTITION_RULES, "_t", (
        (r"kernel$", ("embed", "mlp")),
        (r"special/kernel$", ("mlp", "embed")),
    ))
    # first match wins even though the second rule also matches
    assert R.spec_for("_t", "special/kernel", (4, 4)) == (None, "tensor")
    # scalars and size-1 leaves replicate without consulting the table
    assert R.spec_for("_t", "anything_at_all", ()) == ()
    assert R.spec_for("_t", "anything_at_all", (1, 1)) == ()
    with pytest.raises(KeyError, match="no partition rule"):
        R.spec_for("_t", "unknown_leaf", (4, 4))


def test_canonical_specs_have_no_trailing_none():
    # ln scale: ('norm',) -> (None,) -> canonical ()
    assert R.spec_for("gpt", "gpt/ln_f/scale", (64,)) == ()
    # wte: ('vocab','embed') -> ('tensor', None) -> canonical ('tensor',)
    assert R.spec_for("gpt", "gpt/embeddings/word_embeddings",
                      (128, 64)) == ("tensor",)
    assert R.canonicalize((None, "fsdp", None, None)) == (None, "fsdp")


def test_stack_padding_covers_scan_pp_vpp():
    tpl = ("embed", None, "heads", "kv")
    name = "gpt/layers/attn/qkv_kernel"
    assert R.spec_for("gpt", name, (64, 3, 4, 16)) == \
        (None, None, "tensor")                          # unstacked
    assert R.spec_for("gpt", name, (2, 64, 3, 4, 16)) == \
        (None, None, None, "tensor")                    # scan [L]
    assert R.spec_for("gpt", name, (2, 2, 64, 3, 4, 16)) == \
        ("pipe", None, None, None, "tensor")            # pp [S, L/S]
    assert R.spec_for("gpt", name, (2, 2, 1, 64, 3, 4, 16)) == \
        (None, "pipe", None, None, None, "tensor")      # vpp [V, S, ...]
    del tpl
    # an unstacked path with a rank the template cannot cover is loud
    with pytest.raises(ValueError, match="rank"):
        R.spec_for("gpt", "gpt/ln_f/scale", (2, 2, 64, 1))


def test_mesh_axis_conflict_resolves_by_table_order():
    """MoE wi_kernel: expert AND mlp both map to tensor — flax gives the
    axis to the logical name earlier in the rule table (mlp), the other
    replicates. The registry must match (pinned against flax in the
    parity gate below)."""
    spec = R.spec_for("gpt_moe", "gpt/layers/mlp/wi_kernel",
                      (2, 4, 64, 256))
    assert spec == (None, None, None, "tensor")


def test_layout_knobs_route_embed_and_act_seq():
    lay3 = R.SpecLayout(stage=3)
    assert R.spec_for("gpt", "gpt/embeddings/word_embeddings",
                      (128, 64), lay3) == ("tensor", "fsdp")
    table = dict(R.SpecLayout(sequence_parallel=True).axis_rules())
    assert table["act_seq"] == ("seq", "tensor")
    assert dict(R.SpecLayout().axis_rules())["act_seq"] == ("seq",)


def test_audit_detects_ambiguous_overlap(monkeypatch):
    monkeypatch.setitem(R.PARTITION_RULES, "_t", (
        (r"kernel$", ("embed", "mlp")),
        (r"special/kernel$", ("mlp", "embed")),
    ))
    leaves = [("special/kernel", jax.ShapeDtypeStruct((4, 4), jnp.float32))]
    issues, used = R.audit_leaves("_t", leaves)
    assert [i["kind"] for i in issues] == ["ambiguous"]
    assert used == {0}
    # same-spec overlap is benign (not ambiguity)
    monkeypatch.setitem(R.PARTITION_RULES, "_t", (
        (r"kernel$", ("embed", "mlp")),
        (r"special/kernel$", ("embed", "mlp")),
    ))
    issues, _ = R.audit_leaves("_t", leaves)
    assert issues == []


def test_audit_divisibility_per_layout():
    leaves = [("gpt/embeddings/word_embeddings",
               jax.ShapeDtypeStruct((100, 64), jnp.float32))]
    issues, _ = R.audit_leaves("gpt", leaves, degrees={"tensor": 8})
    assert [i["kind"] for i in issues] == ["indivisible"]
    assert "word_embeddings" in issues[0]["message"]
    issues, _ = R.audit_leaves("gpt", leaves, degrees={"tensor": 4})
    assert issues == []


def test_audit_flags_oversized_replicated_leaf():
    big = [("gpt/embeddings/position_embeddings",
            jax.ShapeDtypeStruct((1 << 14, 1 << 12), jnp.float32))]
    issues, _ = R.audit_leaves("gpt", big)
    assert [i["kind"] for i in issues] == ["replicated-large"]
    # imagen DECLARES replication — exempt at any size
    big_im = [("unet/mid1/conv1/kernel",
               jax.ShapeDtypeStruct((1 << 14, 1 << 12), jnp.float32))]
    issues, _ = R.audit_leaves("imagen", big_im)
    assert issues == []


def test_audit_names_unmatched_leaf():
    leaves = [("gpt/brand_new_adapter/lora_a",
               jax.ShapeDtypeStruct((64, 8), jnp.float32))]
    issues, _ = R.audit_leaves("gpt", leaves)
    assert [i["kind"] for i in issues] == ["unmatched"]
    assert "lora_a" in issues[0]["message"]


def test_with_fsdp_axis():
    # moments and grads alike: keep existing, add fsdp on the first free
    # divisible dim (a moment frozen at its param's tensor spec stayed
    # whole on every fsdp shard: 14.5 GiB a chip for fsdp4 stage 2 at
    # GPT-345M, above the one-chip 14.2 — PERF.md)
    assert R.with_fsdp_axis((8, 3), (), 4) == ("fsdp",)
    assert R.with_fsdp_axis((3, 8), (None, "tensor"), 4) == (None, "tensor")
    assert R.with_fsdp_axis((8, 8), (None, "tensor"), 4) == \
        ("fsdp", "tensor")
    # the param's own spec already uses the axis (stage 3): unchanged
    assert R.with_fsdp_axis((8, 8), ("fsdp", "tensor"), 4) == \
        ("fsdp", "tensor")
    # nothing divisible / degree 1 → canonical replicated
    assert R.with_fsdp_axis((3, 5), (), 4) == ()
    assert R.with_fsdp_axis((8, 8), (), 1) == ()


def test_stage_table_matches_memory_model():
    from fleetx_tpu.parallel.auto_layout import _per_device_bytes

    terms = {"moments": 800.0, "grads": 400.0, "weights": 600.0,
             "act": 100.0}
    for stage in (0, 1, 2, 3):
        got = _per_device_bytes(terms, fsdp=4, mp=1, pp=1, seq=1,
                                stage=stage)
        want = (terms["moments"] / (4 if R.stage_shards("moments", stage)
                                    else 1)
                + terms["grads"] / (4 if R.stage_shards("grads", stage)
                                    else 1)
                + terms["weights"] / (4 if R.stage_shards("weights", stage)
                                      else 1)
                + terms["act"])
        assert got == want
    assert R.stage_shards("moments", 1) and not R.stage_shards("grads", 1)
    assert R.stage_shards("grads", 2) and not R.stage_shards("weights", 2)


def test_kv_pool_and_batch_specs_come_from_registry():
    from fleetx_tpu.serving.paged_cache import pool_shardings

    assert R.kv_pool_spec() == P(None, "fsdp", None, "tensor")
    assert R.batch_spec() == P(("data", "fsdp"))
    mesh = build_mesh({}, devices=jax.devices()[:1])
    assert pool_shardings(mesh).spec == R.kv_pool_spec()


def test_registry_fingerprint_tracks_mutation(monkeypatch):
    before = R.registry_fingerprint()
    monkeypatch.setitem(R.PARTITION_RULES, "gpt",
                        R.PARTITION_RULES["gpt"][:-1])
    assert R.registry_fingerprint() != before


# ================================================= coverage + parity gate

def _family_modules():
    from fleetx_tpu.core.module import GPTModule
    from fleetx_tpu.finetune.module import LoRAGPTModule
    from fleetx_tpu.models.ernie.module import ErnieModule
    from fleetx_tpu.models.imagen.module import ImagenModule
    from fleetx_tpu.models.vision.module import GeneralClsModule

    vit = {"Model": {"name": "ViT_base_patch16_224",
                     "model": {"num_layers": 2, "hidden_size": 64,
                               "num_attention_heads": 4, "image_size": 32,
                               "patch_size": 16, "num_classes": 10}}}
    yield ("gpt scan", GPTModule({"Model": dict(TINY)}), TOK, {})
    yield ("gpt stage3", GPTModule({"Model": dict(TINY)}), TOK,
           {"sharding": {"sharding_stage": 3}})
    yield ("gpt noscan", GPTModule({"Model": dict(TINY, scan_layers=False)}),
           TOK, {})
    yield ("gpt pp2", GPTModule({"Model": dict(TINY, num_layers=4),
                                 "Distributed": {"pp_degree": 2}}), TOK,
           {"pp_degree": 2})
    yield ("gpt vpp2",
           GPTModule({"Model": dict(TINY, num_layers=4),
                      "Distributed": {"pp_degree": 2,
                                      "virtual_pp_degree": 2}}), TOK,
           {"pp_degree": 2})
    yield ("gpt_moe", GPTModule({"Model": dict(TINY, moe_num_experts=4,
                                               moe_top_k=2)}), TOK, {})
    # LoRA fine-tuning (docs/finetune.md): the adapted tree is its own
    # family — base rules + adapter rules — and the injected leaves carry
    # registry-derived flax boxing, so the parity gate pins both sides
    yield ("gpt_lora", LoRAGPTModule({"Model": dict(TINY),
                                      "FineTune": {"lora": {"rank": 4}}}),
           TOK, {})
    yield ("gpt_lora stage3",
           LoRAGPTModule({"Model": dict(TINY),
                          "FineTune": {"lora": {"rank": 4}}}), TOK,
           {"sharding": {"sharding_stage": 3}})
    yield ("vision", GeneralClsModule(vit),
           {"images": np.zeros((1, 32, 32, 3), np.float32)}, {})
    yield ("ernie", ErnieModule({"Model": dict(TINY, type_vocab_size=2)}),
           {"input_ids": np.zeros((1, 32), np.int32)}, {})
    yield ("imagen", ImagenModule({"Model": {"preset": "base64",
                                             "image_size": 16}}),
           {"images": np.zeros((1, 16, 16, 3), np.float32),
            "text_embeds": np.zeros((1, 8, 64), np.float32),
            "text_mask": np.ones((1, 8), bool)}, {})


def test_every_family_tree_fully_matched_and_flax_parity():
    """THE drift gate: for every family (and the pp/vpp/noscan/stage
    layout variants), (a) the audit reports zero issues — full coverage —
    and (b) the registry's resolved specs equal the canonicalised flax
    logical annotations. A model edit that renames a leaf, or a registry
    edit that mis-specs one, fails here on CPU."""
    for tag, module, batch, dist in _family_modules():
        family = R.family_of(module)
        abstract, leaves = _leaves(module, batch)
        layout = R.SpecLayout.from_dist_config(dist)
        issues, _ = R.audit_leaves(family, leaves, layout)
        assert issues == [], (tag, issues)
        table = layout.axis_rules()
        legacy = nn.get_partition_spec(abstract)
        reg = R.registry_specs(family, abstract, layout)
        lf, _ = jax.tree_util.tree_flatten_with_path(
            legacy, is_leaf=lambda x: isinstance(x, P))
        rf, _ = jax.tree_util.tree_flatten_with_path(
            reg, is_leaf=lambda x: isinstance(x, P))
        assert len(lf) == len(rf), tag
        for (kp, ls), (_, rs) in zip(lf, rf):
            lcan = R.canonicalize(tuple(nn.logical_to_mesh_axes(ls, table)))
            assert lcan == tuple(rs), (tag, kp, lcan, tuple(rs))


def test_zoo_audit_clean_and_names_injected_dead_rules(monkeypatch):
    """One whole-zoo audit run: the real registry is clean (no issues, no
    dead rules), an injected never-matching rule is reported dead, and a
    registered family no config exercises is reported unexercised."""
    monkeypatch.setitem(
        R.PARTITION_RULES, "gpt",
        R.PARTITION_RULES["gpt"] + ((r"never_matches_anything$",
                                     ("embed",)),))
    monkeypatch.setitem(R.PARTITION_RULES, "ghost_family",
                        ((r".", R.REPLICATED),))
    report = SC.audit_zoo(REPO)
    assert report["issues"] == []
    assert report["configs"] > 20
    dead = {(d["family"], d["pattern"]) for d in report["dead_rules"]}
    assert ("gpt", r"never_matches_anything$") in dead
    assert ("ghost_family", "") in dead
    assert len(dead) == 2, report["dead_rules"]


def test_seeded_drift_fails_naming_leaf_and_consumer(monkeypatch):
    """ISSUE acceptance: a deliberately mutated rule fails shardcheck
    naming the offending leaf and the consuming config."""
    table = list(R.PARTITION_RULES["gpt"])
    table[0] = (table[0][0], ("bogus_axis", None, "heads", "kv"))
    monkeypatch.setitem(R.PARTITION_RULES, "gpt", tuple(table))
    rel = "fleetx_tpu/configs/nlp/gpt/pretrain_gpt_345M_single_card.yaml"
    report = SC.audit_config(REPO, rel)
    kinds = {i["kind"] for i in report["issues"]}
    assert "unknown-axis" in kinds, report["issues"]
    bad = [i for i in report["issues"] if i["kind"] == "unknown-axis"][0]
    assert "qkv_kernel" in bad["leaf"]
    assert bad["config"] == rel


def test_fx011_fx012_findings_through_lint_stack(monkeypatch):
    """The mutated registry surfaces through run_lint as FX011/FX012
    findings with config/rules.py anchors (text/JSON/SARIF-renderable)."""
    from fleetx_tpu.lint import render_sarif, run_lint

    # drop the attn out_bias rule: its leaves go unmatched (FX011) and
    # its absence leaves mlp/wo_bias alone — keep it simple: also shadow
    # the ln rule so the ORIGINAL (present in rules.py text) goes dead
    gpt = R.PARTITION_RULES["gpt"]
    ln_rule = next(r for r in gpt if "ln1" in r[0])
    monkeypatch.setitem(R.PARTITION_RULES, "gpt",
                        (ln_rule,) + tuple(r for r in gpt
                                           if "out_bias" not in r[0]))
    result = run_lint([os.path.join(REPO, "fleetx_tpu")], root=REPO,
                      select=["FX011", "FX012"])
    codes = {f.code for f in result.findings}
    assert "FX011" in codes, [f.message for f in result.findings][:5]
    unmatched = [f for f in result.findings
                 if f.code == "FX011" and "out_bias" in f.message]
    assert unmatched and unmatched[0].path.endswith(".yaml")
    assert "consumers" in unmatched[0].message
    sarif = render_sarif(result)
    assert sarif["runs"][0]["results"], "SARIF carries the findings"


# ======================================================== FX013 fixtures

def _lint_src(tmp_path, src, name="m.py", select=("FX013",)):
    from fleetx_tpu.lint import run_lint

    f = tmp_path / name
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(src)
    return run_lint([f], root=tmp_path, select=list(select))


def test_fx013_flags_hand_wired_table(tmp_path):
    res = _lint_src(tmp_path, '''"""Doc."""
_SPECS = (
    ("word_embeddings", ("vocab", "embed")),
    ("wi_kernel", ("embed", "mlp")),
)
''')
    assert [f.code for f in res.findings] == ["FX013"]
    assert "parallel/rules.py" in res.findings[0].message


def test_fx013_flags_literal_axis_pspec(tmp_path):
    res = _lint_src(tmp_path, '''"""Doc."""
from jax.sharding import NamedSharding, PartitionSpec


def pool(mesh):
    """Doc."""
    return NamedSharding(mesh, PartitionSpec(None, "fsdp", None, "tensor"))
''')
    assert [f.code for f in res.findings] == ["FX013"]
    assert "fsdp" in res.findings[0].message


def test_fx013_negative_dynamic_specs_and_noqa(tmp_path):
    res = _lint_src(tmp_path, '''"""Doc."""
from jax.sharding import PartitionSpec


def dyn(axis, entries):
    """Dynamic spec construction is fine — no literals."""
    return PartitionSpec(axis, *entries)


TABLE = (("a", 1), ("b", 2))  # value pairs, not specs
''')
    assert res.findings == []
    res = _lint_src(tmp_path, '''"""Doc."""
from jax.sharding import PartitionSpec

S = PartitionSpec("tensor")  # fleetx: noqa[FX013] -- test fixture
''')
    assert res.findings == [] and len(res.suppressed) == 1


def test_fx013_exempts_rules_py(tmp_path):
    res = _lint_src(tmp_path, '''"""Doc."""
PARTITION_RULES = (
    ("word_embeddings", ("vocab", "embed")),
    ("wi_kernel", ("embed", "mlp")),
)
''', name="fleetx_tpu/parallel/rules.py")
    assert res.findings == []


def test_repo_has_no_hand_wired_specs():
    """The acceptance bar: zero FX013 findings (and zero baseline) over
    the real tree — every spec table lives in parallel/rules.py."""
    from fleetx_tpu.lint import run_lint

    res = run_lint([os.path.join(REPO, "fleetx_tpu")], root=REPO,
                   select=["FX013"])
    assert res.findings == [], [f.location() for f in res.findings]


# ================================================== consumer integration

def test_engine_prepare_resolves_through_registry(tmp_path, devices8):
    from fleetx_tpu.core.engine import EagerEngine
    from fleetx_tpu.core.module import GPTModule
    from fleetx_tpu.optims.lr_scheduler import build_lr_scheduler
    from fleetx_tpu.optims.optimizer import build_optimizer

    cfg = {"Model": dict(TINY),
           "Engine": {"max_steps": 1,
                      "save_load": {"output_dir": str(tmp_path)}},
           "Distributed": {"mp_degree": 2, "dp_degree": 4},
           "Global": {"seed": 7}}
    mesh = build_mesh(cfg["Distributed"], devices=devices8)
    module = GPTModule(cfg)
    assert module.spec_family == "gpt"
    lr = build_lr_scheduler({"name": "cosine", "max_lr": 1e-3,
                             "min_lr": 1e-4, "warmup_steps": 2,
                             "decay_steps": 10})
    opt = build_optimizer({"name": "AdamW", "weight_decay": 0.0,
                           "grad_clip": {"clip_norm": 1.0}}, lr)
    eng = EagerEngine(cfg, module, optimizer=opt, lr_schedule=lr, mesh=mesh)
    batch = {"tokens": np.zeros((8, 32), np.int32),
             "position_ids": np.zeros((8, 32), np.int32),
             "labels": np.zeros((8, 32), np.int32),
             "loss_mask": np.ones((8, 32), np.float32)}
    eng.prepare(batch)
    flat = dict(R.tree_leaf_names(eng.state_shardings.params))
    wte = flat["gpt/embeddings/word_embeddings"]
    assert tuple(wte.spec) == ("tensor",)
    # Adam moments resolve by the SAME rules (name-suffix match)
    opt_specs = {n: s for n, s in R.tree_leaf_names(eng.state_shardings)
                 if "word_embeddings" in n and n.startswith("opt_state")}
    assert opt_specs and all(tuple(s.spec) == ("tensor",)
                             for s in opt_specs.values())

    # checkpoint meta carries the registry stamp (both codecs share the
    # meta writer) and load_params restores registry-sharded
    from fleetx_tpu.core import checkpoint as ckpt_lib

    eng.save()
    meta = ckpt_lib.peek_meta(str(tmp_path))
    assert meta["spec_family"] == "gpt"
    assert meta["spec_registry"] == R.registry_fingerprint()
    with mesh:
        params = ckpt_lib.load_params(str(tmp_path), mesh=mesh)
    got = dict(R.tree_leaf_names(params))
    wte_arr = got["gpt/embeddings/word_embeddings"]
    assert tuple(wte_arr.sharding.spec) == ("tensor",)


def test_unknown_module_falls_back_to_logical_metadata(caplog):
    from fleetx_tpu.core.engine.eager_engine import _named_shardings

    mesh = build_mesh({}, devices=jax.devices()[:1])
    tree = {"x": jax.ShapeDtypeStruct((4, 4), jnp.float32)}
    sh = _named_shardings(tree, mesh, R.SpecLayout().axis_rules(),
                          family=None)
    assert tuple(sh["x"].spec) == ()


# ========================================================== CLI + driver

def test_shardcheck_cli_all_configs_exits_zero():
    """ISSUE acceptance: `python tools/shardcheck.py --all-configs` exits
    0 over the whole YAML zoo on CPU, JSON output included."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "shardcheck.py"),
         "--all-configs", "--no-cache", "--json", "-"],
        capture_output=True, text=True, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout[:proc.stdout.rindex("}") + 1])
    assert payload["clean"] is True
    assert set(payload["rules"]) == {"shard-rule-coverage",
                                     "shard-rule-health",
                                     "hand-wired-spec-table"}


def test_shardcheck_cli_selftest_drift_exits_nonzero():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "shardcheck.py"),
         "--selftest-drift"],
        capture_output=True, text=True, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "qkv_kernel" in proc.stdout  # names the leaf


def test_shardcheck_single_config_filter():
    rel = "fleetx_tpu/configs/nlp/gpt/pretrain_gpt_base.yaml"
    report = SC.audit_zoo(REPO, only=[rel])
    assert report["configs"] == 1
    assert report["issues"] == []
    # a filtered run cannot prove deadness — no dead-rule entries
    assert report["dead_rules"] == []


def test_changed_only_config_edit_triggers_full_report(tmp_path,
                                                       monkeypatch,
                                                       capsys):
    """Satellite: a YAML-only diff re-runs the project-scope rules over
    the full tree with an UNRESTRICTED report — a .py finding (here:
    FX006-visible dead config key territory, approximated with a
    docstring finding) is reported even though only a config changed."""
    spec = importlib.util.spec_from_file_location(
        "fleetx_lint_cli_sc", os.path.join(REPO, "tools", "lint.py"))
    cli = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cli)

    def git(*args):
        subprocess.run(["git", "-C", str(tmp_path / "repo"), "-c",
                        "user.email=t@t", "-c", "user.name=t", *args],
                       capture_output=True, text=True, check=True)

    repo = tmp_path / "repo"
    (repo / "fleetx_tpu" / "configs").mkdir(parents=True)
    bad = repo / "fleetx_tpu" / "mod.py"
    bad.write_text('"""Doc."""\nimport jax\n\n\n@jax.jit\ndef f(x):\n'
                   '    """Doc."""\n    return float(x)\n')  # FX001
    conf = repo / "fleetx_tpu" / "configs" / "a.yaml"
    conf.write_text("Engine:\n  max_steps: 1\n")
    git("init", "-q")
    git("add", "-A")
    git("commit", "-qm", "seed")
    conf.write_text("Engine:\n  max_steps: 2\n")  # YAML-only diff
    monkeypatch.setattr(cli, "REPO_ROOT", str(repo))
    monkeypatch.setattr(cli, "DEFAULT_BASELINE", str(repo / "b.json"))
    monkeypatch.setattr(cli, "DEFAULT_CACHE", str(repo / ".c.json"))
    rc = cli.main(["--changed-only", "--select",
                   "host-sync-in-traced-code,FX006"])
    out = capsys.readouterr()
    assert "full-tree scan" in out.err
    # the .py finding is REPORTED although only the yaml changed
    assert rc == 1 and "mod.py" in out.out
