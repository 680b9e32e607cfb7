"""The ``joyai-flash-train-b2s8192`` cell's files on the CPU: the shipped
manifest finds them, their counts are checked by hand, and the real
``train_cell.py`` rehearses the cell at toy widths against the real
``joyai_ref.py`` and ``adamw_noaux_ref.py`` (``toy.make_root``'s way). No
number here stands for a device."""

from __future__ import annotations

import argparse
import io
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import toy  # noqa: E402
from benchmarks import manifest as manifest_mod  # noqa: E402
from benchmarks import run, traffic  # noqa: E402
from benchmarks.manifest import Manifest  # noqa: E402

CELL = "joyai-flash-train-b2s8192"
TOY_WIDTHS = dict(
    hidden_size=64, intermediate_size=96, num_attention_heads=4,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, moe_intermediate_size=32, num_experts_per_tok=3,
    vocab_size=256, num_hidden_layers=3, rope_theta=10000.0)
TOY_MIX = {"kind": "train_steps", "sequences_per_chip": 2, "seq_len": 128,
           "check_steps": 3, "warmup_steps": 1, "reference_rows_per_block": 1,
           "trace_seconds": 0.5}
# toy readings on the CPU (seeds 5, 6, 2**31 + 7; float32 reference): the
# reference in bfloat16 gives loss <= 6.5e-6, gradient <= 4.8e-3, change <=
# 1.2e-2; in float8 loss >= 5.6e-5, gradient >= 2.0e-2, change 6e-3 .. 1.4e-2
# (the change does not tell them apart at these widths: it is held against
# a step that returns its state)
TOY_LIMITS = {"loss_rel_gap": 5e-5, "grad_norm_worst_leaf_gap": 1.0e-2,
              "delta_norm_worst_leaf_gap": 3e-2}


def _toy_config() -> dict:
    with open(os.path.join(ROOT, "benchmarks/configs/joyai-llm-flash.json")) as f:
        cfg = json.load(f)
    cfg.update(TOY_WIDTHS, name="toy-joyai", n_routed_experts=4,
               router_experts=16, first_expert_held=4)
    model = dict(TOY_WIDTHS, n_routed_experts=16, experts_held=4,
                 first_expert_held=4, moe_chunk_rows=64, moe_tile_rows=8,
                 loss_chunk_rows=64)
    cfg["train"]["overrides"] = cfg["train"]["overrides"] + [
        f"Model.{k}={v}" for k, v in model.items()] + [
        "Global.max_seq_len=128", "Global.local_batch_size=2",
        "Global.micro_batch_size=2"]
    cfg["check"] = {"train": dict(TOY_LIMITS)}
    return cfg


@pytest.fixture(scope="module")
def real():
    return Manifest(ROOT)


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """``toy.make_root``'s rehearsal root with one more configuration and
    one more cell: this family at toy widths."""
    root = toy.make_root(str(tmp_path_factory.mktemp("joyai_root")))
    with open(os.path.join(root, "benchmarks/configs/toy-joyai.json"),
              "w") as f:
        json.dump(_toy_config(), f)
    with open(os.path.join(root, "benchmarks/traffic/toy-joyai-train.json"),
              "w") as f:
        json.dump(TOY_MIX, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "toy-joyai", "source": "tests", "reduced": [],
        "file": "benchmarks/configs/toy-joyai.json", "why": "toy widths"})
    bench["workloads"].append({
        "name": "toy-joyai-train", "config": "toy-joyai",
        "traffic": "toy-joyai-train", "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "toy-train" in m.get("workloads", []):
            m["workloads"].append("toy-joyai-train")
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


# ------------------------------------------------------- the shipped files
def test_the_shipped_manifest_finds_the_cells_files(real):
    w = real.cells[CELL]
    assert w["chips"] == 1 and w["config"] == "joyai-llm-flash"
    cfg, mix = real.config(w["config"]), real.traffic(w["traffic"])
    assert mix["kind"] == "train_steps" and mix["seq_len"] == 8192
    assert mix["sequences_per_chip"] == 2 and mix["check_steps"] == 3
    for name in (cfg["reference"], cfg["train"]["optimizer"]["reference"]):
        assert os.path.exists(real.reference_path(name))
    assert os.path.exists(os.path.join(ROOT, cfg["train"]["recipe"]))
    assert hasattr(manifest_mod.load_module(
        real.kernel_path(cfg["model_flops"])), "train_flops_per_token")
    names = {m["name"] for m in real.metrics_of(CELL, "per_layer")}
    assert {"mla_flash_fwd_roofline", "mla_flash_bwd_roofline",
            "moe_gmm_roofline", "moe_expert_ms", "moe_load_max_over_mean",
            "train_step_ms", "train_mfu_pct", "hbm_peak_gb"} <= names
    assert not names & {"flash_fwd_roofline", "flash_bwd_roofline"}
    for name in names:
        assert hasattr(manifest_mod.load_module(real.reader_path(name)),
                       "read")
    assert {m["name"] for m in real.metrics_of(CELL, "end_to_end")} == \
        {"train_tokens_per_s", "setup_s"}
    for kernel in ("mla_flash_fwd", "mla_flash_bwd_dq", "mla_flash_bwd_dkv",
                   "moe_gmm", "moe_gmm_t", "moe_tgmm"):
        assert kernel in real.kernel_trace_names()


def test_the_configuration_keeps_the_catalogs_widths_and_the_floors(real):
    cfg = real.config("joyai-llm-flash")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        row = next(json.loads(line) for line in f
                   if '"name": "JoyAI-LLM-Flash"' in line)
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    assert sorted(cfg["reduced"]) == ["n_routed_experts", "num_hidden_layers",
                                      "vocab_size"]
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    for limit in cfg["check"]["train"].values():
        assert 0 < limit < 0.05


def test_counts_by_hand(real):
    cfg = real.config("joyai-llm-flash")
    fwd = manifest_mod.load_module(real.kernel_path("mla_flash_fwd"))
    got = fwd.count(2, 8192, 32, 128, 64, 128)
    assert got["flops"] == 2 * 2 * 32 * 8192 * 8192 * (192 + 128) // 2
    assert got["bytes"] == 2 * 8192 * (32 * (192 + 128 + 128 + 128) + 64) \
        * 2 + 2 * 32 * 8192 * 4
    bwd = manifest_mod.load_module(real.kernel_path("mla_flash_bwd"))
    dq = bwd.count(2, 8192, 32, 128, 64, 128, variant="mla_flash_bwd_dq")
    dkv = bwd.count(2, 8192, 32, 128, 64, 128, variant="mla_flash_bwd_dkv")
    pair = 2 * 32 * 8192 * 8192        # one multiply-add per (q, k) pair
    assert dq["flops"] == pair * (192 + 128 + 192)
    assert dkv["flops"] == pair * (192 + 128 + 128 + 192)
    gmm = manifest_mod.load_module(real.kernel_path("moe_gmm"))
    one = gmm.count(rows=10240, k=2048, n=1536, experts=16)
    assert one["flops"] == 2 * 10240 * 2048 * 1536
    assert one["bytes"] == (10240 * (2048 + 1536) + 16 * 2048 * 1536) * 2
    # the rows of the roofline are the harness's: 16,384 x 8 x 16 / 256
    assert gmm.expected_rows(cfg, 2 * 8192) == 8192
    model = manifest_mod.load_module(real.kernel_path("joyai_share_model"))
    parts = model.forward_macs_per_token(cfg, 8192)
    # the issue's own figures, forward operations a token = 2 x these
    assert round(2 * parts["attention_products"] / 1e6) == 84
    assert round(2 * parts["attention_projections"] / 1e6) == 53
    assert round(2 * parts["shared_expert"] / 1e6, 1) == 9.4
    assert round(2 * parts["held_experts"] / 1e6, 1) == 4.7
    assert abs(model.train_flops_per_token(cfg, 8192) / 1e9 - 3.398) < 1e-3


def test_the_traffic_draws_ids_from_the_slice(real):
    cfg, mix = real.config("joyai-llm-flash"), real.traffic("train-b2s8192")
    batch = next(traffic.train_batches(mix, 2 ** 31 + 5, cfg["vocab_size"], 1))
    assert batch["tokens"].shape == (2, 8192)
    assert int(batch["tokens"].max()) < 16160
    assert int(batch["tokens"].max()) > 16000
    assert (batch["tokens"][0] != batch["tokens"][1]).any()
    assert (batch["labels"][:, :-1] == batch["tokens"][:, 1:]).all()


# ------------------------------------------------------------ the rehearsal
_LINES: dict = {}


def _rehearse(root, trace):
    if trace not in _LINES:
        out, err = io.StringIO(), io.StringIO()
        args = argparse.Namespace(workload="toy-joyai-train", seed=3000000019,
                                  seconds=1.5, trace=trace, control="")
        run.run_cell(args, root=root, platforms=("cpu",), out=out, err=err)
        _LINES[trace] = json.loads(out.getvalue().strip().splitlines()[-1])
        _LINES[trace]["_log"] = err.getvalue()
    return _LINES[trace]


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell_prints_a_well_formed_line(toy_root, trace):
    line = _rehearse(toy_root, trace)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["check"]) == set(TOY_LIMITS)
    assert "NOT CORRECT" not in line["_log"]
    if trace:
        # a counter the program keeps reaches the line; nothing that needs
        # a device does
        assert line["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
        assert not [k for k in line["metrics"]
                    if "roofline" in k or "mfu" in k or k == "moe_expert_ms"]
    else:
        assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
        assert line["metrics"]["train_tokens_per_s"]["value"] > 0


def _toy_numbers(precision, seed):
    import jax.numpy as jnp
    from benchmarks import check, weights

    m = Manifest(ROOT)
    cfg = _toy_config()
    ref = manifest_mod.load_module(m.reference_path("joyai_ref"))
    adam = manifest_mod.load_module(m.reference_path("adamw_noaux_ref"))
    w = weights.make(ref.weight_spec(cfg), seed)
    feed = traffic.train_batches(TOY_MIX, seed, cfg["vocab_size"], 1)
    batches = [{k: jnp.asarray(v) for k, v in next(feed).items()}
               for _ in range(3)]
    opt = cfg["train"]["optimizer"]
    want = check.train_reference(ref, adam, cfg, opt, w, batches)
    got = check.train_reference(ref, adam, cfg, opt, w, batches,
                                precision=precision)
    return check.train_numbers(got, want)


@pytest.mark.parametrize("precision,seed,passes", [
    ("bfloat16", 5, True), ("float8", 5, False), ("float8", 2 ** 31 + 7,
                                                  False)])
def test_the_check_passes_the_stated_precision_and_fails_the_one_below(
        precision, seed, passes):
    from benchmarks import check

    numbers = _toy_numbers(precision, seed)
    assert check.judge(numbers, TOY_LIMITS, out=io.StringIO()) is passes
    if not passes:
        assert numbers["grad_norm_worst_leaf_gap"] > \
            TOY_LIMITS["grad_norm_worst_leaf_gap"]
