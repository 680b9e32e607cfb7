"""The ``laguna-s-2.1`` configuration and its cell
``laguna-s-serve-mixed-closed`` (ISSUE 35): the files load through the
manifest, state the cut the issue names, and — at toy widths on the CPU,
through the same ``run_cell`` — serve ``correct`` while the float8 control
does not."""

import argparse
import io
import json
import os
import shutil

import pytest

from benchmarks import manifest as manifest_mod, run
from benchmarks.manifest import Manifest

from tests.benchmarks import toy

ROOT = toy.ROOT
CELL, CONFIG, TRAFFIC = ("laguna-s-serve-mixed-closed", "laguna-s-2.1",
                         "serve-mixed-closed")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("kv_decode_roofline", "moe_serve_roofline", "moe_serve_ms",
               "prefill_chunk_dev_ms", "moe_experts_hit_pct", "kv_cache_gb",
               "first_token_p50_ms", "first_token_p95_ms")

TOY_WINDOW, TOY_LIMIT = 16, 0.01
TOY_MODEL = {
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
    "num_key_value_heads": 2, "head_dim": 16, "sliding_window": TOY_WINDOW,
    "num_attention_heads_per_layer": [4, 6, 6, 6, 4, 6, 6, 6, 4],
    "num_experts": 32, "experts_held": 8, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
}


@pytest.fixture(scope="module")
def real():
    return Manifest(ROOT)


def test_the_cells_files_load_and_state_the_cut(real):
    cell = real.cells[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    cfg, mix = real.config(CONFIG), real.traffic(TRAFFIC)
    entry = real.configs[CONFIG]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 256,
                                "vocab_size": 100352}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"], cfg["router_experts"]) == (9, 32, 12544, 256)
    assert entry["source"] == cfg["source"]
    for line in ("activation", "gate", "router", "qk_norm", "shared_expert",
                 "window"):
        assert cfg["assumed"][line]
    assert "8 chips" in cfg["deployment"]
    # the traffic ISSUE 35 names, letter for letter
    assert mix == {**mix, "kind": "closed_loop", "clients": 64,
                   "prompt_lengths": [512, 512, 1024, 1024, 2048, 2048,
                                      4096, 8192],
                   "output_lengths": [256, 512, 768, 1024],
                   "stationary_start": True, "trace_seconds": 5,
                   "check": {"requests": 4, "pad_to": 9216}}
    assert min(mix["prompt_lengths"]) >= cfg["sliding_window"]
    over = dict(o.split("=") for o in cfg["serve"]["overrides"])
    assert over["Serving.prefill_chunk"] == "512"
    assert over["Serving.max_seq_len"] == "9728"
    assert (int(over["Serving.num_pages"]) - 1) * int(
        over["Serving.page_size"]) == 288000
    assert int(over["Serving.max_batch"]) == mix["clients"]
    reported = {m["name"] for group in ("end_to_end", "per_layer")
                for m in real.metrics_of(CELL, group)}
    assert {"serve_out_tokens_per_s", "itl_p95_ms", "setup_s",
            "decode_step_ms", "pool_copy_ms", "decode_occupancy",
            "preempt_per_req", "tick_host_ms", "tick_idle_ms",
            *NEW_METRICS} <= reported
    assert "ttft_mean_ms" not in reported
    assert "paged_decode_roofline" not in reported
    for name in NEW_METRICS:
        assert real.per_layer[name]["workloads"] == [CELL]
    assert real.family("SWAMoEModule") and real.reference_path("laguna_ref")


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_published_number_is_the_catalogs(real):
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Laguna-S-2.1")
    cfg = real.config(CONFIG)
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key


def _toy_root(tmp: str) -> str:
    """A rehearsal root whose one cell is the shipped cell's files at toy
    widths: the shipped configuration with toy ``Model.*`` overrides and a
    small engine, a small mix of the same kind."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(tmp, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "benchmarks/configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    cfg.update({k: v for k, v in TOY_MODEL.items() if k in cfg})
    cfg.update(num_experts=8, router_experts=32, vocab_size=256,
               num_attention_heads_per_layer=TOY_MODEL[
                   "num_attention_heads_per_layer"] + [4] * 39)
    cfg["serve"]["overrides"] = [
        f"Model.{k}={json.dumps(v)}" for k, v in TOY_MODEL.items()] + [
        "Serving.max_batch=4", "Serving.page_size=8", "Serving.num_pages=129",
        "Serving.max_seq_len=256", "Serving.prefill_chunk=32",
        "Serving.max_queue=0"]
    # toy readings on the CPU (bfloat16 program, float32 reference; logits
    # of size ~0.2 at these widths): sound 0.0 on three seeds (every served
    # token the reference's best), the float8 control 0.028 .. 0.048
    cfg["check"] = {"serve": {"served_logit_widest_gap": TOY_LIMIT}}
    with open(os.path.join(tmp, "benchmarks/configs/toy-laguna.json"),
              "w") as f:
        json.dump(cfg, f)
    mix = {"kind": "closed_loop", "clients": 4,
           "prompt_lengths": [16, 32, 48, 96], "output_lengths": [6, 10, 14],
           "stationary_start": True, "trace_seconds": 0.5,
           "check": {"requests": 3, "pad_to": 128}}
    with open(os.path.join(tmp, "benchmarks/traffic/toy-mixed.json"),
              "w") as f:
        json.dump(mix, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "toy-laguna", "source": "tests",
                         "file": "benchmarks/configs/toy-laguna.json",
                         "reduced": [], "why": "toy widths"}]
    bench["workloads"] = [{"name": "toy-mixed", "config": "toy-laguna",
                           "traffic": "toy-mixed", "chips": 1,
                           "why": "rehearsal"}]
    for group in ("end_to_end", "per_layer"):
        kept = []
        for m in bench[group]:
            if "workloads" in m:
                if CELL not in m["workloads"]:
                    continue
                m = dict(m, workloads=["toy-mixed"])
            kept.append(m)
        bench[group] = kept
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearsed_at_toy_widths(tmp_path, trace):
    """Through ``run_cell``: the family file, ``param_paths``, the weights
    made in the served dtypes, the engine, the window, the streamed check.
    Untraced: ``correct``, nothing failed or preempted, and the float8
    control is not correct. Traced: the program counters' metrics are on
    the line (the device ones need a device)."""
    root = _toy_root(str(tmp_path))
    out, err = io.StringIO(), io.StringIO()
    run.run_cell(argparse.Namespace(
        workload="toy-mixed", seed=3500000007 + trace, seconds=1.5,
        trace=trace, control="" if trace else "float8"),
        root=root, platforms=("cpu",), out=out, err=err)
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, err.getvalue()
    limit = TOY_LIMIT
    assert line["check"]["served_logit_widest_gap"] <= limit
    if trace:
        got = line["metrics"]
        assert got["preempt_per_req"]["value"] == 0
        assert 0 < got["moe_experts_hit_pct"]["value"] <= 100
        assert got["kv_cache_gb"]["value"] > 0
        assert got["decode_occupancy"]["value"] > 50
        # the window's first-token waits, under this cell's own readers
        assert 0 < got["first_token_p50_ms"]["value"] \
            <= got["first_token_p95_ms"]["value"]
    else:
        assert set(line["metrics"]) == {"serve_out_tokens_per_s",
                                        "itl_p95_ms", "setup_s"}
        assert line["control"]["check"]["served_logit_widest_gap"] > limit
