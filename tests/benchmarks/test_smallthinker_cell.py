"""The ``smallthinker-21b-a3b`` configuration and its cell
``smallthinker-serve-reason-closed`` (ISSUE 38): the files load through the
manifest, state the cut the issue names (depth alone), every published
number is the catalog's, and — at toy widths on the CPU, through the same
``run_cell`` — the cell serves ``correct`` while the float8 control does
not."""

import argparse
import io
import json
import os
import shutil

import pytest

from benchmarks import check, control_first, run
from benchmarks.manifest import Manifest

from tests.benchmarks import toy
from tests.benchmarks.test_benchmark_harness import _toy_served

ROOT = toy.ROOT
CELL, CONFIG, TRAFFIC = ("smallthinker-serve-reason-closed",
                         "smallthinker-21b-a3b", "serve-reason-closed")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("moe_serve_load_max_over_mean", "moe_serve_passes_per_layer")
APPENDED = ("decode_step_ms", "pool_copy_ms", "decode_occupancy",
            "preempt_per_req", "tick_host_ms", "tick_idle_ms")

TOY_WINDOW, TOY_LIMIT = 16, 0.01
# the published keys at toy widths, and the family's keys derived from them
TOY_PUBLISHED = {
    "vocab_size": 256, "hidden_size": 64, "head_dim": 16,
    "num_attention_heads": 14, "num_key_value_heads": 2,
    "sliding_window_size": TOY_WINDOW, "moe_num_primary_experts": 16,
    "moe_num_active_primary_experts": 3, "moe_ffn_hidden_size": 32}
TOY_MODEL = {
    "vocab_size": 256, "hidden_size": 64, "num_key_value_heads": 2,
    "head_dim": 16, "sliding_window": TOY_WINDOW,
    "num_attention_heads_per_layer": [14] * 8, "num_experts": 16,
    "experts_held": 16, "num_experts_per_tok": 3,
    "moe_intermediate_size": 32}


@pytest.fixture(scope="module")
def real():
    return Manifest(ROOT)


def test_the_cells_files_load_and_state_the_cut(real):
    cell = real.cells[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    cfg, mix = real.config(CONFIG), real.traffic(TRAFFIC)
    entry = real.configs[CONFIG]
    # the only cut is depth
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 52}
    assert cfg["num_hidden_layers"] == 8
    assert (cfg["num_experts"], cfg["router_experts"],
            cfg["num_experts_per_tok"], cfg["vocab_size"]) == (
        64, 64, 6, 151936)
    assert entry["source"] == cfg["source"]
    for line in ("activation", "router_input", "router_scoring",
                 "shared_expert", "window", "nope", "secondary_experts"):
        assert cfg["assumed"][line]
    for key in ("layer_types", "num_attention_heads_per_layer",
                "num_experts", "router_experts", "num_experts_per_tok",
                "moe_intermediate_size", "sliding_window"):
        assert cfg["derived"][key] and key in cfg
    assert "WHOLE ON ONE CHIP" in cfg["deployment"]
    assert cfg["check"]["why"] and cfg["bytes"]["parameters"] == 3966937600
    # the derived lists say what the published ones say
    assert cfg["layer_types"] == [
        "sliding_attention" if w else "full_attention"
        for w in cfg["sliding_window_layout"]]
    assert cfg["rope_layout"] == cfg["sliding_window_layout"]
    # the traffic ISSUE 38 names, letter for letter
    assert mix == {**mix, "kind": "closed_loop", "clients": 48,
                   "prompt_lengths": [4096, 4096, 6144, 8192],
                   "output_lengths": [2048, 3072, 4096],
                   "stationary_start": True, "trace_seconds": 5,
                   "check": {"requests": 4, "pad_to": 12288}}
    # every running row's ring is full: what kv_decode's floor assumes
    assert min(mix["prompt_lengths"]) >= cfg["sliding_window"] == 4096
    over = dict(o.split("=") for o in cfg["serve"]["overrides"])
    assert over["Serving.prefill_chunk"] == "512"
    assert int(over["Serving.max_batch"]) == mix["clients"] == 48
    # longest prompt + longest output + the fill's lengthening (a chunk
    # tick for every chunk of the 47 prompts behind the first)
    behind = 47 * max(mix["prompt_lengths"]) // 512
    assert max(mix["prompt_lengths"]) + max(mix["output_lengths"]) + behind \
        <= int(over["Serving.max_seq_len"]) == 13056 \
        <= cfg["max_position_embeddings"]
    assert mix["check"]["pad_to"] == max(mix["prompt_lengths"]) + max(
        mix["output_lengths"])
    reported = {m["name"] for group in ("end_to_end", "per_layer")
                for m in real.metrics_of(CELL, group)}
    assert {"serve_out_tokens_per_s", "itl_p95_ms", "setup_s", *APPENDED,
            *NEW_METRICS} <= reported
    assert "ttft_mean_ms" not in reported
    # the cell is ON each new reader's list; which other cells a later PR
    # appends there is not this test's to pin
    for name in NEW_METRICS:
        entry = real.per_layer[name]
        assert CELL in entry["workloads"]
        assert (entry["source"], entry["better"]) == ("program_counter",
                                                      "lower")
        assert entry["layer"] == real.per_layer["decode_step_ms"]["layer"]
    assert real.per_layer[NEW_METRICS[0]]["moves"] == "serve_out_tokens_per_s"
    assert real.per_layer[NEW_METRICS[1]]["moves"] == "itl_p95_ms"
    assert real.family("SWAMoEModule") and \
        real.reference_path("smallthinker_ref")


def test_the_reference_imports_nothing_of_the_program(real):
    with open(real.reference_path("smallthinker_ref")) as f:
        text = f.read()
    assert "fleetx_tpu" not in text.replace("``fleetx_tpu", "")
    imports = [ln for ln in text.splitlines()
               if ln.startswith(("import ", "from "))]
    assert all(ln.split()[1].split(".")[0] in
               {"__future__", "functools", "json", "math", "jax", "numpy"}
               for ln in imports), imports


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_published_number_is_the_catalogs(real):
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
    cfg = real.config(CONFIG)
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key


@pytest.mark.parametrize("chooser", [None, "float8"])
def test_the_control_taken_first_reads_the_harnesss_numbers(chooser):
    """``benchmarks/control_first.py`` (what the builder read the float8
    control of this cell with: two ``[12,288, 151,936]`` float32 logits do
    not fit the chip) settles the judged tokens before the float32 forward
    and gives ``check.served_logit_gaps``' numbers, sound and control; and
    its ``main`` leaves the harness's function in place."""
    ref, cfg, source, samples = _toy_served()
    want = check.served_logit_gaps(ref, cfg, source, samples, 128,
                                   chooser=chooser)
    got = control_first.served_logit_gaps(ref, cfg, source, samples, 128,
                                          chooser=chooser)
    assert got == want and got["tokens_compared"] == 164
    limit = cfg["check"]["serve"]["served_logit_widest_gap"]
    assert (got["widest_gap"] > limit) == (chooser == "float8")
    harness = check.served_logit_gaps
    with pytest.raises(SystemExit):
        control_first.main(["--workload", "no-such-cell", "--seed", "1",
                            "--seconds", "1"], err=io.StringIO())
    assert check.served_logit_gaps is harness


def _toy_root(tmp: str) -> str:
    """A rehearsal root whose one cell is the shipped cell's files at toy
    widths: the shipped configuration with toy published keys, toy
    ``Model.*`` overrides and a small engine, a small mix of the same kind
    whose prompts are all at least the window."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(tmp, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "benchmarks/configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    cfg.update(TOY_PUBLISHED)
    cfg.update({k: v for k, v in TOY_MODEL.items() if k in cfg})
    cfg.update(router_experts=16, max_position_embeddings=512,
               num_attention_heads_per_layer=[14] * 52)
    cfg["serve"]["overrides"] = [
        f"Model.{k}={json.dumps(v)}" for k, v in TOY_MODEL.items()] + [
        "Serving.max_batch=4", "Serving.page_size=8", "Serving.num_pages=129",
        "Serving.max_seq_len=256", "Serving.prefill_chunk=8",
        "Serving.max_queue=0"]
    # toy readings on the CPU (bfloat16 program, float32 reference; logits
    # of size ~0.2 at these widths): sound 0.0 on three seeds (every served
    # token the reference's best), the reference in bfloat16 0.0003 ..
    # 0.0014, the float8 control 0.017 .. 0.034
    cfg["check"] = {"serve": {"served_logit_widest_gap": TOY_LIMIT}}
    with open(os.path.join(tmp, "benchmarks/configs/toy-smallthinker.json"),
              "w") as f:
        json.dump(cfg, f)
    mix = {"kind": "closed_loop", "clients": 4,
           "prompt_lengths": [16, 16, 24, 40], "output_lengths": [6, 10, 14],
           "stationary_start": True, "trace_seconds": 0.5,
           "check": {"requests": 3, "pad_to": 128}}
    assert min(mix["prompt_lengths"]) >= TOY_WINDOW
    with open(os.path.join(tmp, "benchmarks/traffic/toy-reason.json"),
              "w") as f:
        json.dump(mix, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "toy-smallthinker", "source": "tests",
                         "file": "benchmarks/configs/toy-smallthinker.json",
                         "reduced": [], "why": "toy widths"}]
    bench["workloads"] = [{"name": "toy-reason",
                           "config": "toy-smallthinker",
                           "traffic": "toy-reason", "chips": 1,
                           "why": "rehearsal"}]
    for group in ("end_to_end", "per_layer"):
        kept = []
        for m in bench[group]:
            if "workloads" in m:
                if CELL not in m["workloads"]:
                    continue
                m = dict(m, workloads=["toy-reason"])
            kept.append(m)
        bench[group] = kept
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearsed_at_toy_widths(tmp_path, trace):
    """Through ``run_cell``: the family file unedited, ``param_paths``, the
    weights made in the served dtypes, the engine, a ring of 24 tokens that
    wraps and is folded a key block at a time, the window, the streamed
    check. Untraced, through ``benchmarks/control_first.py``: ``correct``,
    nothing failed or preempted, and the float8 control is not correct. Traced: the two new program counters'
    metrics are on the line (the device ones need a device)."""
    root = _toy_root(str(tmp_path))
    out, err = io.StringIO(), io.StringIO()
    if trace:
        run.run_cell(argparse.Namespace(
            workload="toy-reason", seed=3800000008, seconds=2.5, trace=1,
            control=""), root=root, platforms=("cpu",), out=out, err=err)
    else:       # as the builder read the control on the chip
        control_first.main(
            ["--workload", "toy-reason", "--seed", "3800000007", "--seconds",
             "2.5", "--trace", "0", "--control", "float8"],
            root=root, platforms=("cpu",), out=out, err=err)
        assert "counters: serving_decode_steps=" in err.getvalue()
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, err.getvalue()
    assert line["check"]["served_logit_widest_gap"] <= TOY_LIMIT
    if trace:
        got = line["metrics"]
        assert got["preempt_per_req"]["value"] == 0
        assert got["decode_occupancy"]["value"] > 50
        # up to 4 rows of 3 experts each over 16: between 16/12 and 16/3
        assert 1.0 <= got["moe_serve_load_max_over_mean"]["value"] <= 16 / 3
        # every fetched step took one pass in each of the 8 layers (a step
        # none of whose rows is live takes none)
        assert 0.9 < got["moe_serve_passes_per_layer"]["value"] <= 1.0
    else:
        assert set(line["metrics"]) == {"serve_out_tokens_per_s",
                                        "itl_p95_ms", "setup_s"}
        assert line["control"]["check"]["served_logit_widest_gap"] \
            > TOY_LIMIT
