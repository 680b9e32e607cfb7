"""The ``lfm2-24b-a2b`` configuration and its cell
``lfm2-serve-chat-closed`` (ISSUE 44): the files load through the manifest,
state the cut the issue names, every published number is the catalog's, the
reference imports nothing of the program, and — at toy widths on the CPU,
through the same ``run_cell`` — the cell serves ``correct`` while the float8
control does not."""

import argparse
import io
import json
import os
import shutil
import sys

import pytest

from benchmarks import manifest as manifest_mod, run, traffic
from benchmarks.manifest import Manifest

from tests.benchmarks import toy

sys.path.insert(0, os.path.join(toy.ROOT, "tests"))
import conv_moe_toy  # noqa: E402

ROOT = toy.ROOT
CELL, CONFIG, TRAFFIC = ("lfm2-serve-chat-closed", "lfm2-24b-a2b",
                         "serve-chat-closed")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {
    # name: (unit, better, source, moves)
    "gqa_decode_roofline": ("%", "higher", "device_trace", "itl_p95_ms"),
    "moe_decode_roofline": ("%", "higher", "device_trace",
                            "serve_out_tokens_per_s"),
    "conv_decode_ms": ("ms", "lower", "program_span", "itl_p95_ms"),
    "conv_chunk_ms": ("ms", "lower", "program_span", "itl_p95_ms"),
}
JOINED = ("decode_occupancy", "preempt_per_req",
          "moe_serve_load_max_over_mean", "state_cache_gb")
END_TO_END = {"serve_out_tokens_per_s", "itl_p95_ms", "setup_s"}
REDUCED = {"num_hidden_layers": (40, 9), "num_dense_layers": (2, 1)}
TOY_LIMIT = 0.08


@pytest.fixture(scope="module")
def real():
    return Manifest(ROOT)


@pytest.fixture(autouse=True)
def own_expert_counters():
    """A rehearsal starts from zero and leaves zero behind
    (``gdn_mla_toy.zero_expert_counters`` has the reason)."""
    conv_moe_toy.zero_expert_counters()
    yield
    conv_moe_toy.zero_expert_counters()


def test_the_cells_files_load_and_state_the_cut(real):
    cell = real.cells[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    cfg, mix = real.config(CONFIG), real.traffic(TRAFFIC)
    entry = real.configs[CONFIG]
    assert entry["reduced"] == cfg["reduced"] == list(REDUCED)
    assert entry["source"] == cfg["source"]
    for key, (published, cut) in REDUCED.items():
        assert cfg["published"][key] == published and cfg[key] == cut, key
    # the cut in depth: published layer 0, then two whole periods
    kept = [cfg["layer_types"][i] for i in cfg["kept_layers"]]
    assert cfg["kept_layers"] == [0, 2, 3, 4, 5, 6, 7, 8, 9]
    assert kept == ["conv"] + ["full_attention", "conv", "conv", "conv"] * 2
    assert len(cfg["layer_types"]) == 40 and cfg["derived"]["kept_layers"]
    # every expert held: what the accepted expert counts read
    assert (cfg["router_experts"], cfg["num_experts"],
            cfg["num_experts_per_tok"]) == (64, 64, 4)
    for line in ("tied_head", "split_order", "route_sum_eps", "rope_form",
                 "intermediate_size"):
        assert cfg["assumed"][line], line
    assert "ONE PIPELINE STAGE ON ONE CHIP" in cfg["deployment"]
    assert cfg["check"]["why"] and cfg["bytes"]["parameters"] == 5177950976
    assert abs(cfg["bytes"]["served_bytes"] / 10.35e9 - 1) < 0.02
    # the traffic ISSUE 44 names, letter for letter
    assert mix == {**mix, "kind": "closed_loop", "clients": 256,
                   "prompt_lengths": [128, 256, 256, 512, 512, 1024],
                   "output_lengths": [512, 1024, 2048],
                   "stationary_start": True, "trace_seconds": 5,
                   "check": {"requests": 4, "pad_to": 3072}}
    over = dict(o.split("=") for o in cfg["serve"]["overrides"])
    chunk = int(over["Serving.prefill_chunk"])
    assert chunk == 512 and int(over["Serving.page_size"]) == 16
    assert int(over["Serving.max_batch"]) == mix["clients"] == 256
    assert (int(over["Serving.num_pages"]) - 1) * 16 == 524_288
    assert int(over["Serving.max_queue"]) == 0
    assert mix["check"]["pad_to"] == max(mix["prompt_lengths"]) + max(
        mix["output_lengths"])
    # longest prompt + longest output + the fill's lengthening (a chunk tick
    # for every chunk of the 255 prompts behind the first)
    gen = traffic.ClosedLoop(mix, 4400000001, 16)
    longest = max(len(p.prompt) + p.max_new for p in gen.first(chunk))
    assert longest <= int(over["Serving.max_seq_len"]) == 3584 \
        <= cfg["max_position_embeddings"]
    reported = {m["name"] for group in ("end_to_end", "per_layer")
                for m in real.metrics_of(CELL, group)}
    assert END_TO_END | set(JOINED) | set(NEW_METRICS) <= reported
    assert "ttft_mean_ms" not in reported
    assert real.family("ConvMoEModule") and real.reference_path("lfm2_ref")


def test_the_readers_are_on_the_cells_list(real):
    """Each with the cell ON its list — "in": which other cells a later PR
    appends is not this test's to pin —, a layer the manifest already had,
    a reader file, and the four new ones after every entry the parent had:
    the driver takes an entry put in the middle for a change to the one it
    displaced."""
    names = list(real.per_layer)
    older = {e["layer"] for n, e in real.per_layer.items()
             if n not in NEW_METRICS}
    for name, (unit, better, source, moves) in NEW_METRICS.items():
        entry = real.per_layer[name]
        assert CELL in entry["workloads"]
        assert (entry["unit"], entry["better"], entry["source"],
                entry["moves"]) == (unit, better, source, moves), name
        assert entry["layer"] in older
        assert names.index(name) > names.index("state_cache_gb")
        assert hasattr(manifest_mod.load_module(real.reader_path(name)),
                       "read")
    for name in JOINED:
        assert CELL in real.per_layer[name]["workloads"], name
    for name in ("serve_out_tokens_per_s", "itl_p95_ms"):
        assert CELL in real.end_to_end[name]["workloads"], name
    assert {"paged_decode", "moe_gmm_decode"} <= set(
        real.kernel_trace_names())


def test_the_readers_find_nothing_in_a_program_without_the_family(real):
    """On the parent (no kernel of these names in the trace, no ``conv``
    scope, no table of scopes at all) each new reader returns None and
    raises nothing; and none of them adds a host span."""
    ctx = argparse.Namespace(config={"serve": {"overrides": []}},
                             manifest=real, err=io.StringIO())
    facts = {"occupancy": [3], "context_tokens": [100], "slots": 4}
    trace = {"n_devices": 1, "ops": {}, "op_counts": {}, "modules": {}}
    for name in NEW_METRICS:
        reader = manifest_mod.load_module(real.reader_path(name))
        assert reader.read({}, facts, dict(trace), {"ctx": ctx}) is None, name
    # ... nor in a program whose scopes are another family's
    other = {"jit_decode": {"calls": 4, "by": {("gdn.core", "fwd"): 9.0}},
             "jit_prefill": {"calls": 1, "by": {("attn.core", "fwd"): 9.0}}}
    for name in ("conv_decode_ms", "conv_chunk_ms"):
        reader = manifest_mod.load_module(real.reader_path(name))
        assert reader.read({}, facts, dict(trace, _program_scopes=other),
                           {"ctx": ctx}) is None, name
    from fleetx_tpu.observability.trace import HOT_LOOP_SPANS

    assert len(HOT_LOOP_SPANS) == 15
    assert not [s for s in HOT_LOOP_SPANS if "conv" in s or "tail" in s]


def test_the_floors_are_the_accepted_counts_at_the_published_widths(real):
    """``gqa_decode_roofline`` and ``moe_decode_roofline`` against a trace
    made by hand: the accepted counts (``kernels/kv_decode.py``,
    ``kernels/moe_serve.py``) at 32 / 8 heads of 64 and 64 experts of 2,048
    x 1,536, over the kernel's own seconds."""
    ctx = argparse.Namespace(
        config=dict(real.config(CONFIG)), manifest=real, err=io.StringIO(),
        devices=[argparse.Namespace(device_kind="TPU v5 lite")])
    facts = {"occupancy": [256, 254], "context_tokens": [300_000, 320_000],
             "slots": 256}
    trace = {"n_devices": 1,
             "ops": {"kernel:paged_decode": 0.2, "kernel:moe_gmm_decode": 3.0,
                     "kernel:moe_gmm_prefill": 9.0},
             "op_counts": {"kernel:paged_decode": 200,
                           "kernel:moe_gmm_decode": 2400,
                           "kernel:moe_gmm_prefill": 600}}
    gqa = manifest_mod.load_module(real.reader_path(
        "gqa_decode_roofline")).read({}, facts, dict(trace), {"ctx": ctx})
    keys = 2 * 310_000 * 8 * 64 * 2 + 2 * 255 * 32 * 64 * 2
    assert gqa == pytest.approx(100 * 200 * (keys / 819e9) / 0.2)
    moe = manifest_mod.load_module(real.reader_path(
        "moe_decode_roofline")).read({}, facts, dict(trace), {"ctx": ctx})
    hit = 64 * (1 - (1 - 4 / 64) ** 255)
    one = (255 * 4 * (2048 + 1536) + hit * 2048 * 1536) * 2
    assert moe == pytest.approx(100 * 2400 * (one / 819e9) / 3.0)
    assert 0 < gqa < 100 and 0 < moe < 100


def test_two_seeds_offer_the_same_work(real):
    mix = real.traffic(TRAFFIC)
    a = traffic.offered_work(mix, 600, 4400000001)
    b = traffic.offered_work(mix, 600, 4400000002)
    assert a == b and a["tokens"] == 100 * (2688 + 2 * 3584)


def test_the_reference_imports_nothing_of_the_program(real):
    with open(real.reference_path("lfm2_ref")) as f:
        text = f.read()
    assert "import fleetx_tpu" not in text and "from fleetx_tpu" not in text
    imports = [ln for ln in text.splitlines()
               if ln.startswith(("import ", "from "))]
    assert all(ln.split()[1].split(".")[0] in
               {"__future__", "functools", "json", "math", "jax", "numpy"}
               for ln in imports), imports
    assert 'jax.lax.Precision.HIGHEST' in text and "lax.scan" in text


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_published_number_is_the_catalogs(real):
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-24B-A2B")
    cfg = real.config(CONFIG)
    assert cfg["source"] == row["source_url"] \
        == real.configs[CONFIG]["source"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    # the widths the issue names, unchanged
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts"], cfg["num_experts_per_tok"],
            cfg["conv_L_cache"], cfg["vocab_size"],
            cfg["rope_parameters"]["rope_theta"], cfg["norm_eps"]) == (
        2048, 32, 8, 64, 11776, 1536, 64, 4, 3, 65536, 1000000, 1e-5)
    assert row["head_dim"] is None


def _toy_root(tmp: str) -> str:
    """A rehearsal root whose one cell is the shipped cell's files at toy
    widths: the shipped configuration with toy published keys, toy
    ``Model.*`` overrides and a small engine, a small mix of the same
    kind."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(tmp, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "benchmarks/configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    cfg.update(conv_moe_toy.PUBLISHED)
    cfg.update(kept_layers=list(range(9)), router_experts=8,
               max_position_embeddings=512)
    model = conv_moe_toy.model_section(dtype="bfloat16")
    cfg["serve"]["overrides"] = [
        f"Model.{k}={v if isinstance(v, bool) else json.dumps(v)}"
        for k, v in model.items() if k != "module"] + [
        "Serving.max_batch=4", "Serving.page_size=8", "Serving.num_pages=129",
        "Serving.max_seq_len=256", "Serving.prefill_chunk=8",
        "Serving.max_queue=0", "Serving.paged_kernel=False"]
    # toy readings on the CPU (bfloat16 program, float32 reference; logits
    # of standard deviation ~0.5): sound 0.000 / 0.003 / 0.011 / 0.018 on
    # four seeds, the float8 control 0.36 / 0.41 / 0.47 / 0.47: the limit at
    # their geometric middle
    cfg["check"] = {"serve": {"served_logit_widest_gap": TOY_LIMIT}}
    with open(os.path.join(tmp, "benchmarks/configs/toy-lfm2.json"),
              "w") as f:
        json.dump(cfg, f)
    mix = {"kind": "closed_loop", "clients": 4,
           "prompt_lengths": [9, 16, 18, 33], "output_lengths": [6, 10, 14],
           "stationary_start": True, "trace_seconds": 0.5,
           "check": {"requests": 3, "pad_to": 128}}
    with open(os.path.join(tmp, "benchmarks/traffic/toy-chat.json"),
              "w") as f:
        json.dump(mix, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "toy-lfm2", "source": "tests",
                         "file": "benchmarks/configs/toy-lfm2.json",
                         "reduced": [], "why": "toy widths"}]
    bench["workloads"] = [{"name": "toy-chat", "config": "toy-lfm2",
                           "traffic": "toy-chat", "chips": 1,
                           "why": "rehearsal"}]
    for group in ("end_to_end", "per_layer"):
        kept = []
        for m in bench[group]:
            if "workloads" in m:
                if CELL not in m["workloads"]:
                    continue
                m = dict(m, workloads=["toy-chat"])
            kept.append(m)
        bench[group] = kept
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearsed_at_toy_widths(tmp_path, trace):
    """Through ``run_cell``: the family file unedited, ``param_paths``, the
    weights made in the served dtypes, the engine, prefill in chunks then
    decode through the pool and the tails, the streamed check. Untraced,
    with ``--control float8``: ``correct``, nothing failed or preempted,
    and the float8 control is not correct. Traced: the program counters'
    metrics are on the line (the device ones need a device)."""
    root = _toy_root(str(tmp_path))
    out, err = io.StringIO(), io.StringIO()
    line = run.run_cell(argparse.Namespace(
        workload="toy-chat", seed=4400000007 + trace, seconds=2.5,
        trace=trace, control="" if trace else "float8"),
        root=root, platforms=("cpu",), out=out, err=err)
    assert line == json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, err.getvalue()
    assert line["check"]["served_logit_widest_gap"] <= TOY_LIMIT
    if trace:
        got = line["metrics"]
        assert got["preempt_per_req"]["value"] == 0
        assert got["decode_occupancy"]["value"] > 50
        assert got["moe_serve_load_max_over_mean"]["value"] >= 1.0
        # 4 slots x 7 layers x a tail of 2 x 512 values of bfloat16
        assert got["state_cache_gb"]["value"] == pytest.approx(
            4 * 7 * 2 * 512 * 2 / 1e9)
        assert not set(got) & set(NEW_METRICS)
    else:
        assert set(line["metrics"]) == END_TO_END
        assert line["control"]["check"]["served_logit_widest_gap"] \
            > TOY_LIMIT
