"""The ``phi-4-mini-flash-reasoning`` configuration and its cell
``phi4flash-serve-longreason-closed`` (ISSUE 48): the files load through the
manifest, state the one cut the issue names, every published number is the
catalog's, the reference imports nothing of the program, the readers read a
trace made by hand and nothing off the device, and — at toy widths on the
CPU, through the same ``run_cell`` — the cell serves ``correct`` while the
float8 control does not."""

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
import samba_y_toy  # noqa: E402

ROOT = toy.ROOT
CELL, CONFIG, TRAFFIC = ("phi4flash-serve-longreason-closed",
                         "phi-4-mini-flash-reasoning",
                         "serve-longreason-closed")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {
    # name: (unit, better, source)
    "ssm_decode_roofline": ("%", "higher", "device_trace"),
    "ssm_chunk_roofline": ("%", "higher", "device_trace"),
    "shared_kv_walk_roofline": ("%", "higher", "device_trace"),
    "ssm_decode_ms": ("ms", "lower", "program_span"),
    "ssm_chunk_ms": ("ms", "lower", "program_span"),
    "gmu_decode_ms": ("ms", "lower", "program_span"),
    "cross_decode_ms": ("ms", "lower", "program_span"),
    "prefill_cross_ms": ("ms", "lower", "program_span"),
}
JOINED = ("state_cache_gb",)
# (not ``serve_out_tokens_per_s``: five seeds on the chip spread 3.5 %, over
# the 2.5 % the driver admits a new cell at — a window's chunk count follows
# which prompts arrive, as in ``gigachat35-serve-longgen-closed``; so not
# ``decode_occupancy`` and ``preempt_per_req`` either, which move it)
END_TO_END = {"itl_p95_ms", "setup_s"}
TOY_LIMIT = 0.006


@pytest.fixture(scope="module")
def real():
    return Manifest(ROOT)


@pytest.fixture(autouse=True)
def own_counters():
    """A rehearsal starts from zero and leaves zero behind: the registry is
    the process's, and other cells' rehearsals read its counters whole."""
    def zero():
        from fleetx_tpu.observability.metrics import get_registry

        get_registry().counter("serving_requests_preempted").reset()
    zero()
    yield
    zero()


def test_the_cells_files_load_and_state_the_cut(real):
    cell = real.cells[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    assert list(real.cells)[-1] == CELL and list(real.configs)[-1] == CONFIG
    cfg, mix = real.config(CONFIG), real.traffic(TRAFFIC)
    entry = real.configs[CONFIG]
    assert entry["reduced"] == cfg["reduced"] == ["vocab_size"]
    assert entry["source"] == cfg["source"]
    # the one cut: an eighth of the vocabulary, the guide's floor, and the
    # reason written beside the published value
    assert cfg["published"]["vocab_size"] == 200_064
    assert cfg["vocab_size"] == 25_008 == 200_064 // 8
    assert "check" in cfg["published"]["why"] and "WHOLE ON ONE CHIP" in \
        cfg["deployment"]
    assert (cfg["num_hidden_layers"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"]) == (32, 40, 20, 64)
    for line in ("d_state", "d_conv", "expand", "dt_rank", "scan_biases",
                 "attention_biases", "window", "split_order", "memory",
                 "lambda", "weights"):
        assert cfg["assumed"][line], line
    assert (cfg["assumed"]["d_state"], cfg["assumed"]["d_conv"],
            cfg["assumed"]["expand"], cfg["assumed"]["dt_rank"]) == (
        16, 4, 2, 160)
    assert cfg["check"]["why"] and cfg["bytes"]["parameters"] == 3404419584
    assert abs(cfg["bytes"]["served_bytes"] / 6.80e9 - 1) < 0.01
    # the traffic ISSUE 48 names, letter for letter
    assert mix == {**mix, "kind": "closed_loop", "clients": 64,
                   "prompt_lengths": [2048, 4096, 4096, 8192, 8192, 16384],
                   "output_lengths": [2048, 4096, 8192],
                   "stationary_start": True, "trace_seconds": 5,
                   "check": {"requests": 4, "pad_to": 24576}}
    over = dict(o.split("=") for o in cfg["serve"]["overrides"])
    chunk = int(over["Serving.prefill_chunk"])
    assert chunk == 512 and int(over["Serving.page_size"]) == 16
    assert int(over["Serving.max_batch"]) == mix["clients"] == 64
    assert (int(over["Serving.num_pages"]) - 1) * 16 >= 655_360
    assert int(over["Serving.max_queue"]) == 0
    assert int(over["Model.vocab_size"]) == cfg["vocab_size"]
    assert mix["check"]["pad_to"] == max(mix["prompt_lengths"]) + max(
        mix["output_lengths"])
    # longest prompt + longest output + the fill's lengthening (a chunk tick
    # for every chunk of the 63 prompts behind the first)
    for seed in (4800000001, 4800000002, 3):
        gen = traffic.ClosedLoop(mix, seed, 16)
        longest = max(len(p.prompt) + p.max_new for p in gen.first(chunk))
        assert longest <= int(over["Serving.max_seq_len"]) == 25_600 \
            <= cfg["max_position_embeddings"]
    # ... at its worst: the longest request first, every other prompt behind
    chunks = sum(-(-n // chunk) for n in mix["prompt_lengths"])
    assert 16_384 + 8_192 + chunks * -(-mix["clients"] // 6) <= 25_600
    reported = {m["name"] for group in ("end_to_end", "per_layer")
                for m in real.metrics_of(CELL, group)}
    assert END_TO_END | set(JOINED) | set(NEW_METRICS) <= reported
    assert not {"ttft_mean_ms", "serve_out_tokens_per_s"} & reported
    assert real.family("SambaYModule") and \
        real.reference_path("phi4flash_ref")


def test_the_readers_are_on_the_cells_list_and_stand_last(real):
    """Each with the cell alone on its list, a layer the manifest already
    had, a reader file, and the eight after every entry the parent had."""
    names = list(real.per_layer)
    assert names[-len(NEW_METRICS):] == list(NEW_METRICS)
    older = {e["layer"] for n, e in real.per_layer.items()
             if n not in NEW_METRICS}
    for name, (unit, better, source) in NEW_METRICS.items():
        entry = real.per_layer[name]
        assert CELL in entry["workloads"]
        assert (entry["unit"], entry["better"], entry["source"],
                entry["moves"]) == (unit, better, source, "itl_p95_ms"), name
        assert entry["layer"] in older
        assert hasattr(manifest_mod.load_module(real.reader_path(name)),
                       "read")
    for name in JOINED:
        assert CELL in real.per_layer[name]["workloads"], name
    assert real.end_to_end["itl_p95_ms"]["workloads"][-1] == CELL
    assert CELL not in real.end_to_end["serve_out_tokens_per_s"]["workloads"]
    assert {"ssm_decode", "ssm_chunk", "paged_decode"} <= set(
        real.kernel_trace_names())


def test_the_readers_find_nothing_in_a_program_without_the_family(real):
    """On the parent (no kernel of these names in the trace, no ``ssm``,
    ``gmu`` or ``attn.cross`` scope, no table of scopes at all) each new reader returns None and raises nothing; so
    does each in another family's cell; and none adds a host span."""
    ctx = argparse.Namespace(config={"serve": {"overrides": []}},
                             manifest=real, err=io.StringIO())
    facts = {"occupancy": [3], "context_tokens": [100], "slots": 4}
    trace = {"n_devices": 1, "ops": {}, "op_counts": {}, "modules": {}}
    for name in NEW_METRICS:
        reader = manifest_mod.load_module(real.reader_path(name))
        assert reader.read({}, facts, dict(trace), {"ctx": ctx}) is None, name
    other = {"jit_decode": {"calls": 4, "by": {("gdn.core", "fwd"): 9.0}},
             "jit_prefill": {"calls": 1, "by": {("attn.core", "fwd"): 9.0}}}
    for name in ("ssm_decode_ms", "ssm_chunk_ms", "gmu_decode_ms",
                 "cross_decode_ms", "prefill_cross_ms"):
        reader = manifest_mod.load_module(real.reader_path(name))
        assert reader.read({}, facts, dict(trace, _program_scopes=other),
                           {"ctx": ctx}) is None, name
    # another family's cell with ``paged_decode`` in its trace: not this
    # reader's walk
    ctx.config = dict(real.config("lfm2-24b-a2b"))
    walk = manifest_mod.load_module(
        real.reader_path("shared_kv_walk_roofline"))
    assert walk.read({}, facts, dict(
        trace, ops={"kernel:paged_decode": 0.2},
        op_counts={"kernel:paged_decode": 20},
        modules={"jit_decode": [10, 0.5]}), {"ctx": ctx}) is None
    from fleetx_tpu.observability.trace import HOT_LOOP_SPANS

    assert len(HOT_LOOP_SPANS) == 15


def test_the_floors_are_the_counts_at_the_published_widths(real):
    """The three rooflines against a trace made by hand: the count
    functions at 5,120 channels x 16 states and 40 / 20 heads of 64, over
    the kernels' own seconds (the chunk's: the ``ssm.core`` scope's)."""
    ctx = argparse.Namespace(
        config=dict(real.config(CONFIG)), manifest=real, err=io.StringIO(),
        devices=[argparse.Namespace(device_kind="TPU v5 lite")])
    facts = {"occupancy": [64, 62], "context_tokens": [640_000, 660_000],
             "slots": 64}
    scopes = {"jit_prefill": {"calls": 20, "by": {
        ("ssm.core", "fwd"): 60_000.0, ("ssm.proj", "fwd"): 5.0}}}
    trace = {"n_devices": 1, "_program_scopes": scopes,
             "modules": {"jit_decode": [100, 6.0], "jit_prefill": [20, 0.5]},
             "ops": {"kernel:ssm_decode": 0.06, "kernel:ssm_chunk": 0.04,
                     "kernel:paged_decode": 5.0,
                     "kernel:paged_decode_window": 0.3},
             "op_counts": {"kernel:ssm_decode": 900, "kernel:ssm_chunk": 180,
                           "kernel:paged_decode": 940,
                           "kernel:paged_decode_window": 800}}
    read = lambda name: manifest_mod.load_module(  # noqa: E731
        real.reader_path(name)).read({}, facts, dict(trace), {"ctx": ctx})
    state = 16 * 5120 * 4
    one = 63 * (2 * state + (3 * 5120 + 32) * 4) + state + 5120 * 4
    assert read("ssm_decode_roofline") == pytest.approx(
        100 * 900 * (one / 819e9) / 0.06)
    one = 3 * state + 5120 * 4 + 512 * (3 * 5120 + 32) * 4
    assert read("ssm_chunk_roofline") == pytest.approx(
        100 * 180 * (one / 819e9) / 0.06)
    # 100 decode steps x 8 reading layers, 5,120 B a token of live context
    one = 650_000 * 5120 + 63 * 40 * 64 * (2 + 8)
    assert read("shared_kv_walk_roofline") == pytest.approx(
        100 * 800 * (one / 819e9) / 5.0)
    for name in ("ssm_decode_roofline", "ssm_chunk_roofline",
                 "shared_kv_walk_roofline"):
        assert 0 < read(name) < 100, name
    # ... and the upper half's mixers a chunk: both scopes of the prefill
    # program, its lower half's scopes left out
    scopes["jit_prefill"]["by"].update({("gmu", "fwd"): 9_600.0,
                                        ("attn.cross", "fwd"): 8_200.0})
    scopes["jit_prefill"]["leaf_us"] = 1e6
    assert read("prefill_cross_ms") == pytest.approx(17.8 / 20)


def test_two_seeds_offer_the_same_work(real):
    mix = real.traffic(TRAFFIC)
    a = traffic.offered_work(mix, 600, 4800000001)
    b = traffic.offered_work(mix, 600, 4800000002)
    assert a == b and a["tokens"] == 100 * (43008 + 2 * 14336)


def test_the_reference_imports_nothing_of_the_program(real):
    with open(real.reference_path("phi4flash_ref")) as f:
        text = f.read()
    assert "import fleetx_tpu" not in text and "from fleetx_tpu" not in text
    imports = [ln for ln in text.splitlines()
               if ln.startswith(("import ", "from "))]
    assert all(ln.split()[1].split(".")[0] in
               {"__future__", "functools", "json", "math", "jax"}
               for ln in imports), imports
    assert 'jax.lax.Precision.HIGHEST' in text and "lax.scan" in text
    # every equation of the issue has its line
    for piece in ("jax.nn.softplus", "lam * o[", 'lw["subln"]',
                  "(1.0 - lam0)", 'lw["D"] * x_t', 'carried["m"]',
                  "0.8 - 0.6 * jnp.exp(-0.3 * published)"):
        assert piece in text, piece


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_published_number_is_the_catalogs(real):
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Phi-4-mini-flash-reasoning")
    cfg = real.config(CONFIG)
    assert cfg["source"] == row["source_url"] \
        == real.configs[CONFIG]["source"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert row["head_dim"] is None and cfg["head_dim"] == 64


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
    cfg.update(samba_y_toy.PUBLISHED)
    cfg.update(max_position_embeddings=512,
               assumed=dict(cfg["assumed"], **samba_y_toy.ASSUMED))
    model = samba_y_toy.model_section(dtype="bfloat16")
    cfg["serve"]["overrides"] = [
        f"Model.{k}={v if isinstance(v, bool) else json.dumps(v)}"
        for k, v in model.items() if k not in ("module", "hidden_act")] + [
        "Serving.max_batch=4", "Serving.page_size=8", "Serving.num_pages=129",
        "Serving.max_seq_len=256", "Serving.prefill_chunk=8",
        "Serving.max_queue=0", "Serving.paged_kernel=False"]
    # toy readings on the CPU (bfloat16 program, float32 reference; logits
    # of standard deviation ~0.17): sound 0.000 on four seeds (every served
    # token the reference's own first), the float8 control 0.010 / 0.018 /
    # 0.018 / 0.024: the limit between them
    cfg["check"] = {"serve": {"served_logit_widest_gap": TOY_LIMIT}}
    with open(os.path.join(tmp, "benchmarks/configs/toy-phi4flash.json"),
              "w") as f:
        json.dump(cfg, f)
    mix = {"kind": "closed_loop", "clients": 4,
           "prompt_lengths": [9, 16, 18, 33], "output_lengths": [6, 10, 14],
           "stationary_start": True, "trace_seconds": 0.5,
           "check": {"requests": 3, "pad_to": 128}}
    with open(os.path.join(tmp, "benchmarks/traffic/toy-reason.json"),
              "w") as f:
        json.dump(mix, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "toy-phi4flash", "source": "tests",
                         "file": "benchmarks/configs/toy-phi4flash.json",
                         "reduced": [], "why": "toy widths"}]
    bench["workloads"] = [{"name": "toy-reason", "config": "toy-phi4flash",
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
    weights made in the served dtypes, the engine, prefill in chunks (the
    upper half on one row) then decode through pool, rings, states and
    tails, the streamed check. Untraced, with ``--control float8``:
    ``correct``, nothing failed or preempted, and the float8 control is not
    correct. Traced: the gauge's metric is on the line (the device ones
    need a device)."""
    root = _toy_root(str(tmp_path))
    out, err = io.StringIO(), io.StringIO()
    line = run.run_cell(argparse.Namespace(
        workload="toy-reason", seed=4800000007 + trace, seconds=2.5,
        trace=trace, control="" if trace else "float8"),
        root=root, platforms=("cpu",), out=out, err=err)
    assert line == json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, err.getvalue()
    assert line["check"]["served_logit_widest_gap"] <= TOY_LIMIT
    if trace:
        got = line["metrics"]
        # 4 slots x 3 scan layers x (a float32 state of 8 x 128 + a
        # bfloat16 tail of 3 x 128)
        assert got["state_cache_gb"]["value"] == pytest.approx(
            4 * 3 * (8 * 128 * 4 + 3 * 128 * 2) / 1e9)
        assert not set(got) & set(NEW_METRICS)
    else:
        assert set(line["metrics"]) == END_TO_END
        assert line["control"]["check"]["served_logit_widest_gap"] \
            > TOY_LIMIT
