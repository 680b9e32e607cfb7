"""The ``ai21-jamba2-3b`` configuration and its cell
``jamba2-serve-packedreason-closed`` (ISSUE 51): the files load through the
manifest and state that NOTHING is cut, every published number is the
catalog's, the reference imports nothing of the program nor of another
reference, the readers read a trace made by hand and nothing off the device,
a CPU count of the scheduler holds the pool's size, and — at toy widths on
the CPU, through the same ``run_cell`` — the cell serves ``correct`` while
the float8 control does not.

The cell's lists are pinned by MEMBERSHIP, and the order of this PR's own
entries among themselves: never "stands last", so the next cell does not
fail this file."""

import argparse
import collections
import io
import json
import math
import os
import shutil
import sys

import pytest

from benchmarks import manifest as manifest_mod, run, traffic
from benchmarks.manifest import Manifest

from tests.benchmarks import toy

sys.path.insert(0, os.path.join(toy.ROOT, "tests"))
import ssm_mqa_toy  # noqa: E402

ROOT = toy.ROOT
CELL, CONFIG, TRAFFIC = ("jamba2-serve-packedreason-closed",
                         "ai21-jamba2-3b", "serve-packedreason-closed")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {
    # name: (unit, better, source)
    "hybrid_ssm_decode_roofline": ("%", "higher", "device_trace"),
    "hybrid_ssm_chunk_roofline": ("%", "higher", "device_trace"),
    "mqa_decode_roofline": ("%", "higher", "device_trace"),
    "hybrid_ssm_decode_ms": ("ms", "lower", "program_span"),
    "hybrid_ssm_chunk_ms": ("ms", "lower", "program_span"),
    "mqa_decode_ms": ("ms", "lower", "program_span"),
    "ssm_norm_ms": ("ms", "lower", "program_span"),
}
JOINED = ("state_cache_gb", "decode_occupancy", "preempt_per_req")
END_TO_END = {"serve_out_tokens_per_s", "itl_p95_ms", "setup_s"}
TOY_LIMIT = 0.004
#: the toy rehearsal's widths: at 64 the token's own embedding rules its
#: logits through the tied head and no rounding moves a choice
TOY_WIDTHS = {"hidden_size": 128, "intermediate_size": 256}


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


def test_the_cells_files_load_and_state_that_nothing_is_cut(real):
    cell = real.cells[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    cfg, mix = real.config(CONFIG), real.traffic(TRAFFIC)
    entry = real.configs[CONFIG]
    assert entry["reduced"] == cfg["reduced"] == []
    assert entry["source"] == cfg["source"]
    assert "NOTHING IS CUT" in cfg["deployment"]
    assert (cfg["num_hidden_layers"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["vocab_size"]) == (28, 20, 1, 128, 65_536)
    for line in ("layer_order", "head_dim", "split_order", "inner_norms",
                 "scan_biases", "attention", "weights", "eos_token_id",
                 "max_seq_len"):
        assert cfg["assumed"][line], line
    assert cfg["check"]["why"]
    assert cfg["bytes"]["parameters"] == 3_029_337_472
    assert cfg["bytes"]["served_bytes"] == 6_064_035_328
    # the traffic ISSUE 51 names, letter for letter
    assert mix == {**mix, "kind": "closed_loop", "clients": 256,
                   "prompt_lengths": [1024, 2048, 2048, 4096, 4096, 8192],
                   "output_lengths": [2048, 4096, 8192],
                   "stationary_start": True, "trace_seconds": 5,
                   "check": {"requests": 4, "pad_to": 16384}}
    over = dict(o.split("=") for o in cfg["serve"]["overrides"])
    assert not [k for k in over if k.startswith("Model.")]
    chunk, page = int(over["Serving.prefill_chunk"]), \
        int(over["Serving.page_size"])
    assert chunk == 512 and page == 128 and cfg["serve"]["page_size"]
    assert int(over["Serving.max_batch"]) == mix["clients"] == 256
    assert (int(over["Serving.num_pages"]) - 1) * page >= 2_600_000
    assert int(over["Serving.max_queue"]) == 0
    assert mix["check"]["pad_to"] == max(mix["prompt_lengths"]) + max(
        mix["output_lengths"])
    # longest prompt + longest output + the fill's lengthening (a chunk tick
    # for every chunk of the 255 prompts behind the first)
    for seed in (5100000001, 5100000002, 3):
        gen = traffic.ClosedLoop(mix, seed, 16)
        longest = max(len(p.prompt) + p.max_new for p in gen.first(chunk))
        assert longest <= int(over["Serving.max_seq_len"]) == 18_432 \
            <= cfg["max_position_embeddings"]
    # ... at its worst: the longest request first, every other prompt behind
    chunks = sum(-(-n // chunk) for n in mix["prompt_lengths"])
    assert 8_192 + 8_192 + chunks * -(-mix["clients"] // 6) <= 18_432
    reported = {m["name"] for group in ("end_to_end", "per_layer")
                for m in real.metrics_of(CELL, group)}
    assert END_TO_END | set(JOINED) | set(NEW_METRICS) <= reported
    assert "ttft_mean_ms" not in reported
    assert real.family("SSMMQAModule") and \
        real.reference_path("jamba2_ref")


def test_the_readers_are_on_the_cells_list(real):
    """Each with the cell on its list, a layer the manifest already had, a
    reader file; the seven in the issue's order among themselves; the cell
    joins no list another file pins to its end."""
    names = [n for n in real.per_layer if n in NEW_METRICS]
    assert names == list(NEW_METRICS)
    older = {e["layer"] for n, e in real.per_layer.items()
             if n not in NEW_METRICS}
    for name, (unit, better, source) in NEW_METRICS.items():
        entry = real.per_layer[name]
        assert entry["workloads"] == [CELL]
        assert (entry["unit"], entry["better"], entry["source"],
                entry["moves"]) == (unit, better, source, "itl_p95_ms"), name
        assert entry["layer"] in older
        assert hasattr(manifest_mod.load_module(real.reader_path(name)),
                       "read")
    for name in JOINED:
        assert CELL in real.per_layer[name]["workloads"], name
    for name in ("serve_out_tokens_per_s", "itl_p95_ms"):
        assert CELL in real.end_to_end[name]["workloads"], name
    for pinned in ("decode_step_ms", "pool_copy_ms", "tick_host_ms",
                   "tick_idle_ms", "moe_serve_passes_per_layer",
                   "serve_unscoped_pct", "decode_attn_ms"):
        assert CELL not in real.per_layer[pinned]["workloads"], pinned
    assert {"ssm_decode", "ssm_chunk", "paged_decode"} <= set(
        real.kernel_trace_names())


def test_the_readers_find_nothing_in_a_program_without_the_family(real):
    """On the parent (no kernel of these names in the trace, no ``ssm``
    scope, no table of scopes at all) each new reader returns None and
    raises nothing; so does each in another family's cell — the fifth
    family's, which has the scan's kernels and scopes, among them; and none
    adds a host span."""
    ctx = argparse.Namespace(config={"serve": {"overrides": []}},
                             manifest=real, err=io.StringIO())
    facts = {"occupancy": [3], "context_tokens": [100], "slots": 4}
    trace = {"n_devices": 1, "ops": {}, "op_counts": {}, "modules": {}}
    for name in NEW_METRICS:
        reader = manifest_mod.load_module(real.reader_path(name))
        assert reader.read({}, facts, dict(trace), {"ctx": ctx}) is None, name
    other = {"jit_decode": {"calls": 4, "by": {("gdn.core", "fwd"): 9.0}},
             "jit_prefill": {"calls": 1, "by": {("attn.core", "fwd"): 9.0}}}
    ctx.config = dict(real.config(CONFIG))
    for name in ("hybrid_ssm_decode_ms", "hybrid_ssm_chunk_ms",
                 "ssm_norm_ms"):
        reader = manifest_mod.load_module(real.reader_path(name))
        assert reader.read({}, facts, dict(trace, _program_scopes=other),
                           {"ctx": ctx}) is None, name
    # the fifth family's cell, with the scan's kernels, ``paged_decode`` and
    # the ``ssm.*`` and ``attn.*`` scopes in its trace: not these readers'
    ctx.config = dict(real.config("phi-4-mini-flash-reasoning"))
    ctx.devices = [argparse.Namespace(device_kind="TPU v5 lite")]
    scan = {"jit_decode": {"calls": 4, "by": {("ssm.core", "fwd"): 9.0,
                                              ("attn.core", "fwd"): 9.0}},
            "jit_prefill": {"calls": 1, "by": {("ssm.core", "fwd"): 9.0}}}
    theirs = dict(trace, _program_scopes=scan,
                  ops={"kernel:paged_decode": 0.2, "kernel:ssm_decode": 0.1,
                       "kernel:ssm_chunk": 0.1},
                  op_counts={"kernel:paged_decode": 20,
                             "kernel:ssm_decode": 20, "kernel:ssm_chunk": 9})
    for name in NEW_METRICS:
        if name == "ssm_norm_ms":
            continue        # reads a scope that program does not open
        reader = manifest_mod.load_module(real.reader_path(name))
        assert reader.read({}, facts, dict(theirs), {"ctx": ctx}) is None, \
            name
    from fleetx_tpu.observability.trace import HOT_LOOP_SPANS

    assert len(HOT_LOOP_SPANS) == 15


def test_the_floors_are_the_counts_at_the_published_widths(real):
    """The three rooflines against a trace made by hand: the count
    functions the benchmark already kept, at 5,120 channels x 16 states and
    20 query heads over one key-value head of 128, over the kernels' own
    seconds (the chunk's: the ``ssm.core`` scope's) — and the four scope
    readers."""
    ctx = argparse.Namespace(
        config=dict(real.config(CONFIG)), manifest=real, err=io.StringIO(),
        devices=[argparse.Namespace(device_kind="TPU v5 lite")])
    facts = {"occupancy": [256, 254], "context_tokens": [1_690_000,
                                                         1_710_000],
             "slots": 256}
    scopes = {"jit_prefill": {"calls": 20, "by": {
        ("ssm.core", "fwd"): 110_000.0, ("ssm.proj", "fwd"): 50_000.0,
        ("attn.core", "fwd"): 7.0}},
        "jit_decode": {"calls": 100, "by": {
            ("ssm.core", "fwd"): 700_000.0, ("ssm.norm", "fwd"): 26_000.0,
            ("ssm.proj", "fwd"): 300_000.0, ("attn.core", "fwd"): 500_000.0,
            ("attn.proj", "fwd"): 30_000.0, ("mlp", "fwd"): 1.0}}}
    trace = {"n_devices": 1, "_program_scopes": scopes,
             "modules": {"jit_decode": [100, 2.5], "jit_prefill": [20, 0.6]},
             "ops": {"kernel:ssm_decode": 0.7, "kernel:ssm_chunk": 0.09,
                     "kernel:paged_decode": 0.5},
             "op_counts": {"kernel:ssm_decode": 2600, "kernel:ssm_chunk": 520,
                           "kernel:paged_decode": 200}}
    read = lambda name: manifest_mod.load_module(  # noqa: E731
        real.reader_path(name)).read({}, facts, dict(trace), {"ctx": ctx})
    state = 16 * 5120 * 4
    one = 255 * (2 * state + (3 * 5120 + 32) * 4) + state + 5120 * 4
    assert read("hybrid_ssm_decode_roofline") == pytest.approx(
        100 * 2600 * (one / 819e9) / 0.7)
    one = 3 * state + 5120 * 4 + 512 * (3 * 5120 + 32) * 4
    assert read("hybrid_ssm_chunk_roofline") == pytest.approx(
        100 * 520 * (one / 819e9) / 0.11)
    # 100 decode steps x 2 attention layers, 512 B a token a layer (K and V,
    # one head of 128), the 20 query heads' rows in and out
    one = 1_700_000 * 2 * 128 * 2 + 2 * 255 * 20 * 128 * 2
    assert read("mqa_decode_roofline") == pytest.approx(
        100 * 200 * (one / 819e9) / 0.5)
    for name in ("hybrid_ssm_decode_roofline", "hybrid_ssm_chunk_roofline",
                 "mqa_decode_roofline"):
        assert 0 < read(name) < 100, name
    assert read("hybrid_ssm_decode_ms") == pytest.approx(10.26)
    assert read("hybrid_ssm_chunk_ms") == pytest.approx(8.0)
    assert read("mqa_decode_ms") == pytest.approx(5.3)
    assert read("ssm_norm_ms") == pytest.approx(0.26)


def test_two_seeds_offer_the_same_work(real):
    mix = real.traffic(TRAFFIC)
    a = traffic.offered_work(mix, 600, 5100000001)
    b = traffic.offered_work(mix, 600, 5100000002)
    assert a == b and a["tokens"] == 100 * (21504 + 2 * 14336)


def _count_the_scheduler(real, seed: int, ticks: int) -> dict:
    """The engine's policy over the cell's traffic with no device
    (``serving/engine.py``: lazy allocation with a watermark of one page,
    strict FIFO admission, one chunk a tick of the oldest prefilling
    request, a token a tick for every running row, a page grown when a row
    crosses into it): the pool's peak fill once the slots are full, the
    preemptions a dry pool would force, the share of ticks after the fill
    that carry a chunk."""
    over = dict(o.split("=")
                for o in real.config(CONFIG)["serve"]["overrides"])
    slots, ps = int(over["Serving.max_batch"]), int(over["Serving.page_size"])
    chunk = int(over["Serving.prefill_chunk"])
    usable = int(over["Serving.num_pages"]) - 1
    gen = traffic.ClosedLoop(real.traffic(TRAFFIC), seed, 16)
    waiting = collections.deque(
        (len(p.prompt), p.max_new, p.client) for p in gen.first(chunk))
    prefilling, running = collections.deque(), []
    free, peak, chunk_ticks, preempted, filled = usable, 0, 0, 0, None
    for t in range(ticks):
        while waiting and len(prefilling) + len(running) < slots:
            plen, new, client = waiting[0]
            need = min(math.ceil(plen / ps) + 1, math.ceil((plen + new) / ps))
            if need > free:
                break
            waiting.popleft()
            free -= need
            prefilling.append([plen, new, client, 0, need])
        if prefilling:
            r = prefilling[0]
            r[3] += chunk
            chunk_ticks += filled is not None
            if r[3] >= r[0]:
                prefilling.popleft()
                running.append([r[0], r[1] - 1, r[2], r[4]])
        still = []
        for r in running:       # [tokens held, tokens to go, client, pages]
            if r[1] <= 0:
                free += r[3]
                p = gen.next_for(r[2])
                waiting.append((len(p.prompt), p.max_new, p.client))
                continue
            need = math.ceil((r[0] + 1) / ps)
            if need > r[3]:
                preempted += free <= 0
                free -= need - r[3]
                r[3] = need
            r[0] += 1
            r[1] -= 1
            still.append(r)
        running = still
        if filled is None and not prefilling and not waiting:
            filled = t
        if filled is not None:
            peak = max(peak, usable - free)
    return {"peak_fill": peak / usable, "preempted": preempted,
            "chunk_share": chunk_ticks / (ticks - filled),
            "filled_at": filled}


@pytest.mark.parametrize("seed", [5100001, 5100007])
def test_the_pool_holds_the_traffic_with_no_preemption(real, seed):
    """ISSUE 51 section 3: a CPU count of the scheduler over this traffic —
    12,000 ticks, some eight times what a run lasts — fills the pool of
    2.62 M token slots to at most 75 % and preempts nobody (eight seeds read
    0.68–0.72: PERF.md section 6; two kept here); after the fill three
    ticks in eight carry a chunk; the fill is ~1,790 chunk ticks."""
    got = _count_the_scheduler(real, seed, 12_000)
    assert got["preempted"] == 0
    assert 0.6 < got["peak_fill"] <= 0.75, got
    assert 0.33 < got["chunk_share"] < 0.42, got
    assert 1_700 < got["filled_at"] < 1_900, got


def test_the_reference_imports_nothing_of_the_program(real):
    with open(real.reference_path("jamba2_ref")) as f:
        text = f.read()
    assert "import fleetx_tpu" not in text and "from fleetx_tpu" not in text
    assert "import phi4flash_ref" not in text and "benchmarks" not in \
        [ln.split()[1].split(".")[0] for ln in text.splitlines()
         if ln.startswith(("import ", "from "))]
    imports = [ln for ln in text.splitlines()
               if ln.startswith(("import ", "from "))]
    assert all(ln.split()[1].split(".")[0] in
               {"__future__", "functools", "json", "math", "jax"}
               for ln in imports), imports
    assert 'jax.lax.Precision.HIGHEST' in text and "lax.scan" in text
    assert "def logits_streamed(leaf," in text and "jax.lax.map" in text
    # every equation of the issue has its line
    for piece in ("jax.nn.softplus", 'lw["dt_norm_w"]', 'lw["b_norm_w"]',
                  'lw["c_norm_w"]', 'lw["D"] * x_t', "math.sqrt(hd)",
                  'lw["mlp_gate"]', 'lw["mlp_up"]'):
        assert piece in text, piece


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_published_number_is_the_catalogs(real):
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "AI21-Jamba2-3B")
    cfg = real.config(CONFIG)
    assert cfg["source"] == row["source_url"] \
        == real.configs[CONFIG]["source"]
    assert cfg["reduced"] == []
    for key, value in row["config"].items():
        assert key in cfg and cfg[key] == value, key
    assert row["head_dim"] is None and cfg["head_dim"] == 128
    assert (row["layers"], row["dense_width"]) == (
        cfg["num_hidden_layers"], cfg["intermediate_size"])


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
    cfg.update(ssm_mqa_toy.PUBLISHED, **TOY_WIDTHS)
    cfg.update(max_position_embeddings=512)
    model = ssm_mqa_toy.model_section(dtype="bfloat16", **TOY_WIDTHS)
    cfg["serve"]["overrides"] = [
        f"Model.{k}={v if isinstance(v, bool) else json.dumps(v)}"
        for k, v in model.items() if k not in ("module", "hidden_act")] + [
        "Serving.max_batch=4", "Serving.page_size=8", "Serving.num_pages=129",
        "Serving.max_seq_len=256", "Serving.prefill_chunk=8",
        "Serving.max_queue=0", "Serving.paged_kernel=False"]
    # toy readings on the CPU (bfloat16 program, float32 reference) are in
    # the rehearsal's docstring: the limit lies between them
    cfg["check"] = {"serve": {"served_logit_widest_gap": TOY_LIMIT}}
    with open(os.path.join(tmp, "benchmarks/configs/toy-jamba2.json"),
              "w") as f:
        json.dump(cfg, f)
    mix = {"kind": "closed_loop", "clients": 4,
           "prompt_lengths": [9, 16, 18, 33], "output_lengths": [6, 10, 14],
           "stationary_start": True, "trace_seconds": 0.5,
           "check": {"requests": 3, "pad_to": 128}}
    with open(os.path.join(tmp, "benchmarks/traffic/toy-packed.json"),
              "w") as f:
        json.dump(mix, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "toy-jamba2", "source": "tests",
                         "file": "benchmarks/configs/toy-jamba2.json",
                         "reduced": [], "why": "toy widths"}]
    bench["workloads"] = [{"name": "toy-packed", "config": "toy-jamba2",
                           "traffic": "toy-packed", "chips": 1,
                           "why": "rehearsal"}]
    for group in ("end_to_end", "per_layer"):
        kept = []
        for m in bench[group]:
            if "workloads" in m:
                if CELL not in m["workloads"]:
                    continue
                m = dict(m, workloads=["toy-packed"])
            kept.append(m)
        bench[group] = kept
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearsed_at_toy_widths(tmp_path, trace):
    """Through ``run_cell``: the family file unedited (its one scaled
    leaf), ``param_paths``, the weights made in the served dtypes, the
    engine, prefill in chunks then decode through pool, states and tails,
    the streamed check. Untraced, with ``--control float8``: ``correct``,
    nothing failed or preempted, and the float8 control is not correct.
    Traced: the gauge's and the counters' metrics are on the line (the
    device ones need a device)."""
    root = _toy_root(str(tmp_path))
    out, err = io.StringIO(), io.StringIO()
    line = run.run_cell(argparse.Namespace(
        workload="toy-packed", seed=5100000007 + trace, seconds=2.5,
        trace=trace, control="" if trace else "float8"),
        root=root, platforms=("cpu",), out=out, err=err)
    assert line == json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, err.getvalue()
    assert line["check"]["served_logit_widest_gap"] <= TOY_LIMIT
    if trace:
        got = line["metrics"]
        # 4 slots x 6 scan layers x (a float32 state of 8 x 256 + a
        # bfloat16 tail of 3 x 256)
        assert got["state_cache_gb"]["value"] == pytest.approx(
            4 * 6 * (8 * 256 * 4 + 3 * 256 * 2) / 1e9)
        assert got["preempt_per_req"]["value"] == 0
        assert not set(got) & set(NEW_METRICS)
    else:
        assert set(line["metrics"]) == END_TO_END
        assert line["control"]["check"]["served_logit_widest_gap"] \
            > TOY_LIMIT
