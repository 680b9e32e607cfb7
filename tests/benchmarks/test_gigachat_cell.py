"""The ``gigachat3.5-432b-a28b`` configuration and its cell
``gigachat35-serve-longgen-closed`` (ISSUE 42): the files load through the
manifest, state the cut the issue names, every published number is the
catalog's, the reference imports nothing of the program, and — at toy
widths on the CPU, through the same ``run_cell`` — the cell serves
``correct`` while the float8 control does not."""

import argparse
import collections
import io
import json
import math
import os
import shutil
import sys

import pytest

from benchmarks import control_first, manifest as manifest_mod, run, traffic
from benchmarks.manifest import Manifest

from tests.benchmarks import toy

sys.path.insert(0, os.path.join(toy.ROOT, "tests"))
import gdn_mla_toy  # noqa: E402

ROOT = toy.ROOT
CELL, CONFIG, TRAFFIC = ("gigachat35-serve-longgen-closed",
                         "gigachat3.5-432b-a28b", "serve-longgen-closed")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {
    # name: (unit, better, source, moves)
    "gdn_decode_roofline": ("%", "higher", "device_trace", "itl_p95_ms"),
    "gdn_chunk_roofline": ("%", "higher", "device_trace", "itl_p95_ms"),
    "mla_decode_roofline": ("%", "higher", "device_trace", "itl_p95_ms"),
    "gdn_decode_ms": ("ms", "lower", "program_span", "itl_p95_ms"),
    "gdn_chunk_ms": ("ms", "lower", "program_span", "itl_p95_ms"),
    "state_cache_gb": ("GB", "higher", "program_counter", "itl_p95_ms"),
}
# (not ``decode_occupancy``, ``preempt_per_req`` and
# ``moe_serve_load_max_over_mean``: they move ``serve_out_tokens_per_s``,
# which this cell does not report — below)
APPENDED = ("decode_step_ms", "pool_copy_ms", "tick_host_ms", "tick_idle_ms",
            "moe_serve_passes_per_layer")
REDUCED = {"num_hidden_layers": (40, 5), "first_k_dense_replace": (3, 1),
           "full_attention_layers": ([3, 7, 11, 15, 19, 23, 27, 31, 35, 39],
                                     [1]),
           "n_routed_experts": (256, 16), "vocab_size": (128256, 16032),
           "num_nextn_predict_layers": (2, 0)}
TOY_LIMIT = 0.12


@pytest.fixture(scope="module")
def real():
    return Manifest(ROOT)


@pytest.fixture(autouse=True)
def own_expert_counters():
    """A rehearsal starts from zero and leaves zero behind
    (``gdn_mla_toy.zero_expert_counters`` has the reason)."""
    gdn_mla_toy.zero_expert_counters()
    yield
    gdn_mla_toy.zero_expert_counters()


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
    # the share: the router keeps its width, 8 a token
    assert (cfg["router_experts"], cfg["first_expert_held"],
            cfg["num_experts_per_tok"], cfg["num_experts"]) == (256, 0, 8, 16)
    # one leading dense layer, then a whole period: latent, linear x 3
    assert cfg["mlp_only_layers"] == [0] and cfg["derived"]["mlp_only_layers"]
    for line in ("norm", "attention_gate", "swiglu_limit", "output_gate",
                 "scoring_func", "state_dtype", "l2_norm_eps", "rope", "mtp"):
        assert cfg["assumed"][line], line
    assert "ONE CHIP OF THE 16" in cfg["deployment"]
    assert cfg["check"]["why"] and cfg["bytes"]["parameters"] == 4731722752
    # the traffic ISSUE 42 names, letter for letter
    assert mix == {**mix, "kind": "closed_loop", "clients": 96,
                   "prompt_lengths": [1024, 1024, 2048, 2048, 4096, 8192,
                                      16384, 32768],
                   "output_lengths": [2048, 4096, 8192],
                   "stationary_start": True, "trace_seconds": 5,
                   "check": {"requests": 4, "pad_to": 40960}}
    over = dict(o.split("=") for o in cfg["serve"]["overrides"])
    chunk = int(over["Serving.prefill_chunk"])
    assert chunk == 512 and int(over["Serving.page_size"]) == 16
    assert int(over["Serving.max_batch"]) == mix["clients"] == 96
    # every prompt is whole chunks: what gdn_chunk_roofline counts a call at
    assert all(p % chunk == 0 for p in mix["prompt_lengths"])
    # longest prompt + longest output + the fill's lengthening (a chunk tick
    # for every chunk of the 95 prompts behind the first: 12 rounds of the
    # eight lengths, less the shortest prompt of all)
    behind = (12 * sum(mix["prompt_lengths"])
              - min(mix["prompt_lengths"])) // chunk
    assert max(mix["prompt_lengths"]) + max(mix["output_lengths"]) + behind \
        <= int(over["Serving.max_seq_len"]) == 42560 \
        <= cfg["max_position_embeddings"]
    assert mix["check"]["pad_to"] == max(mix["prompt_lengths"]) + max(
        mix["output_lengths"])
    # the pool: 1,750,000 token slots; the mean fill of 96 slots is far below
    assert (int(over["Serving.num_pages"]) - 1) * 16 == 1_750_000
    reported = {m["name"] for group in ("end_to_end", "per_layer")
                for m in real.metrics_of(CELL, group)}
    assert {"itl_p95_ms", "setup_s", *APPENDED, *NEW_METRICS} <= reported
    # neither the first token's wait (a 64-chunk prompt behind a FIFO swings
    # with the seed: ISSUE 42) nor, for the same reason, tokens/s: a 40 s
    # window holds ~27 arrivals of 2 to 64 chunks each and a chunk tick is
    # 2.5 decode ticks long, so six seeds spread 7.3 % where half the bound
    # is 2.5 % (my chip runs, PR 42; PERF.md section 6)
    assert not {"ttft_mean_ms", "serve_out_tokens_per_s"} & reported
    assert real.family("GDNMLAModule") and \
        real.reference_path("gigachat35_ref")


def test_the_six_readers_are_on_the_cells_list(real):
    """Each with the cell ON its list (which other cells a later PR appends
    is not this test's to pin), a layer the manifest already had, a reader
    file, and after every entry the parent had: the driver takes an entry
    put in the middle for a change to the one it displaced."""
    names = list(real.per_layer)
    older = {e["layer"] for n, e in real.per_layer.items()
             if n not in NEW_METRICS}
    for name, (unit, better, source, moves) in NEW_METRICS.items():
        entry = real.per_layer[name]
        assert CELL in entry["workloads"]
        assert (entry["unit"], entry["better"], entry["source"],
                entry["moves"]) == (unit, better, source, moves), name
        assert entry["layer"] in older
        assert names.index(name) > names.index("serve_unscoped_pct")
        assert hasattr(manifest_mod.load_module(real.reader_path(name)),
                       "read")
    for name in APPENDED:
        assert real.per_layer[name]["workloads"][-1] == CELL
    assert {"gdn_decode", "gdn_chunk", "mla_paged_decode"} <= set(
        real.kernel_trace_names())


def test_the_readers_find_nothing_in_a_program_without_the_family():
    """On the parent (no gauge, no kernel of these names in the trace, no
    ``gdn`` scope) each new reader returns None and raises nothing."""
    real = Manifest(ROOT)
    ctx = argparse.Namespace(config={"serve": {"overrides": []}},
                             manifest=real, err=io.StringIO())
    facts = {"occupancy": [3], "context_tokens": [100], "slots": 4}
    trace = {"n_devices": 1, "ops": {}, "op_counts": {}, "modules": {}}
    from fleetx_tpu.observability.metrics import get_registry

    get_registry().gauge("serving_state_cache_bytes").set(0)
    for name in NEW_METRICS:
        reader = manifest_mod.load_module(real.reader_path(name))
        assert reader.read({}, facts, dict(trace), {"ctx": ctx}) is None, name


def test_the_chunk_share_is_over_all_the_rule_costs_a_chunk():
    """``gdn_chunk_roofline`` divides the recurrence's floor by the
    ``gdn.core`` scope of ``jit_prefill`` — the kernel AND the XLA in front
    of it —, not by the kernel's own time: work pushed out of the kernel
    cannot raise it."""
    real = Manifest(ROOT)
    ctx = argparse.Namespace(
        config=dict(real.config(CONFIG)), manifest=real, err=io.StringIO(),
        devices=[argparse.Namespace(device_kind="TPU v5 lite")])
    scopes = {"jit_prefill": {"calls": 10, "by": {
        ("gdn.core", "fwd"): 80_000.0, ("gdn.proj", "fwd"): 50_000.0,
        ("moe.experts", "fwd"): 70_000.0}}}
    trace = {"n_devices": 1, "ops": {"kernel:gdn_chunk": 0.012},
             "op_counts": {"kernel:gdn_chunk": 40}, "_program_scopes": scopes}
    reader = manifest_mod.load_module(real.reader_path("gdn_chunk_roofline"))
    got = reader.read({}, {}, trace, {"ctx": ctx})
    one = manifest_mod.load_module(real.kernel_path("gdn_chunk")).count(
        512, 64, 32, 128, 128)
    assert got == pytest.approx(100 * 40 * (one["bytes"] / 819e9) / 0.08)
    assert got < 100 * 40 * (one["bytes"] / 819e9) / 0.012 / 6
    # no scope table (a parent, a CPU rehearsal): nothing to read
    assert reader.read({}, {}, dict(trace, _program_scopes=None),
                       {"ctx": ctx}) is None


def _count_the_scheduler(real, seed: int, ticks: int) -> dict:
    """The engine's policy over the cell's traffic with no device
    (``serving/engine.py``: lazy allocation with a watermark of one page,
    strict FIFO admission, one chunk a tick of the oldest prefilling
    request, a token a tick for every running row, a page grown when a row
    crosses into it): the latent pool's peak fill once the slots are full,
    the preemptions a dry pool would force, the share of ticks that carry
    a chunk."""
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
            chunk_ticks += 1
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
            "chunk_share": chunk_ticks / ticks, "filled_at": filled}


@pytest.mark.parametrize("seed", [4200001, 4200005])
def test_the_latent_pool_holds_the_traffic_with_no_preemption(real, seed):
    """ISSUE 42 section 3: a CPU count of the scheduler over this traffic —
    60,000 ticks, some twenty times what a run lasts — fills the pool to at
    most 85 % and preempts nobody; a third of the ticks carry a chunk."""
    got = _count_the_scheduler(real, seed, 60_000)
    assert got["preempted"] == 0
    assert 0.5 < got["peak_fill"] <= 0.85, got
    assert 0.3 < got["chunk_share"] < 0.4, got


def test_the_kernel_counts_are_the_issues():
    real = Manifest(ROOT)
    k = manifest_mod.load_module(real.kernel_path("gdn_decode")).count(
        1, 64, 32, 128, 128)
    assert k["flops"] == 7 * 64 * 128 * 128
    assert k["bytes"] == 2 * 4_194_304 + (2 * 32 * 128 + 2 * 64 * 128
                                          + 2 * 64) * 4
    c = manifest_mod.load_module(real.kernel_path("gdn_chunk")).count(
        512, 64, 32, 128, 128)
    assert c["flops"] == 512 * 7 * 64 * 128 * 128
    assert c["bytes"] == 2 * 4_194_304 + 512 * (
        2 * 32 * 128 + 2 * 64 * 128 + 2 * 64) * 4
    m = manifest_mod.load_module(real.kernel_path("mla_decode")).count(
        96, 1_000_000, 64, 576, 512)
    assert m["flops"] == 2 * 64 * (576 + 512) * 1_000_000
    assert m["bytes"] == 1_000_000 * 1152 + 96 * 64 * (576 + 512) * 2


def test_the_reference_imports_nothing_of_the_program(real):
    with open(real.reference_path("gigachat35_ref")) as f:
        text = f.read()
    assert "fleetx_tpu" not in text
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
                   if r["name"] == "GigaChat3.5-432B-A28B")
    cfg = real.config(CONFIG)
    assert cfg["source"] == row["source_url"] \
        == real.configs[CONFIG]["source"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key


def _toy_root(tmp: str) -> str:
    """A rehearsal root whose one cell is the shipped cell's files at toy
    widths: the shipped configuration with toy published keys, toy
    ``Model.*`` overrides and a small engine, a small mix of the same kind
    whose prompts are whole chunks."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(tmp, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "benchmarks/configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    published = dict(gdn_mla_toy.PUBLISHED, swiglu_limit=10)
    cfg.update(published)
    cfg.update(num_experts=8, max_position_embeddings=512)
    model = gdn_mla_toy.model_section(swiglu_limit=10, dtype="bfloat16")
    cfg["serve"]["overrides"] = [
        f"Model.{k}={v if isinstance(v, bool) else json.dumps(v)}"
        for k, v in model.items() if k != "module"] + [
        "Serving.max_batch=4", "Serving.page_size=8", "Serving.num_pages=129",
        "Serving.max_seq_len=256", "Serving.prefill_chunk=8",
        "Serving.max_queue=0"]
    # toy readings on the CPU (bfloat16 program, float32 reference; logits
    # of size ~0.4 at these widths, where one flipped expert of 3 moves a
    # post-normed layer's whole contribution): sound 0.005 / 0.043 / 0.071
    # on three seeds, the float8 control 0.21 / 0.26 / 0.31
    cfg["check"] = {"serve": {"served_logit_widest_gap": TOY_LIMIT}}
    with open(os.path.join(tmp, "benchmarks/configs/toy-gigachat.json"),
              "w") as f:
        json.dump(cfg, f)
    mix = {"kind": "closed_loop", "clients": 4,
           "prompt_lengths": [16, 16, 24, 40], "output_lengths": [6, 10, 14],
           "stationary_start": True, "trace_seconds": 0.5,
           "check": {"requests": 3, "pad_to": 128}}
    with open(os.path.join(tmp, "benchmarks/traffic/toy-longgen.json"),
              "w") as f:
        json.dump(mix, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "toy-gigachat", "source": "tests",
                         "file": "benchmarks/configs/toy-gigachat.json",
                         "reduced": [], "why": "toy widths"}]
    bench["workloads"] = [{"name": "toy-longgen", "config": "toy-gigachat",
                           "traffic": "toy-longgen", "chips": 1,
                           "why": "rehearsal"}]
    for group in ("end_to_end", "per_layer"):
        kept = []
        for m in bench[group]:
            if "workloads" in m:
                if CELL not in m["workloads"]:
                    continue
                m = dict(m, workloads=["toy-longgen"])
            kept.append(m)
        bench[group] = kept
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearsed_at_toy_widths(tmp_path, trace):
    """Through ``run_cell``: the family file unedited, ``param_paths``, the
    weights made in the served dtypes, the engine, prefill in chunks then
    decode through the latent pool and the states, the streamed check.
    Untraced, through ``benchmarks/control_first.py``: ``correct``, nothing
    failed or preempted, and the float8 control is not correct. Traced: the
    program counters' metrics are on the line (the device ones need a
    device)."""
    root = _toy_root(str(tmp_path))
    out, err = io.StringIO(), io.StringIO()
    if trace:
        run.run_cell(argparse.Namespace(
            workload="toy-longgen", seed=4200000008, seconds=2.5, trace=1,
            control=""), root=root, platforms=("cpu",), out=out, err=err)
    else:       # as the builder read the control on the chip
        control_first.main(
            ["--workload", "toy-longgen", "--seed", "4200000007", "--seconds",
             "2.5", "--trace", "0", "--control", "float8"],
            root=root, platforms=("cpu",), out=out, err=err)
        assert "counters: serving_decode_steps=" in err.getvalue()
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, err.getvalue()
    assert line["check"]["served_logit_widest_gap"] <= TOY_LIMIT
    if trace:
        got = line["metrics"]
        assert "preempt_per_req" not in got     # moves a metric not reported
        # 4 slots x 4 layers x (a state of 4 x 16 x 16 float32 + a tail of
        # 3 x 128 channels of bfloat16)
        assert got["state_cache_gb"]["value"] == pytest.approx(
            4 * 4 * (4 * 16 * 16 * 4 + 3 * 128 * 2) / 1e9)
        assert 0.9 < got["moe_serve_passes_per_layer"]["value"] <= 1.0
        assert not set(got) & {"gdn_decode_roofline", "gdn_decode_ms"}
    else:
        assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}
        assert "serving_requests_preempted=0" in err.getvalue()
        assert line["control"]["check"]["served_logit_widest_gap"] \
            > TOY_LIMIT
