"""The benchmark harness on the CPU: the manifest, the traffic generator,
the metric arithmetic, the trace reduction, the kernels' counts, the
comparison behind ``correct`` with its control, and a tiny rehearsal of
each traffic kind. No number here stands for a device."""

from __future__ import annotations

import argparse
import copy
import gzip
import io
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import toy  # noqa: E402
from benchmarks import manifest as manifest_mod  # noqa: E402
from benchmarks import readers, run, stats, trace_reduce, traffic  # noqa: E402
from benchmarks.manifest import Manifest, ManifestError  # noqa: E402


@pytest.fixture(scope="module")
def real():
    return Manifest(ROOT)


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return toy.make_root(str(tmp_path_factory.mktemp("bench_root")))


# ------------------------------------------------------------------ manifest
def test_manifest_validates(real):
    assert set(real.data) == set(manifest_mod.TOP_KEYS)
    assert real.data["command"] == ["python3", "benchmarks/run.py"]
    assert len(json.dumps(real.data)) < 64 * 1024


def test_units_and_names(real):
    for m in real.data["end_to_end"] + real.data["per_layer"]:
        assert len(m["unit"]) <= 16 and " " not in m["unit"], m
        assert manifest_mod.NAME_RE.match(m["name"]), m


def test_every_moves_is_reported_where_the_layer_metric_is(real):
    for m in real.per_layer.values():
        target = real.end_to_end[m["moves"]]
        for cell in real.cells_of(m):
            assert cell in real.cells_of(target), (m["name"], cell)


@pytest.mark.parametrize("cell", ["gpt345m-train-b8s1024",
                                  "gpt345m-serve-decode-closed",
                                  "gpt345m-serve-prefill-closed",
                                  "gpt1p3b-train-fsdp4"])
def test_cell_files_found_by_name(real, cell):
    if cell not in real.cells:
        pytest.skip(f"{cell} is not shipped (PERF.md, Open questions)")
    w = real.cells[cell]
    cfg = real.config(w["config"])
    mix = real.traffic(w["traffic"])
    assert mix["kind"] in traffic.KINDS
    assert os.path.exists(real.reference_path(cfg["reference"]))
    part = cfg["train" if mix["kind"] == "train_steps" else "serve"]
    assert os.path.exists(os.path.join(ROOT, part["recipe"]))
    if mix["kind"] != "train_steps":
        from fleetx_tpu.utils import config as config_mod

        recipe = config_mod.get_config(os.path.join(ROOT, part["recipe"]),
                                       list(part["overrides"]),
                                       num_devices=w["chips"])
        family = real.family(recipe["Model"]["module"])
        assert hasattr(family, "served_template") and \
            hasattr(family, "serving_engine")
    for m in real.metrics_of(cell, "per_layer"):
        assert hasattr(manifest_mod.load_module(real.reader_path(m["name"])),
                       "read")
    for key in cfg["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size")), key


def _broken(edit):
    def make(tmp_path):
        root = toy.make_root(str(tmp_path))
        path = os.path.join(root, "BENCHMARK.json")
        with open(path) as f:
            data = json.load(f)
        edit(data)
        with open(path, "w") as f:
            json.dump(data, f)
        return root
    return make


BROKEN = {
    "unit_with_space": lambda d: d["end_to_end"][0].update(unit="tokens per s"),
    "unit_too_long": lambda d: d["per_layer"][0].update(unit="x" * 17),
    "moves_unknown": lambda d: d["per_layer"][0].update(moves="nothing"),
    "moves_not_reported_in_cell": lambda d: d["per_layer"][2].update(
        workloads=["toy-closed"]),
    "extra_key_on_metric": lambda d: d["per_layer"][0].update(why="because"),
    "bound_too_wide": lambda d: d["end_to_end"][0].update(bound=0.2),
    "no_setup_s": lambda d: d.update(end_to_end=[
        m for m in d["end_to_end"] if m["name"] != "setup_s"]),
    "unknown_traffic_file": lambda d: d["workloads"][0].update(traffic="nope"),
    "pair_twice": lambda d: d["workloads"].append(dict(
        d["workloads"][0], name="again")),
    "end_to_end_from_program": lambda d: d["end_to_end"][0].update(
        source="program_counter"),
    "extra_top_level_key": lambda d: d.update(metrics=[]),
}


@pytest.mark.parametrize("case", list(BROKEN))
def test_manifest_refuses(tmp_path, case):
    root = _broken(BROKEN[case])(tmp_path)
    with pytest.raises((ManifestError, KeyError)):
        Manifest(root)


# ------------------------------------------------------------------- traffic
SERVE_MIXES = ["serve-decode-closed", "serve-prefill-closed"]


@pytest.mark.parametrize("mix_name", SERVE_MIXES)
@pytest.mark.parametrize("seeds", [(1, 2), (7, 2 ** 31 + 11),
                                   (3000000019, 5)])
def test_two_seeds_offer_the_same_work(real, mix_name, seeds):
    mix = real.traffic(mix_name)
    rounds = len(mix["prompt_lengths"]) * len(mix["output_lengths"])
    n = 4 * rounds
    a, b = (traffic.offered_work(mix, n, s) for s in seeds)
    assert a == b
    # and the generator that the cells use deals exactly that
    for seed in seeds:
        gen = traffic.ClosedLoop(mix, seed, vocab=100)
        reqs = gen.first() + [gen.next_for(i % gen.clients)
                              for i in range(n - gen.clients)]
        reqs = sorted(reqs, key=lambda r: r.index)[:n]
        assert sorted(len(r.prompt) for r in reqs) == a["prompts"]
        assert sorted(r.drawn_new for r in reqs) == a["outputs"]


def test_seed_permutes_the_order():
    mix = {"clients": 4, "prompt_lengths": [8, 16, 24],
           "output_lengths": [2, 4]}
    orders = {tuple(len(r.prompt) for r in sorted(
        traffic.ClosedLoop(mix, s, 50).first(), key=lambda r: r.index))
        for s in range(8)}
    assert len(orders) > 1


@pytest.mark.parametrize("mix_name", SERVE_MIXES)
def test_stationary_start_dephases_the_slots(real, mix_name):
    mix = real.traffic(mix_name)
    for seed in (11, 2 ** 31 + 5):
        first = traffic.ClosedLoop(mix, seed, vocab=100).first()
        assert len(first) == mix["clients"]
        by_len: dict = {}
        for r in first:
            assert 1 <= r.max_new <= r.drawn_new
            by_len.setdefault(r.drawn_new, []).append(r.max_new / r.drawn_new)
        for length, phases in by_len.items():
            phases.sort()
            n = len(phases)
            # evenly spaced: phase j sits within one token of (j + 0.5) / n
            for j, ph in enumerate(phases):
                assert abs(ph - (j + 0.5) / n) <= 1.0 / length + 1e-9
        # every seed has the same multiset of (length, residual) pairs
        pairs = sorted((r.drawn_new, r.max_new) for r in first)
        other = sorted((r.drawn_new, r.max_new) for r in
                       traffic.ClosedLoop(mix, seed + 1, 100).first())
        if sorted(p[0] for p in pairs) == sorted(p[0] for p in other):
            assert pairs == other


def test_stationary_start_allows_for_the_fill(real):
    """While the prompts queued behind a request are prefilled, one chunk
    a tick, that request already decodes a token a tick: its residual is
    lengthened by the chunks behind it."""
    mix = real.traffic("serve-decode-closed")
    plain = traffic.ClosedLoop(mix, 21, vocab=100).first()
    filled = traffic.ClosedLoop(mix, 21, vocab=100).first(prefill_chunk=128)
    assert [r.index for r in plain] == [r.index for r in filled]
    behind = 0
    for a, b in reversed(list(zip(plain, filled))):
        assert b.max_new == a.max_new + behind
        behind += -(-len(b.prompt) // 128)
    assert filled[-1].max_new == plain[-1].max_new
    # the longest first request still fits the context the cell states
    assert max(len(r.prompt) + r.max_new for r in filled) <= 1024


def _serving_override(cfg: dict, key: str) -> int:
    return next(int(o.split("=")[1]) for o in cfg["serve"]["overrides"]
                if o.startswith(f"Serving.{key}="))


def _closed_cells() -> list:
    """Every shipped cell whose traffic is a closed loop: a cell that a
    later PR adds is held to the same rule without an edit here."""
    m = Manifest(ROOT)
    return sorted(name for name, w in m.cells.items()
                  if m.traffic(w["traffic"])["kind"] == "closed_loop")


# the longest request dealt over seeds 2,600,000,000 + 0..299 (first round
# and 200 more): what ISSUE 27 reckoned by hand, pinned
LONGEST_DEALT = {"gpt345m-serve-decode-closed": 876,
                 "gpt345m-serve-prefill-closed": 550}


@pytest.mark.parametrize("cell", _closed_cells())
def test_every_request_dealt_fits_the_engine(real, cell, capsys):
    """A first-round request is longer than prompt + drawn output (the
    fill's chunks behind it), so it may pass ``check.pad_to``; it never
    passes the engine's ``max_seq_len`` nor the reference's positions."""
    cfg = real.config(real.cells[cell]["config"])
    mix = real.traffic(real.cells[cell]["traffic"])
    chunk = _serving_override(cfg, "prefill_chunk")
    longest, over_pad = 0, 0
    for seed in range(2600000000, 2600000300):
        gen = traffic.ClosedLoop(mix, seed, vocab=100)
        first = max(len(r.prompt) + r.max_new for r in gen.first(chunk))
        later = max(len(r.prompt) + r.max_new for r in (
            gen.next_for(i % gen.clients) for i in range(200)))
        assert later <= mix["check"]["pad_to"]      # steady state fits it
        longest = max(longest, first, later)
        over_pad += first > mix["check"]["pad_to"]
    with capsys.disabled():
        print(f"\n{cell}: longest request dealt {longest} tokens; first "
              f"round passes pad_to {mix['check']['pad_to']} on {over_pad} "
              f"of 300 seeds")
    assert longest <= _serving_override(cfg, "max_seq_len")
    assert longest <= cfg["max_position_embeddings"]
    assert longest == LONGEST_DEALT.get(cell, longest)


def test_open_loop_plan_same_work_and_due_times():
    mix = {"rate_rps": 10.0, "burst": 2, "prompt_lengths": [8, 16],
           "output_lengths": [2, 4, 6]}
    a = traffic.open_loop_plan(mix, 1, 50, seconds=6.0)
    b = traffic.open_loop_plan(mix, 2 ** 31 + 1, 50, seconds=6.0)
    assert len(a) == len(b) == 60
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [r.due_s for r in a] != [r.due_s for r in b]
    assert all(0 <= r.due_s < 6.0 for r in a)
    assert a == sorted(a, key=lambda r: r.due_s)


def test_train_batches_rows_differ_and_repeat_by_seed():
    mix = {"sequences_per_chip": 2, "seq_len": 16}
    a = next(traffic.train_batches(mix, 9, 100, chips=2))
    b = next(traffic.train_batches(mix, 9, 100, chips=2))
    c = next(traffic.train_batches(mix, 10, 100, chips=2))
    assert a["tokens"].shape == (4, 16)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    assert len({row.tobytes() for row in a["tokens"]}) == 4


# --------------------------------------------------------- metric arithmetic
def test_window_token_count_and_rate():
    log = stats.TokenLog()
    log.open("a", 0.0)
    log.note("a", [1.0, 2.0, 3.0, 4.0])
    log.open("b", 1.5)
    log.note("b", [2.5, 3.5])
    assert log.tokens_in(1.0, 3.5) == 4          # (1.0, 3.5]: 2.0 2.5 3.0 3.5
    assert stats.window_rate(100, 160, 10.0, 40.0) == 2.0


def test_gaps_count_where_they_end_and_percentile():
    log = stats.TokenLog()
    log.open("a", 0.0)
    log.note("a", [1.0, 1.2, 1.5, 2.5])
    log.open("b", 0.0)
    log.note("b", [0.9, 1.9])
    gaps = sorted(log.gaps_in(1.0, 2.0))
    assert gaps == pytest.approx([0.2, 0.3, 1.0])     # 2.5 ends outside
    assert stats.percentile(gaps, 50) == pytest.approx(0.3)
    assert stats.percentile(gaps, 95) == pytest.approx(0.3 + 0.7 * 0.9)
    assert stats.percentile([], 95) is None
    assert stats.percentile(list(range(101)), 95) == 95


def test_closed_and_open_loop_latencies():
    log = stats.TokenLog()
    log.open("closed", 10.0)                # submitted at 10.0
    log.note("closed", [10.4, 10.6])
    log.open("open", 9.0)                   # due at 9.0, sent late
    log.note("open", [10.5])
    log.open("outside", 0.0)
    log.note("outside", [5.0])
    lat = sorted(log.first_token_latencies_in(10.0, 11.0))
    assert lat == pytest.approx([0.4, 1.5])
    assert stats.mean(lat) == pytest.approx(0.95)


def test_generator_lateness():
    assert stats.generator_lateness([1.0, 2.0, 3.0], [1.0, 2.25, 2.9]) == \
        pytest.approx([0.0, 0.25, 0.0])


def test_steps_in_window():
    ends = [0.5, 1.0, 1.5, 2.0, 2.5, 3.0]
    assert stats.steps_in_window(ends, 1.0, 1.6) == (3, 2.5)
    assert stats.steps_in_window(ends, 5.0, 1.0) == (0, 5.0)


# --------------------------------------------------------------- the command
def _args(cell, trace=0, seconds=1.5, seed=3000000019, control=""):
    return argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=trace, control=control)


def test_refuses_any_platform_but_tpu(toy_root):
    with pytest.raises(SystemExit) as e:
        run.run_cell(_args("toy-train"), root=toy_root, out=io.StringIO())
    assert "measures the chip" in str(e.value)


def test_refuses_fewer_chips_than_the_cell_asks(tmp_path, monkeypatch):
    root = toy.make_root(str(tmp_path), chips=4)
    import jax

    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: one)
    with pytest.raises(SystemExit) as e:
        run.run_cell(_args("toy-train"), root=root, platforms=("cpu",),
                     out=io.StringIO())
    assert "needs 4 chips" in str(e.value)


def _busy(seconds):
    t0 = time.monotonic()
    while time.monotonic() - t0 < seconds:
        sum(len(str(i)) for i in range(200))


def test_a_stall_is_dumped_while_the_main_thread_runs_python(toy_root,
                                                             tmp_path):
    """The dump comes from a thread that holds the interpreter lock
    (``dump_traceback_later``'s does not, and on the chip's host that
    killed runs with SIGSEGV); a re-arm in time or a disarm prevents it."""
    m = Manifest(toy_root)
    with open(tmp_path / "log", "w+") as log:
        ctx = run.Context(m, m.cells["toy-closed"], _args("toy-closed"), [],
                          log, 0.0)
        ctx.watch(0.05)
        _busy(0.3)
        ctx.watch(0.05)
        ctx.watch(None)
        time.sleep(0.15)
        log.seek(0)
        text = log.read()
    assert text.count("stalled for 0.05 s:") == 1
    assert "in _busy" in text and "most recent call first" in text
    assert not [t for t in threading.enumerate()
                if isinstance(t, threading.Timer)]


_LINES: dict = {}


def _rehearse(toy_root, cell, trace):
    key = (cell, trace)
    if key not in _LINES:
        out, err = io.StringIO(), io.StringIO()
        run.run_cell(_args(cell, trace, seed=REHEARSAL_SEEDS.get(
            cell, 3000000019)), root=toy_root, platforms=("cpu",), out=out,
            err=err)
        _LINES[key] = json.loads(out.getvalue().strip().splitlines()[-1])
        _LINES[key]["_log"] = err.getvalue()
    return _LINES[key]


# a seed whose first round holds a request longer than the mix's pad_to
REHEARSAL_SEEDS = {"toy-closed-tight": 3000000024}


DEVICE_ONLY = ("roofline", "mfu", "hbm_peak", "pool_copy", "fwd_ms",
               "decode_step", "prefill_chunk", "fused_norm_ms",
               "bwd_ms", "outside_scan", "collective")


@pytest.mark.parametrize("cell,trace", [("toy-train", 0), ("toy-train", 1),
                                        ("toy-closed", 0), ("toy-closed", 1),
                                        ("toy-closed-tight", 0),
                                        ("toy-open", 0)])
def test_rehearsal_prints_a_well_formed_line(toy_root, cell, trace):
    line = _rehearse(toy_root, cell, trace)
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    m = Manifest(toy_root)
    group = "per_layer" if trace else "end_to_end"
    allowed = {x["name"] for x in m.metrics_of(cell, group)}
    assert set(line["metrics"]) <= allowed
    if not trace:
        assert set(line["metrics"]) == allowed
        assert all(v["value"] > 0 for v in line["metrics"].values())
    # never a number under a device metric's name, nor a busy time
    assert not [k for k in line["metrics"]
                if any(d in k for d in DEVICE_ONLY)]
    assert "busy_s" not in line["device"]


def test_rehearsal_checks_a_first_round_request_past_pad_to(toy_root):
    """The mix's ``pad_to`` is its longest prompt + longest output; the
    window is long enough for every first-round request to finish, and the
    longest of them (lengthened by the fill's chunks behind it) is always
    in the sample: the check widens its rows and compares all of it."""
    mix = toy.TOY_TRAFFIC["toy-closed-tight"]
    first = traffic.ClosedLoop(mix, REHEARSAL_SEEDS["toy-closed-tight"],
                               512).first(prefill_chunk=32)
    longest = max(len(r.prompt) + r.max_new for r in first)
    assert mix["check"]["pad_to"] < longest <= 128
    line = _rehearse(toy_root, "toy-closed-tight", 0)
    assert line["correct"] is True and line["failed"] == 0
    assert f"(longest request {longest} tokens, rows 128 wide)" \
        in line["_log"]


# ------------------------------------------------------------ trace reduction
def test_reduction_on_the_recorded_two_step_trace():
    planes = trace_reduce.load(os.path.join(
        ROOT, "tests/fixtures/trace_gpt_2step.json.gz"))
    red = trace_reduce.reduce(planes, ("attn._core_attn",))
    assert red["n_devices"] == 1
    regions = trace_reduce.scan_regions(red["_device0"], "jit_train_step")
    # BENCHMARKS.md's hand analysis of this trace: 251.2 = 153.1 + 59.3 + 38.8
    assert regions["executions"] == 2
    assert regions["module_ms"] == pytest.approx(251.2, abs=0.1)
    assert regions["fwd_ms"] == pytest.approx(59.3, abs=0.1)
    assert regions["bwd_ms"] == pytest.approx(153.1, abs=0.1)
    assert regions["outside_scan_ms"] == pytest.approx(38.8, abs=0.1)
    assert 0.0 <= red["idle_share"] < 0.05
    assert red["ops"]["kernel:attn._core_attn"] == pytest.approx(0.1752,
                                                                 abs=1e-3)
    assert red["modules"]["jit_train_step"][0] == 2


def test_idle_gaps_go_to_the_span_that_covers_them():
    dev = {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
        {"name": "fusion.1", "ts": 0.0, "dur": 100.0, "args": {}},
        {"name": "copy.2", "ts": 400.0, "dur": 100.0,
         "args": {"hlo_category": "copy",
                  "long_name": "%copy.2 = bf16[1,65,16,2,64]{4,3} copy(x)"}},
        {"name": "fusion.3", "ts": 1000.0, "dur": 50.0, "args": {}}]}]}
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        {"name": "bench:engine_step", "ts": 0.0, "dur": 600.0, "args": {}},
        {"name": "bench:client_send", "ts": 620.0, "dur": 300.0, "args": {}},
        {"name": "other", "ts": 0.0, "dur": 5000.0, "args": {}}]}]}
    red = trace_reduce.reduce([dev, host])
    assert red["busy_s"] == pytest.approx(250e-6)
    assert red["window_s"] == pytest.approx(1050e-6)
    gaps = dict(red["idle_gaps"])
    assert gaps["engine_step"] == pytest.approx(300e-6)
    assert gaps["client_send"] == pytest.approx(500e-6)
    assert red["spans"]["engine_step"] == [1, pytest.approx(600e-6)]
    assert "copy:copy_bf16_1_65_16_2_64_" in red["ops"]
    assert trace_reduce.ops_matching(red, "copy:", "65,16,") == \
        pytest.approx(100e-6)


@pytest.mark.parametrize("fixture,module,kernels", [
    ("train_gpt345m_v5e.json.gz", "jit_train_step",
     ("flash_fwd", "flash_bwd_fused", "fused_norm_fwd", "fused_norm_bwd")),
    ("serve_decode_gpt345m_v5e.json.gz", "jit_decode", ("paged_decode",)),
])
def test_reduction_on_this_benchmarks_chip_traces(real, fixture, module,
                                                  kernels):
    path = os.path.join(ROOT, "benchmarks/fixtures", fixture)
    planes = trace_reduce.load(path)
    red = trace_reduce.reduce(planes, real.kernel_trace_names())
    assert red["n_devices"] == 1 and red["busy_s"] > 0
    assert module in red["modules"]
    for k in kernels:
        assert f"kernel:{k}" in red["ops"], sorted(red["ops"])[:40]
    assert red["spans"], "the benchmark's own spans are on the trace"
    if module == "jit_train_step":
        regions = trace_reduce.scan_regions(red["_device0"], module)
        assert regions["bwd_ms"] > regions["fwd_ms"] > 0


# ------------------------------------------------------------ kernel counts
def _kernel(real, name):
    return manifest_mod.load_module(real.kernel_path(name))


def test_flash_fwd_count_by_hand(real):
    c = _kernel(real, "flash_fwd").count(8, 1024, 16, 64)
    # QK^T and PV: 2 products x 2 x 8 x 16 x 1024^2 x 64 flops, halved
    assert c["flops"] == 2 * 2 * 8 * 16 * 1024 * 1024 * 64 // 2 == 17179869184
    # q k v o in bf16 + one float32 lse per row and head
    assert c["bytes"] == 4 * 8 * 1024 * 16 * 64 * 2 + 8 * 16 * 1024 * 4


def test_flash_bwd_count_by_hand(real):
    k = _kernel(real, "flash_bwd")
    fused = k.count(8, 1024, 16, 64)
    assert fused["flops"] == 5 * 8 * 16 * 1024 * 1024 * 64 * 2 // 2
    assert fused["bytes"] == 8 * 8 * 1024 * 16 * 64 * 2 + 2 * 8 * 16 * 1024 * 4
    split = (k.count(8, 1024, 16, 64, variant="flash_bwd_dq")["flops"]
             + k.count(8, 1024, 16, 64, variant="flash_bwd_dkv")["flops"])
    assert split == fused["flops"] * 7 // 5      # S and dP are made twice


def test_fused_norm_count_by_hand(real):
    k = _kernel(real, "fused_norm")
    c = k.count(8192, 1024, k.ROW_TENSORS["fused_norm_fwd"]["residual"])
    assert c["bytes"] == 4 * 8192 * 1024 * 2 + 8192 * 8
    assert c["flops"] == 8 * 8192 * 1024
    # memory bound on the v5e: the byte floor is the larger
    pk = real.peaks("TPU v5 lite")
    assert c["bytes"] / pk["hbm_bytes_per_s"] > \
        c["flops"] / pk["bf16_flops_per_s"]


def test_paged_decode_count_by_hand(real):
    c = _kernel(real, "paged_decode").count(64, 64 * 512, 16, 64)
    assert c["bytes"] == 2 * 32768 * 16 * 64 * 2 + 2 * 64 * 16 * 64 * 2
    assert c["flops"] == 4 * 32768 * 16 * 64


def test_model_flops_per_token_by_hand(real):
    cfg = real.config("gpt-345m")
    got = _kernel(real, cfg["model_flops"]).train_flops_per_token(cfg, 1024)
    matmul = 24 * 12 * 1024 * 1024 + 50304 * 1024
    assert got == pytest.approx(6 * matmul + 6 * 24 * 1024 * 1024)
    assert 2.2e9 < got < 2.3e9


def test_roofline_share_and_unknown_device(real):
    pk = real.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    one = {"flops": 197e12 * 1e-3, "bytes": 10}
    assert readers.roofline_share([(4, one)], 8e-3, pk) == pytest.approx(50.0)
    with pytest.raises(ManifestError):
        real.peaks("TPU v9 imaginary")


def test_a_roofline_over_100_fails_the_run(toy_root, tmp_path, monkeypatch):
    """A share of a peak above 100 % is a counting fault: no line."""
    root = toy.make_root(str(tmp_path))
    with open(os.path.join(root, "benchmarks/layer_metrics/"
                                 "flash_fwd_roofline.py"), "w") as f:
        f.write("def read(spans, facts, trace, info):\n    return 140.0\n")
    out = io.StringIO()
    with pytest.raises(SystemExit) as e:
        run.run_cell(_args("toy-train", trace=1), root=root,
                     platforms=("cpu",), out=out, err=io.StringIO())
    assert "flash_fwd_roofline" in str(e.value) and out.getvalue() == ""


# ---------------------------------------------- correct, and its control
def _toy_train_numbers(precision, seed=5):
    """The reference at toy widths, put in the program's place in the
    stated precision, against the float32 reference."""
    import jax.numpy as jnp
    from benchmarks import check, weights

    m = Manifest(ROOT)
    cfg = toy.toy_config()
    ref = manifest_mod.load_module(m.reference_path("gpt_ref"))
    adam = manifest_mod.load_module(m.reference_path("adamw_ref"))
    spec = ref.weight_spec(cfg)
    w = weights.make(spec, seed)
    feed = traffic.train_batches(toy.TOY_TRAFFIC["toy-train"], seed,
                                 cfg["vocab_size"], 1)
    batches = [{k: jnp.asarray(v) for k, v in next(feed).items()}
               for _ in range(3)]
    opt = cfg["train"]["optimizer"]
    want = check.train_reference(ref, adam, cfg, opt, w, batches)
    got = check.train_reference(ref, adam, cfg, opt, w, batches,
                                precision=precision)
    return check.train_numbers(got, want), cfg["check"]["train"]


def test_train_check_passes_the_stated_precision():
    from benchmarks import check

    numbers, limits = _toy_train_numbers("bfloat16")
    assert check.judge(numbers, limits, out=io.StringIO())


@pytest.mark.parametrize("seed", [5, 6, 2 ** 31 + 7])
def test_train_control_one_precision_lower_is_not_correct(seed):
    from benchmarks import check

    numbers, limits = _toy_train_numbers("float8", seed)
    assert not check.judge(numbers, limits, out=io.StringIO())
    # it is the gradient that the lower precision moves, not the loss at
    # seeded weights: that number is there to catch rows left out
    assert numbers["grad_norm_worst_leaf_gap"] > \
        limits["grad_norm_worst_leaf_gap"]


_TOY_SERVED: dict = {}


def _toy_served(seed=5):
    """``(ref, cfg, source, samples)``: three requests of 64, 100 and 120
    tokens whose served tokens are the reference's own greedy ones (they
    stand for a sound program); ``source`` makes the reference's weights."""
    if seed in _TOY_SERVED:
        return _TOY_SERVED[seed]
    import jax
    import jax.numpy as jnp
    from benchmarks import weights

    m = Manifest(ROOT)
    cfg = toy.toy_config()
    ref = manifest_mod.load_module(m.reference_path("gpt_ref"))
    source = weights.Source(ref.weight_spec(cfg), seed)
    w = source.tree()
    rng = np.random.default_rng(seed)
    samples = []
    fwd = jax.jit(lambda t: ref.logits(w, cfg, t, "float32"))
    for plen, n in ((24, 40), (40, 60), (56, 64)):
        seq = rng.integers(0, cfg["vocab_size"], plen).tolist()
        for _ in range(n):
            padded = jnp.asarray([seq + [0] * (128 - len(seq))])
            seq.append(int(jnp.argmax(fwd(padded)[0, len(seq) - 1])))
        samples.append((seq[:plen], seq[plen:]))
    _TOY_SERVED[seed] = (ref, cfg, source, samples)
    return _TOY_SERVED[seed]


def _toy_serve_gap(chooser, seed=5, pad_to=128, widths=None):
    """The check over ``_toy_served``; ``widths`` collects the shape of
    every token matrix the reference is traced with."""
    import types
    from benchmarks import check

    ref, cfg, source, samples = _toy_served(seed)

    def logits(w, sizes, tokens, precision):
        if widths is not None:
            widths.append(tuple(tokens.shape))
        return ref.logits(w, sizes, tokens, precision)
    got = check.served_logit_gaps(types.SimpleNamespace(logits=logits), cfg,
                                  source, samples, pad_to, chooser=chooser)
    return got, cfg["check"]["serve"]


def test_serve_check_sound_tokens_have_no_gap():
    got, limits = _toy_serve_gap(None)
    assert got["tokens_compared"] == 164
    assert got["widest_gap"] <= 1e-4 < limits["served_logit_widest_gap"]


def test_serve_control_one_precision_lower_is_not_correct():
    got, limits = _toy_serve_gap("float8")
    assert got["widest_gap"] > limits["served_logit_widest_gap"]


@pytest.mark.parametrize("pad_to,width", [(128, 128), (120, 120),
                                          (100, 128), (64, 128)])
def test_serve_check_rows_follow_the_samples(pad_to, width):
    """Samples that all fit ``pad_to`` are compared at exactly that width;
    a longer one widens the rows, and every served token is still compared.
    The reference sees one sample at a time (one program for all three)."""
    widths = []
    got, _ = _toy_serve_gap(None, pad_to=pad_to, widths=widths)
    assert widths == [(1, width)] and got["width"] == width
    assert got["tokens_compared"] == 164
    assert np.isfinite(got["widest_gap"]) and got["widest_gap"] <= 1e-4


def test_serve_control_goes_through_the_same_width():
    widths = []
    got, limits = _toy_serve_gap("float8", pad_to=100, widths=widths)
    assert widths == [(1, 128), (1, 128)]       # float32, then the chooser
    assert got["widest_gap"] > limits["served_logit_widest_gap"]


@pytest.mark.parametrize("samples,pad_to,positions,width", [
    ([(384, 384)], 768, 1024, 768), ([(128, 1)], 768, 1024, 768),
    ([(384, 385)], 768, 1024, 896), ([(384, 416), (128, 128)], 768, 1024, 896),
    ([(384, 512)], 768, 1024, 896), ([(384, 513)], 768, 1024, 1024),
    ([(384, 640)], 768, 1024, 1024), ([(384, 500)], 768, 1000, 896),
    ([(384, 600)], 768, 1000, 1000), ([(512, 38)], 640, 1024, 640)])
def test_row_width(samples, pad_to, positions, width):
    from benchmarks import check

    made = [([0] * p, [0] * s) for p, s in samples]
    assert check.row_width(made, pad_to, positions) == width


def test_a_sample_past_the_position_table_is_too_long():
    from benchmarks import check

    ref, cfg, source, samples = _toy_served()
    prompt, served = samples[-1]
    with pytest.raises(check.TooLong) as e:
        check.served_logit_gaps(ref, cfg, source, samples[:2] + [
            (prompt, served + [1] * 9)], 128)
    assert str(e.value) == ("request of 129 tokens exceeds the reference's "
                            "128 positions")


def test_a_request_the_reference_cannot_hold_is_judged_not_raised(
        toy_root, monkeypatch):
    """A served request longer than the configuration's position table is
    ``correct`` false with the reason in the log and the window's metrics
    in the line; no exception."""
    from benchmarks import check

    sample = check.sample_served

    def lengthened(finished, seed, n):
        got = sample(finished, seed, n)
        prompt, served = got[0]
        return [(prompt, served + [1] * (129 - len(prompt) - len(served)))
                ] + got[1:]
    monkeypatch.setattr(check, "sample_served", lengthened)
    out, err = io.StringIO(), io.StringIO()
    run.run_cell(_args("toy-closed", seed=3000000021), root=toy_root,
                 platforms=("cpu",), out=out, err=err)
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is False and line["check"] == {}
    assert ("check: request of 129 tokens exceeds the reference's 128 "
            "positions  NOT CORRECT") in err.getvalue()
    assert line["metrics"]["serve_out_tokens_per_s"]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0


def test_an_error_of_the_reference_still_ends_the_run(toy_root, monkeypatch):
    """Only ``TooLong`` is judged: anything else the check raises is not
    caught."""
    from benchmarks import check

    def broken(*args, **kwargs):
        raise ValueError("could not broadcast")
    monkeypatch.setattr(check, "served_logit_gaps", broken)
    with pytest.raises(ValueError, match="could not broadcast"):
        run.run_cell(_args("toy-closed", seed=3000000022), root=toy_root,
                     platforms=("cpu",), out=io.StringIO(),
                     err=io.StringIO())


BREAKS = ["step_returns_state_unchanged", "part_of_the_batch_left_out",
          "served_token_altered"]


@pytest.mark.parametrize("fault", BREAKS)
def test_a_broken_timed_path_is_not_correct(toy_root, monkeypatch, fault):
    """Skip the look for a chip, drive the rest of a run with the timed
    path broken underneath: ``correct`` comes out false."""
    import jax

    cell = "toy-train"
    if fault == "step_returns_state_unchanged":
        from benchmarks import train_cell

        build = train_cell.build_engine

        def broken_build(ctx):
            cfg, engine = build(ctx)
            prepare = engine.prepare

            def prepare_then_break(batch):
                state = prepare(batch)
                raw = engine._train_step_raw
                engine._train_step = jax.jit(
                    lambda s, b: (s, raw(s, b)[1]))
                return state
            engine.prepare = prepare_then_break
            return cfg, engine
        monkeypatch.setattr(train_cell, "build_engine", broken_build)
    elif fault == "part_of_the_batch_left_out":
        from fleetx_tpu.core.engine import EagerEngine

        shard = EagerEngine.shard_batch

        def half_masked(self, batch):
            batch = dict(batch)
            mask = np.array(batch["loss_mask"])
            mask[mask.shape[0] // 2:] = 0.0
            batch["loss_mask"] = mask
            return shard(self, batch)
        monkeypatch.setattr(EagerEngine, "shard_batch", half_masked)
        monkeypatch.setenv("FLEETX_PREFETCH_OFF", "1")
    else:
        cell = "toy-closed"
        from fleetx_tpu.serving.engine import ServingEngine

        emit = ServingEngine._emit

        def altered(self, req, token):
            return emit(self, req, (int(token) + 1) % 512
                        if len(req.tokens) == 2 else token)
        monkeypatch.setattr(ServingEngine, "_emit", altered)
    out, err = io.StringIO(), io.StringIO()
    run.run_cell(_args(cell), root=toy_root, platforms=("cpu",), out=out,
                 err=err)
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is False, err.getvalue()
    assert "NOT CORRECT" in err.getvalue()


# ------------------------------------------------ a later PR adds files only
def test_a_later_pr_adds_files_only(tmp_path):
    """A configuration, a traffic mix, a kernel count and a per-layer
    metric arrive as new files and entries; no file that is there changes,
    and the new cell runs."""
    root = toy.make_root(str(tmp_path))
    bench = os.path.join(root, "benchmarks")
    before = toy.files_under(bench)
    cfg = toy.toy_config()
    cfg.update(name="toy-wide", num_attention_heads=1, head_dim=128)
    cfg["serve"]["overrides"] = [
        o for o in cfg["serve"]["overrides"] if "num_attention_heads" not in o
    ] + ["Model.num_attention_heads=1"]
    with open(os.path.join(bench, "configs/toy-wide.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic/toy-short.json"), "w") as f:
        json.dump({"kind": "closed_loop", "clients": 2,
                   "prompt_lengths": [8, 12], "output_lengths": [3, 4],
                   "check": {"requests": 2, "pad_to": 128}}, f)
    with open(os.path.join(bench, "kernels/toy_gather.py"), "w") as f:
        f.write("TRACE_NAMES = ('toy_gather',)\n\n\n"
                "def count(rows, width):\n"
                "    return {'flops': 0, 'bytes': 2 * rows * width * 2}\n")
    with open(os.path.join(bench, "layer_metrics/ticks_per_token.py"),
              "w") as f:
        f.write("from benchmarks import readers\n\n\n"
                "def read(spans, facts, trace, info):\n"
                "    k = readers.kernel(info, 'toy_gather')\n"
                "    assert k.count(2, 4)['bytes'] == 32\n"
                "    c = facts['counters']\n"
                "    return c['engine_steps'] / max(c['tokens_total'], 1)\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        data = json.load(f)
    data["configs"].append({"name": "toy-wide", "source": "tests",
                            "file": "benchmarks/configs/toy-wide.json",
                            "reduced": [], "why": "one wide head"})
    data["workloads"].append({"name": "toy-wide-short", "config": "toy-wide",
                              "traffic": "toy-short", "chips": 1,
                              "why": "added by a later PR"})
    for m in data["end_to_end"] + data["per_layer"]:
        if "workloads" in m and "toy-closed" in m["workloads"]:
            m["workloads"].append("toy-wide-short")
    data["per_layer"].append({
        "name": "ticks_per_token", "unit": "1/token", "better": "lower",
        "source": "program_counter", "moves": "serve_out_tokens_per_s",
        "layer": "serving scheduler (serving/engine.py, paged_cache.py)",
        "workloads": ["toy-wide-short"]})
    with open(path, "w") as f:
        json.dump(data, f)
    out = io.StringIO()
    run.run_cell(_args("toy-wide-short", trace=1, seconds=1.0), root=root,
                 platforms=("cpu",), out=out, err=io.StringIO())
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["metrics"]["ticks_per_token"]["value"] > 0
    assert "toy_gather" in Manifest(root).kernel_trace_names()
    toy.assert_none_edited(before)
