"""The readers of the program's device scopes
(``benchmarks/program_scopes.py``) on a hand-written trace and table, and
the fifteen manifest entries that read them. No number here stands for a
device."""

from __future__ import annotations

import argparse
import io
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import manifest as manifest_mod  # noqa: E402
from benchmarks import program_scopes as sc  # noqa: E402
from benchmarks import trace_reduce  # noqa: E402
from benchmarks.manifest import Manifest  # noqa: E402

TRAIN_CELLS = ["gpt345m-train-b8s1024", "gpt1p3b-train-fsdp4",
               "joyai-flash-train-b2s8192"]
SERVE_CELLS = ["gpt345m-serve-decode-closed", "gpt345m-serve-prefill-closed",
               "laguna-s-serve-mixed-closed",
               "smallthinker-serve-reason-closed"]
SWA_CELLS = SERVE_CELLS[2:]
T, I = "train_tokens_per_s", "itl_p95_ms"
#: metric -> (moves, cells, unit)
NEW = {
    "scope_fwd_ms": (T, TRAIN_CELLS, "ms"),
    "scope_bwd_ms": (T, TRAIN_CELLS, "ms"),
    "scope_remat_ms": (T, TRAIN_CELLS, "ms"),
    "scope_optimizer_ms": (T, TRAIN_CELLS, "ms"),
    "scope_embed_ms": (T, TRAIN_CELLS, "ms"),
    "scope_head_loss_ms": (T, TRAIN_CELLS, "ms"),
    "scope_moe_route_ms": (T, TRAIN_CELLS[2:], "ms"),
    "decode_attn_ms": (I, SERVE_CELLS, "ms"),
    "decode_ffn_ms": (I, SERVE_CELLS, "ms"),
    "decode_route_ms": (I, SWA_CELLS, "ms"),
    "cache_write_ms": (I, SERVE_CELLS, "ms"),
    "chunk_attn_ms": (I, SERVE_CELLS[1:], "ms"),
    "chunk_route_ms": (I, SWA_CELLS, "ms"),
    "train_unscoped_pct": (T, TRAIN_CELLS, "%"),
    "serve_unscoped_pct": (I, SERVE_CELLS, "%"),
}


def _ev(name, ts, end, **args):
    return {"name": name, "ts": float(ts), "dur": float(end - ts),
            "args": args}


def _planes():
    """Two ``jit_decode`` calls, one ``jit_prefill`` call, a module of
    someone else's, and an op between two executions. The first decode call
    holds a ``while`` that holds a ``while`` (containers: their leaves count
    once); ``fusion.9`` is not in the table."""
    ops = [
        # jit_decode, call 1: 0 .. 1000
        _ev("fusion.1", 0, 100),                              # embed
        _ev("while.3", 100, 900, hlo_category="while"),
        _ev("paged_decode.2", 100, 400),                      # attn.core
        _ev("while.4", 400, 800, hlo_category="while"),
        _ev("moe_gmm_decode.5", 400, 700),                    # moe.experts
        _ev("fusion.6", 700, 800),                            # moe.route
        _ev("fusion.7", 800, 900),                            # attn.cache
        _ev("fusion.9", 900, 950),                            # not in table
        _ev("copy.8", 950, 1000),                             # no scope
        # between two executions: nobody's
        _ev("fusion.1", 1100, 1150),
        # jit_prefill: 1200 .. 2000
        _ev("fusion.21", 1200, 1700),                         # attn.core
        _ev("fusion.22", 1700, 1900),                         # attn.cache
        _ev("fusion.23", 1900, 2000),                         # moe.route
        # jit_decode, call 2: 2000 .. 2600
        _ev("paged_decode.2", 2000, 2500),
        _ev("fusion.7", 2500, 2600),
        # a module the program did not compile through log_compile
        _ev("fusion.1", 3000, 3100),
    ]
    modules = [_ev("jit_decode(123)", 0, 1000),
               _ev("jit_prefill(456)", 1200, 2000),
               _ev("jit_decode(123)", 2000, 2600),
               _ev("jit_norms(789)", 3000, 3100)]
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": modules},
        {"name": "XLA Ops", "events": ops}]}
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        _ev("serve.tick", 0, 1100), _ev("serve.tick", 1150, 2700)]}]}
    return [dev, host]


TABLES = {
    "jit_decode": {"fusion.1": ("embed", "fwd"),
                   "while.3": ("stack", "fwd"), "while.4": ("stack", "fwd"),
                   "paged_decode.2": ("attn.core", "fwd"),
                   "moe_gmm_decode.5": ("moe.experts", "fwd"),
                   "fusion.6": ("moe.route", "fwd"),
                   "fusion.7": ("attn.cache", "fwd"), "copy.8": ("", "")},
    "jit_prefill": {"fusion.21": ("attn.core", "fwd"),
                    "fusion.22": ("attn.cache", "fwd"),
                    "fusion.23": ("moe.route", "fwd"),
                    "fusion.1": ("head", "fwd")},
}


@pytest.fixture()
def got():
    return sc.build(trace_reduce.reduce(_planes())["_device0"], TABLES)


def test_leaves_go_to_their_module_scope_and_direction(got):
    assert set(got) == {"jit_decode", "jit_prefill"}   # jit_norms: no table
    dec = got["jit_decode"]
    assert dec["calls"] == 2 and dec["module_us"] == 1600
    # containers out: the nested while's leaves are counted once
    assert dec["leaf_us"] == 1000 + 600
    assert dec["by"] == {
        ("embed", "fwd"): 100, ("attn.core", "fwd"): 300 + 500,
        ("moe.experts", "fwd"): 300, ("moe.route", "fwd"): 100,
        ("attn.cache", "fwd"): 100 + 100, (sc.UNSCOPED, ""): 50 + 50}
    # an instruction the table lacks and one it holds without a scope
    assert dec["stray"] == {"fusion.9": 50, "copy.8": 50}
    pre = got["jit_prefill"]
    assert pre["calls"] == 1 and pre["leaf_us"] == 800
    # ``fusion.1`` between the executions belongs to neither module
    assert ("head", "fwd") not in pre["by"]


def test_per_module_the_scopes_and_unscoped_add_up_to_the_leaf_time(got):
    for row in got.values():
        assert sum(row["by"].values()) == pytest.approx(row["leaf_us"])
        assert sum(row["stray"].values()) == pytest.approx(
            row["by"].get((sc.UNSCOPED, ""), 0.0))


def test_the_readers_on_the_hand_made_trace(got):
    # per call of one module; a prefix covers its dotted names
    assert sc.ms_per_call(got, "jit_decode", ("attn",)) == \
        pytest.approx((800 + 200) / 2 / 1e3)
    assert sc.ms_per_call(got, "jit_decode", ("mlp", "moe")) == \
        pytest.approx(400 / 2 / 1e3)
    assert sc.ms_per_call(got, "jit_decode", ("moe.route",)) == \
        pytest.approx(100 / 2 / 1e3)
    assert sc.ms_per_call(got, "jit_prefill", ("attn",)) == \
        pytest.approx(0.7)
    # every scope but one, by direction; unscoped time is in no scope
    assert sc.ms_per_call(got, "jit_decode", directions=("fwd",),
                          but=("embed",)) == pytest.approx(1400 / 2 / 1e3)
    assert sc.ms_per_call(got, "jit_decode", directions=("bwd",)) == 0.0
    assert sc.ms_per_call(got, "jit_train_step", ("optimizer",)) is None
    assert sc.unscoped_pct(got, sc.SERVE_MODULES) == \
        pytest.approx(100.0 * 100 / 2400)
    assert sc.unscoped_pct(got, (sc.TRAIN_MODULE,)) is None


def test_the_log_names_each_module_once_and_what_is_unscoped(got):
    lines = sc._log_lines(got)
    assert lines[0] == (
        "device by scope: jit_decode 0.80 ms a call x 2, 0.80 in leaves: "
        "attn.core 0.40, moe.experts 0.15, attn.cache 0.10, embed 0.05, "
        "moe.route 0.05, unscoped 0.05")
    assert lines[1].startswith("unscoped in jit_decode: ") and \
        "fusion.9 0.025" in lines[1] and "(2 instructions)" in lines[1]
    assert lines[2].startswith("device by scope: jit_prefill 0.80 ms a call "
                               "x 1") and lines[2].endswith("unscoped 0.00")
    assert len(lines) == 3


def test_a_backward_scope_shows_its_directions():
    tables = {"jit_train_step": {"fusion.1": ("mlp", "fwd"),
                                 "fusion.2": ("mlp", "bwd"),
                                 "fusion.3": ("mlp", "remat"),
                                 "fusion.4": ("optimizer", "fwd")}}
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules",
         "events": [_ev("jit_train_step(1)", 0, 1000)]},
        {"name": "XLA Ops", "events": [
            _ev("fusion.1", 0, 100), _ev("fusion.3", 100, 300),
            _ev("fusion.2", 300, 600), _ev("fusion.4", 600, 1000)]}]}
    got = sc.build(dev, tables)
    assert sc._log_lines(got) == [
        "device by scope: jit_train_step 1.00 ms a call x 1, 1.00 in "
        "leaves: mlp 0.60 (bwd 0.30 fwd 0.10 remat 0.20), optimizer 0.40, "
        "unscoped 0.00"]
    step = sc.TRAIN_MODULE
    parts = [sc.ms_per_call(got, step, directions=(d,), but=("optimizer",))
             for d in ("fwd", "bwd", "remat")]
    assert parts == pytest.approx([0.1, 0.3, 0.2])
    assert sum(parts) + sc.ms_per_call(got, step, ("optimizer",)) == \
        pytest.approx(got[step]["module_us"] / 1e3)


@pytest.mark.parametrize("case", ["no compiled_programs", "empty table",
                                  "no device plane", "no known module"])
def test_every_reader_returns_none_where_there_is_nothing_to_join(
        case, monkeypatch):
    """An older commit, a program that compiled nothing through
    ``log_compile``, a CPU rehearsal, a trace of other modules: no reader
    raises, every one returns None, nothing is logged."""
    import fleetx_tpu.observability.trace as program_trace

    red = trace_reduce.reduce(_planes())
    if case == "no compiled_programs":
        monkeypatch.delattr(program_trace, "compiled_programs")
    elif case == "empty table":
        monkeypatch.setattr(program_trace, "compiled_programs",
                            lambda: {"jit_decode": {}})
    elif case == "no device plane":
        monkeypatch.setattr(program_trace, "compiled_programs",
                            lambda: dict(TABLES))
        red = trace_reduce.reduce(_planes()[1:])
    else:
        monkeypatch.setattr(program_trace, "compiled_programs",
                            lambda: {"jit_eval_step": {"fusion.1":
                                                       ("mlp", "fwd")}})
    ctx = argparse.Namespace(err=io.StringIO(), trace_dir="/nonexistent")
    info = {"ctx": ctx}
    m = Manifest(ROOT)
    for name in NEW:
        reader = manifest_mod.load_module(m.reader_path(name))
        assert reader.read(None, {}, red, info) is None, name
    assert red[sc.CACHE_KEY] is None
    assert "device by scope" not in ctx.err.getvalue()


def test_of_run_joins_once_and_logs_the_lines(monkeypatch):
    import fleetx_tpu.observability.trace as program_trace

    monkeypatch.setattr(program_trace, "compiled_programs",
                        lambda: dict(TABLES))
    red = trace_reduce.reduce(_planes())
    ctx = argparse.Namespace(err=io.StringIO(), trace_dir="/nonexistent")
    got = sc.of_run(red, {"ctx": ctx})
    assert got is sc.of_run(red, {"ctx": ctx})          # cached
    log = ctx.err.getvalue().splitlines()
    assert log[0].startswith("device scope tables: 2 programs, 12 "
                             "instructions, made in ")
    assert sum(ln.startswith("device by scope: ") for ln in log) == 2
    m = Manifest(ROOT)
    read = {name: manifest_mod.load_module(m.reader_path(name)).read(
        None, {}, red, {"ctx": ctx}) for name in NEW}
    assert read["decode_attn_ms"] == pytest.approx(0.5)
    assert read["decode_ffn_ms"] == pytest.approx(0.2)
    assert read["decode_route_ms"] == pytest.approx(0.05)
    assert read["chunk_attn_ms"] == pytest.approx(0.7)
    assert read["chunk_route_ms"] == pytest.approx(0.1)
    assert read["serve_unscoped_pct"] == pytest.approx(100 * 100 / 2400)
    # no trace file to read the program's serve.tick spans from
    assert read["cache_write_ms"] is None
    assert all(read[n] is None for n in NEW if "scope_" in n
               or n == "train_unscoped_pct")


def test_cache_writes_are_per_traced_tick(monkeypatch):
    from benchmarks import program_spans

    red = trace_reduce.reduce(_planes())
    red[sc.CACHE_KEY] = sc.build(red["_device0"], TABLES)
    red[program_spans.CACHE_KEY] = {"by_name": {"serve.tick": [1, 2]}}
    assert sc.ms_per_tick(red, {}, ("attn.cache",)) == \
        pytest.approx((200 + 200) / 2 / 1e3)
    red[program_spans.CACHE_KEY] = None
    assert sc.ms_per_tick(red, {}, ("attn.cache",)) is None


def test_the_manifest_holds_the_fifteen_new_metrics():
    m = Manifest(ROOT)
    assert len(NEW) == 15
    for name, (moves, cells, unit) in NEW.items():
        entry = m.per_layer[name]
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        assert (entry["unit"], entry["better"], entry["source"],
                entry["moves"], entry["workloads"]) == \
            (unit, "lower", "program_span", moves, cells), name
        assert hasattr(manifest_mod.load_module(m.reader_path(name)), "read")
    older = {e["layer"] for n, e in m.per_layer.items() if n not in NEW}
    assert {m.per_layer[n]["layer"] for n in NEW} <= older   # no new layer
    assert list(m.per_layer)[-15:] == list(NEW)              # appended
