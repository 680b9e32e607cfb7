"""The readers of the program's own spans (``benchmarks/program_spans.py``)
on hand-written planes and on toy cells rehearsed on the CPU. No number
here stands for a device."""

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
from benchmarks import program_spans as ps  # noqa: E402
from benchmarks import run, trace_reduce  # noqa: E402
from benchmarks.manifest import Manifest  # noqa: E402
from fleetx_tpu.observability.metrics import get_registry  # noqa: E402
from fleetx_tpu.observability.trace import HOT_LOOP_SPANS  # noqa: E402

NEW = {"tick_host_ms": "itl_p95_ms", "tick_idle_ms": "itl_p95_ms",
       "ttft_queue_ms": "ttft_mean_ms", "ttft_prefill_wait_ms": "ttft_mean_ms",
       "ttft_prefill_run_ms": "ttft_mean_ms",
       "fit_host_ms": "train_tokens_per_s",
       "step_host_gap_ms": "train_tokens_per_s"}


def _ev(name, ts, end, **args):
    return {"name": name, "ts": float(ts), "dur": float(end - ts),
            "args": args}


def _serve_planes():
    """Two ticks. The device idles 300 us inside the first tick's
    ``serve.emit``, 10 us (under the floor) later in it, 400 us between the
    ticks, and 100 us in the second tick's ``serve.gauges``."""
    dev = {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
        _ev("fusion.1", 0, 1000), _ev("fusion.2", 1300, 1400),
        _ev("fusion.3", 1410, 1600), _ev("fusion.4", 2000, 3000)]}]}
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        _ev("bench:engine_step", 0, 1500),
        _ev("serve.tick", 0, 1500, tick=0),
        _ev("serve.admit", 5, 20),
        _ev("serve.prefill", 20, 90, rid="b1", chunk=0),
        _ev("serve.schedule", 90, 95),
        _ev("serve.decode", 95, 100),
        _ev("serve.decode.wait", 100, 850),
        _ev("serve.emit", 900, 1400),
        _ev("serve.gauges", 1400, 1490),
        _ev("bench:engine_step", 1900, 3100),
        _ev("serve.tick", 1900, 3100, tick=1),
        _ev("serve.prefill", 1910, 2000, rid="b1", chunk=1),
        _ev("serve.prefill.wait", 1950, 1990),
        _ev("serve.decode.wait", 2100, 2950),
        _ev("serve.gauges", 3000, 3090),
        _ev("some.other.annotation", 0, 5000)]}]}
    return [dev, host]


def _fit_planes():
    """Three steps of 1000 us whose working spans add up to 180, 240 and
    (cut off by the trace's end) more. The device idles 150 us between
    steps, each gap's middle in ``fit.log``, and 410 us before the first
    step, outside every span; the trace ends with the device's last op (the
    window's edges are device ops and ``bench:`` spans, as in ``reduce``)."""
    ops, host = [_ev("fusion.warm", -500, -300)], []
    for k in range(3):
        t = 1000 * k
        ops.append(_ev(f"fusion.{k}", t + 110, t + 960))
        host += [_ev("data_fetch", t + 40, t + 60),
                 _ev("shard_batch", t + 60, t + 90),
                 _ev("train_step", t + 100, t + 160 + 60 * k, step=k),
                 _ev("fit.fetch_metrics", t + 300, t + 985),
                 _ev("fit.log", t + 985, t + 1040)]
    dev = {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops",
                                               "events": ops}]}
    return [dev, {"name": "/host:CPU", "lines": [{"name": "python",
                                                  "events": host}]}]


def _built(planes):
    red = trace_reduce.reduce(planes)
    return red, ps.build(planes, red["_device0"], dict(HOT_LOOP_SPANS))


def test_spans_are_kept_by_name_with_their_arguments():
    _, got = _built(_serve_planes())
    assert len(got["by_name"]["serve.tick"]) == 2
    assert "some.other.annotation" not in got["by_name"]
    assert "bench:engine_step" not in got["by_name"]
    chunk = got["by_name"]["serve.prefill"][1]
    assert chunk[3] == {"rid": "b1", "chunk": 1}
    # an enclosing span sorts before what it holds
    names = [sp[0] for sp in got["spans"]]
    assert names.index("serve.tick") < names.index("serve.admit")


def test_self_time_is_a_span_less_what_lies_inside_it():
    _, got = _built(_serve_planes())
    tick0, tick1 = got["by_name"]["serve.tick"]
    inner0 = 15 + 70 + 5 + 5 + 750 + 500 + 90
    assert ps.self_us(got["spans"], tick0) == pytest.approx(1500 - inner0)
    # serve.prefill.wait lies inside serve.prefill: counted once
    assert ps.self_us(got["spans"], tick1) == pytest.approx(
        1200 - (90 + 850 + 90))
    prefill1 = got["by_name"]["serve.prefill"][1]
    assert ps.self_us(got["spans"], prefill1) == pytest.approx(90 - 40)
    assert ps.covered_us([(0, 10), (5, 20), (30, 40)]) == pytest.approx(30)
    # by name, every microsecond of the two ticks lies under one name
    by = ps.self_by_name(got["spans"])
    assert by["serve.prefill"] == [2, pytest.approx((70 + 50) / 1e6)]
    assert by["serve.tick"] == [2, pytest.approx(
        (1500 - inner0 + 1200 - (90 + 850 + 90)) / 1e6)]
    assert sum(sec for _, sec in by.values()) == pytest.approx(2700e-6)


def test_idle_gaps_go_to_the_shortest_program_span_or_outside():
    red, got = _built(_serve_planes())
    idle = got["idle"]
    assert idle["serve.emit"] == [1, pytest.approx(300e-6)]
    assert idle[ps.OUTSIDE] == [1, pytest.approx(400e-6)]
    assert idle["serve.gauges"] == [1, pytest.approx(100e-6)]
    # the 10 us gap is under the floor; nothing goes to the enclosing tick
    assert set(idle) == {"serve.emit", ps.OUTSIDE, "serve.gauges"}
    # the same gaps as the benchmark's own reduction finds
    assert sum(sec for _, sec in idle.values()) == pytest.approx(
        sum(sec for _, sec in red["idle_gaps"]))
    assert sum(sec for _, sec in idle.values()) == pytest.approx(
        red["window_s"] - red["busy_s"] - 10e-6)


def test_tick_readers_on_the_hand_written_planes():
    _, got = _built(_serve_planes())
    # 1500 - 750 and 1200 - (40 + 850): the median of two is their mean
    assert ps.host_ms_per_unit(got, "serve.tick") == pytest.approx(
        (0.750 + 0.310) / 2)
    assert ps.idle_ms_per_unit(got, "serve.tick") == pytest.approx(0.4)
    assert ps.host_ms_per_unit(got, "train_step") is None
    assert ps.fit_host_ms(got) is None


def test_fit_readers_on_the_hand_written_planes():
    red, got = _built(_fit_planes())
    # per step, from one train_step's start to the next: train_step itself,
    # fit.log, then the next step's data_fetch and shard_batch
    assert ps.fit_host_ms(got) == pytest.approx(
        ((60 + 55 + 20 + 30) + (120 + 55 + 20 + 30)) / 2 / 1e3)
    idle = got["idle"]
    assert idle["fit.log"] == [2, pytest.approx(300e-6)]
    assert idle[ps.OUTSIDE] == [1, pytest.approx(410e-6)]
    assert ps.idle_ms_per_unit(got, "train_step") == pytest.approx(
        (300 + 410) / 3 / 1e3)
    assert sum(sec for _, sec in idle.values()) == pytest.approx(
        red["window_s"] - red["busy_s"])


def test_without_a_device_plane_spans_read_and_idle_does_not():
    planes = _serve_planes()[1:]
    got = ps.build(planes, None, dict(HOT_LOOP_SPANS))
    assert got["idle"] is None
    assert ps.host_ms_per_unit(got, "serve.tick") == pytest.approx(0.53)
    assert ps.idle_ms_per_unit(got, "serve.tick") is None


def test_a_program_without_the_spans_gives_nothing_and_does_not_raise(
        tmp_path, monkeypatch):
    """The parent commit: no table, no ``Histogram.last``, no spans."""
    ctx = argparse.Namespace(trace_dir=str(tmp_path), err=io.StringIO())
    assert ps.of_run({}, {"ctx": ctx}) is None        # no trace file
    monkeypatch.setattr(ps, "span_table", dict)
    trace = {}
    assert ps.of_run(trace, {"ctx": ctx}) is None and \
        trace[ps.CACHE_KEY] is None
    assert ps.host_ms_per_unit(None, "serve.tick") is None
    assert ps.idle_ms_per_unit(None, "serve.tick") is None
    assert ps.fit_host_ms(None) is None
    from fleetx_tpu.observability.metrics import Histogram
    monkeypatch.delattr(Histogram, "last")
    assert ps.first_token_wait_ms({"n_ttft": 3}, "serving_queue_wait") is None
    assert ps.first_token_waits({"n_ttft": 3}, {"ctx": ctx}) is None
    assert ctx.err.getvalue() == ""


def test_first_token_wait_reads_the_newest_samples():
    hist = get_registry().histogram("serving_test_only_wait")
    for v in (9.0, 0.1, 0.2, 0.3):
        hist.record(v)
    assert ps.first_token_wait_ms(
        {"n_ttft": 3}, "serving_test_only_wait") == pytest.approx(200.0)
    assert ps.first_token_wait_ms({"n_ttft": 0},
                                  "serving_test_only_wait") is None
    assert ps.first_token_wait_ms({"n_ttft": 5},
                                  "serving_test_only_wait") is None


def test_manifest_holds_the_seven_new_metrics():
    """By name and by layer: where in ``per_layer`` they stand, and how
    many layers later PRs have named, is not this test's business."""
    m = Manifest(ROOT)
    for name, moves in NEW.items():
        entry = m.per_layer[name]
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        assert (entry["unit"], entry["better"], entry["moves"]) == \
            ("ms", "lower", moves)
        assert hasattr(manifest_mod.load_module(m.reader_path(name)), "read")
    older = {e["layer"] for n, e in m.per_layer.items() if n not in NEW}
    assert {m.per_layer[n]["layer"] for n in NEW} <= older   # no new layer
    assert m.per_layer["tick_host_ms"]["layer"] == \
        m.per_layer["decode_occupancy"]["layer"]
    assert m.per_layer["fit_host_ms"]["layer"] == \
        m.per_layer["train_step_ms"]["layer"]


# ------------------------------------------------- toy cells, rehearsed
@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return toy.make_root(str(tmp_path_factory.mktemp("spans_root")))


def _rehearse(toy_root, cell, monkeypatch):
    """One traced rehearsal; returns (result line, the cell's facts, log)."""
    import importlib

    kind = "train_cell" if "train" in cell else "serve_cell"
    cell_code = importlib.import_module(f"benchmarks.{kind}")
    kept = {}
    original = cell_code.run

    def keeping(ctx):
        kept["result"] = original(ctx)
        return kept["result"]

    monkeypatch.setattr(cell_code, "run", keeping)
    out, err = io.StringIO(), io.StringIO()
    args = argparse.Namespace(workload=cell, seed=3000000023, seconds=1.5,
                              trace=1, control="")
    run.run_cell(args, root=toy_root, platforms=("cpu",), out=out, err=err)
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    return line, kept["result"]["facts"], err.getvalue()


def test_serve_rehearsal_reports_the_span_and_wait_metrics(toy_root,
                                                           monkeypatch):
    line, facts, log = _rehearse(toy_root, "toy-closed", monkeypatch)
    assert line["correct"] is True and line["failed"] == 0, log
    got = line["metrics"]
    assert got["tick_host_ms"]["value"] > 0
    assert "tick_idle_ms" not in got       # no device plane on the CPU
    assert "idle by phase" not in log
    said = [ln for ln in log.splitlines() if ln.startswith("host by phase: ")]
    assert len(said) == 1 and "serve.emit " in said[0] and " ms x " in said[0]
    parts = [got[n]["value"] for n in ("ttft_queue_ms",
                                       "ttft_prefill_wait_ms",
                                       "ttft_prefill_run_ms")]
    assert facts["n_ttft"] > 0 and all(p >= 0 for p in parts)
    mean_ms = 1e3 * sum(facts["ttft_s"]) / len(facts["ttft_s"])
    assert sum(parts) == pytest.approx(mean_ms, rel=1e-6)
    said = [ln for ln in log.splitlines() if ln.startswith("first-token waits")]
    assert len(said) == 1 and f"over {facts['n_ttft']} first tokens" in said[0]


def test_train_rehearsal_reports_the_fit_host_time(toy_root, monkeypatch):
    line, _, log = _rehearse(toy_root, "toy-train", monkeypatch)
    assert line["correct"] is True, log
    assert line["metrics"]["fit_host_ms"]["value"] > 0
    assert "step_host_gap_ms" not in line["metrics"]
