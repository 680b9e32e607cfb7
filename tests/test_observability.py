"""Unified telemetry subsystem (docs/observability.md): registry semantics,
span tracer output, MFU arithmetic, sink formats, schema gating, and the
engine-level JSONL pipeline."""

import io
import json
import logging
import os

import numpy as np
import pytest

from fleetx_tpu.observability import (
    DerivedMetrics, MetricsRegistry, Observability, Tracer, mfu, set_tracer,
    span)
from fleetx_tpu.observability.schema import (
    chrome_trace_errors, validate_jsonl, validate_record)
from fleetx_tpu.observability.sinks import (
    CsvSink, JsonlSink, PrometheusTextfileSink, build_sinks)


# ---------------------------------------------------------------- registry

def test_counter_gauge_histogram_basics():
    r = MetricsRegistry()
    r.counter("steps").inc()
    r.counter("steps").inc(4)
    assert r.counter("steps").value == 5
    r.gauge("loss").set(2.5)
    assert r.gauge("loss").value == 2.5

    h = r.histogram("lat", window=100)
    for v in range(1, 101):  # 1..100
        h.record(v)
    s = h.summary()
    assert s["count"] == 100 and s["min"] == 1 and s["max"] == 100
    assert abs(s["p50"] - 50.5) < 1e-9
    assert abs(s["p95"] - 95.05) < 1e-9
    assert abs(s["p99"] - 99.01) < 1e-9

    # same name returns the same object (get-or-create)
    assert r.histogram("lat") is h


def test_histogram_window_eviction_keeps_totals():
    r = MetricsRegistry()
    h = r.histogram("x", window=4)
    for v in [10, 10, 10, 10, 1, 1, 1, 1]:
        h.record(v)
    assert h.summary()["max"] == 1  # old samples evicted
    assert h.total_count == 8 and h.total_sum == 44.0  # totals survive


def test_reset_semantics():
    r = MetricsRegistry()
    r.counter("c").inc(3)
    r.gauge("g").set(7)
    r.histogram("h").record(1.0)
    r.reset_window()  # histograms only
    assert r.histogram("h").summary() == {"count": 0}
    assert r.counter("c").value == 3 and r.gauge("g").value == 7
    assert r.histogram("h").total_count == 1  # window reset keeps totals
    r.reset()  # everything
    assert r.counter("c").value == 0 and r.gauge("g").value is None
    assert r.histogram("h").total_count == 0


def test_timer_records_histogram_and_total():
    r = MetricsRegistry()
    with r.timer("phase"):
        pass
    assert r.histogram("phase").summary()["count"] == 1
    assert r.counter("phase_seconds_total").value > 0


# ------------------------------------------------------------------ tracer

def test_span_nesting_emits_valid_chrome_trace(tmp_path):
    tracer = Tracer()
    prev = set_tracer(tracer)
    try:
        with span("outer", step=1):
            with span("inner"):
                pass
    finally:
        set_tracer(prev)
    events = tracer.events
    names = [e["name"] for e in events]
    assert names == ["inner", "outer"]  # spans close inner-first
    inner, outer = events
    # nesting: inner's [ts, ts+dur] lies within outer's on the same tid
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1.0
    assert inner["tid"] == outer["tid"]
    assert outer["args"] == {"step": 1}

    path = tracer.save(str(tmp_path / "trace.json"))
    with open(path) as f:
        trace = json.load(f)
    assert chrome_trace_errors(trace) == []
    assert {e["ph"] for e in trace["traceEvents"]} == {"X"}


def test_span_as_decorator_records_event():
    tracer = Tracer()
    prev = set_tracer(tracer)
    try:
        @span("decorated")
        def work(x):
            return x + 1

        assert work(1) == 2
        assert work(2) == 3
    finally:
        set_tracer(prev)
    assert [e["name"] for e in tracer.events] == ["decorated", "decorated"]


def test_span_without_tracer_is_silent():
    prev = set_tracer(None)
    try:
        with span("nothing"):
            pass
    finally:
        set_tracer(prev)


def test_tracer_event_cap_drops_not_grows():
    tracer = Tracer(max_events=3)
    for i in range(5):
        tracer.add_event(f"e{i}", 0.0, 1.0)
    assert len(tracer.events) == 3
    assert tracer.to_chrome_trace()["otherData"]["dropped_events"] == 2


# --------------------------------------------------------------------- MFU

def test_mfu_matches_hand_computed_gpt_345m():
    """GPT-345M (L=24, H=1024, S=1024, V=50304) on one v5e chip at 38,676
    tokens/s (`gpt345m-train-b8s1024`: ledger, PR 30). The run's own MFU
    counts attention without the causal half, so it reads 0.476 where the
    ledger's `train_mfu_pct` reads 44.7."""
    from fleetx_tpu.utils.hardware import gpt_flops_per_token

    L, H, S, V = 24, 1024, 1024, 50304
    n_params = L * 12 * H * H + V * H           # 353,501,184
    assert n_params == 353_501_184
    fpt = gpt_flops_per_token(L, H, S, vocab_size=V)
    # 6N + 12·L·H·S = 2,121,007,104 + 301,989,888
    assert fpt == 6.0 * n_params + 12.0 * L * H * S
    assert fpt == 2_422_996_992.0

    got = mfu(38_676.0, fpt, 197e12, 1)
    expected = 38_676.0 * 2_422_996_992.0 / 197e12   # ≈ 0.4757
    assert got == pytest.approx(expected, rel=1e-12)
    assert 0.47 < got < 0.48

    # unknown inputs → null, never zero
    assert mfu(None, fpt, 197e12, 1) is None
    assert mfu(38_676.0, None, 197e12, 1) is None
    assert mfu(38_676.0, fpt, None, 1) is None


def test_derived_metrics_ewma_and_stall_fraction():
    d = DerivedMetrics(flops_per_token=1e9, peak_flops_per_chip=1e14,
                       n_devices=2, ewma_alpha=0.5)
    r1 = d.update(0.5, 16, tokens_per_sample=128, steps_in_window=2,
                  stall_seconds_total=0.25)
    assert r1["samples_per_sec"] == 32.0
    assert r1["tokens_per_sec"] == 32.0 * 128
    assert r1["step_time_ewma"] == 0.5
    # 0.25s stalled over 2 steps × 0.5s window wall = 25%
    assert r1["data_stall_frac"] == pytest.approx(0.25)
    assert r1["mfu"] == pytest.approx(32.0 * 128 * 1e9 / (2 * 1e14))

    r2 = d.update(0.3, 16, tokens_per_sample=128, steps_in_window=2,
                  stall_seconds_total=0.25)  # no NEW stall time
    assert r2["step_time_ewma"] == pytest.approx(0.5 * 0.3 + 0.5 * 0.5)
    assert r2["data_stall_frac"] == 0.0

    # non-LM module: tokens/sec and MFU are null, samples/sec still real
    r3 = d.update(0.3, 16, tokens_per_sample=None, steps_in_window=1,
                  stall_seconds_total=0.25)
    assert r3["tokens_per_sec"] is None and r3["mfu"] is None
    assert r3["samples_per_sec"] == pytest.approx(16 / 0.3)


# ------------------------------------------------------------------- sinks

def test_jsonl_and_csv_sinks_roundtrip(tmp_path):
    rec1 = {"step": 1, "loss": 2.0, "mfu": None}
    rec2 = {"step": 2, "loss": 1.5, "mfu": 0.4, "extra": "dropped-from-csv"}
    jp, cp = str(tmp_path / "m.jsonl"), str(tmp_path / "m.csv")
    js, cs = JsonlSink(jp), CsvSink(cp)
    for r in (rec1, rec2):
        js.emit(r)
        cs.emit(r)
    js.close(), cs.close()

    lines = [json.loads(l) for l in open(jp)]
    assert lines == [rec1, rec2]
    rows = open(cp).read().splitlines()
    assert rows[0] == "step,loss,mfu"
    assert rows[1] == "1,2.0,"          # None → empty cell
    assert rows[2] == "2,1.5,0.4"       # extra key projected away


def test_prometheus_textfile_sink(tmp_path):
    p = str(tmp_path / "m.prom")
    s = PrometheusTextfileSink(p)
    s.emit({"loss": 2.0, "mfu": None, "engine": "EagerEngine", "step": 3})
    text = open(p).read()
    assert "fleetx_loss 2.0" in text
    assert "fleetx_step 3" in text
    assert "engine" not in text and "mfu" not in text  # numbers only
    # atomic rewrite: second emit replaces, not appends
    s.emit({"loss": 1.0})
    text = open(p).read()
    assert "fleetx_loss 1.0" in text and "fleetx_loss 2.0" not in text


def test_build_sinks_skips_unknown_names(tmp_path):
    sinks = build_sinks(["jsonl", "nope"], str(tmp_path))
    assert len(sinks) == 1 and isinstance(sinks[0], JsonlSink)
    sinks[0].close()


# ------------------------------------------------------------------ schema

def test_schema_accepts_valid_and_rejects_malformed():
    ok = {"step": 3, "ts": 1.0, "loss": 2.0, "step_time": 0.1,
          "tokens_per_sec": None, "mfu": None, "unknown_extra": "fine"}
    assert validate_record(ok) == []
    assert validate_record({"step": 3}) != []                 # missing keys
    bad_type = dict(ok, loss="2.0")
    assert any("loss" in e for e in validate_record(bad_type))
    nan = dict(ok, loss=float("nan"))
    assert any("NaN" in e for e in validate_record(nan))
    boolean = dict(ok, step=True)                             # bool ≠ int
    assert any("step" in e for e in validate_record(boolean))


def test_validate_jsonl_line_numbers(tmp_path):
    p = tmp_path / "m.jsonl"
    good = {"step": 1, "ts": 1.0, "loss": 2.0, "step_time": 0.1,
            "tokens_per_sec": 10.0, "mfu": None}
    p.write_text(json.dumps(good) + "\nnot json\n")
    count, errors = validate_jsonl(str(p))
    assert count == 2
    assert len(errors) == 1 and errors[0].startswith("line 2:")


# ----------------------------------------------------------- log satellites

def test_color_formatter_follows_handler_stream():
    from fleetx_tpu.utils.log import _ColorFormatter

    class TtyIO(io.StringIO):
        def isatty(self):
            return True

    rec = logging.LogRecord("t", logging.INFO, __file__, 1, "hello", (), None)
    pipe_handler = logging.StreamHandler(io.StringIO())
    fmt = _ColorFormatter("%(message)s", stream=pipe_handler)
    assert "\033[" not in fmt.format(rec)  # pipe: no ANSI even if stderr=tty

    tty_handler = logging.StreamHandler(TtyIO())
    fmt = _ColorFormatter("%(message)s", stream=tty_handler)
    assert fmt.format(rec).startswith("\033[")
    # setStream swap is honoured (stream resolved per format call)
    tty_handler.setStream(io.StringIO())
    assert "\033[" not in fmt.format(rec)


def test_log_level_env_override(monkeypatch):
    from fleetx_tpu.utils.log import _initial_level

    monkeypatch.delenv("FLEETX_LOG_LEVEL", raising=False)
    assert _initial_level() == logging.INFO
    monkeypatch.setenv("FLEETX_LOG_LEVEL", "debug")
    assert _initial_level() == logging.DEBUG
    monkeypatch.setenv("FLEETX_LOG_LEVEL", "TRAIN")
    assert _initial_level() == 21
    monkeypatch.setenv("FLEETX_LOG_LEVEL", "15")
    assert _initial_level() == 15
    monkeypatch.setenv("FLEETX_LOG_LEVEL", "bogus")
    assert _initial_level() == logging.INFO


# ------------------------------------------------------------ engine smoke

VOCAB, SEQ, BATCH = 128, 32, 8


def _obs_engine(tmp_path, devices, max_steps=4):
    from fleetx_tpu.core.engine import EagerEngine
    from fleetx_tpu.core.module import GPTModule
    from fleetx_tpu.optims.lr_scheduler import build_lr_scheduler
    from fleetx_tpu.optims.optimizer import build_optimizer
    from fleetx_tpu.parallel.mesh import build_mesh

    cfg = {
        "Model": dict(vocab_size=VOCAB, hidden_size=64, num_layers=2,
                      num_attention_heads=4, max_position_embeddings=SEQ,
                      hidden_dropout_prob=0.0,
                      attention_probs_dropout_prob=0.0,
                      use_flash_attention=False, dtype="float32",
                      param_dtype="float32"),
        "Engine": {"max_steps": max_steps, "logging_freq": 1, "eval_freq": 0,
                   "save_load": {"save_steps": max_steps,
                                 "output_dir": str(tmp_path / "ckpt")}},
        "Global": {"seed": 7},
        "Observability": {"enable": True,
                          "output_dir": str(tmp_path / "telemetry"),
                          "sinks": ["jsonl", "csv", "prometheus"]},
    }
    module = GPTModule(cfg)
    lr = build_lr_scheduler({"max_lr": 1e-3, "warmup_steps": 1,
                             "decay_steps": 10})
    opt = build_optimizer({"name": "AdamW"}, lr)
    return EagerEngine(cfg, module, optimizer=opt, lr_schedule=lr,
                       mesh=build_mesh({}, devices=devices))


def _batches(n):
    rng = np.random.RandomState(0)
    out = []
    for _ in range(n):
        tokens = rng.randint(0, VOCAB, size=(BATCH, SEQ)).astype(np.int32)
        out.append({
            "tokens": tokens,
            "position_ids": np.broadcast_to(
                np.arange(SEQ, dtype=np.int32), (BATCH, SEQ)).copy(),
            "labels": tokens,
            "loss_mask": np.ones((BATCH, SEQ), np.float32)})
    return out


def test_engine_emits_schema_valid_jsonl_and_trace(tmp_path, devices8):
    eng = _obs_engine(tmp_path, devices8[:1], max_steps=4)
    losses = eng.fit(_batches(4))
    assert len(losses) == 4
    eng.obs.close()

    # -- JSONL: one record per logging window, schema-valid, required keys
    jsonl = tmp_path / "telemetry" / "metrics.jsonl"
    count, errors = validate_jsonl(str(jsonl))
    assert errors == [], errors
    assert count == 4
    records = [json.loads(l) for l in open(jsonl)]
    for r in records:
        for key in ("loss", "step_time", "tokens_per_sec", "mfu"):
            assert key in r, (key, r)
        assert r["mfu"] is None          # CPU: no peak-FLOPs entry → null
        assert r["tokens_per_sec"] > 0   # 8×32 tokens / measured step time
        assert r["engine"] == "EagerEngine"
    assert [r["step"] for r in records] == [1, 2, 3, 4]
    # checkpoint telemetry reached the shared registry
    assert eng.obs.registry.counter("ckpt_saves_total").value >= 1
    assert eng.obs.registry.gauge("ckpt_bytes").value > 0

    # -- other sinks wrote too
    assert (tmp_path / "telemetry" / "metrics.csv").exists()
    assert "fleetx_loss" in (tmp_path / "telemetry" / "metrics.prom").read_text()

    # -- Chrome trace: loadable, spans for every phase incl. checkpoint_save
    trace = json.loads((tmp_path / "telemetry" / "trace.json").read_text())
    assert chrome_trace_errors(trace) == []
    names = {e["name"] for e in trace["traceEvents"]}
    for expected in ("data_fetch", "shard_batch", "train_step",
                     "checkpoint_save", "checkpoint_write"):
        assert expected in names, (expected, names)
    # nesting: checkpoint_write lies inside its checkpoint_save parent
    saves = [e for e in trace["traceEvents"] if e["name"] == "checkpoint_save"]
    writes = [e for e in trace["traceEvents"] if e["name"] == "checkpoint_write"]
    s, w = saves[0], writes[0]
    assert s["ts"] <= w["ts"] and \
        w["ts"] + w["dur"] <= s["ts"] + s["dur"] + 1.0


def test_metrics_report_gates_on_schema(tmp_path, devices8, capsys):
    import tools.metrics_report as mr

    eng = _obs_engine(tmp_path, devices8[:1], max_steps=3)
    eng.fit(_batches(3))
    eng.obs.close()
    jsonl = str(tmp_path / "telemetry" / "metrics.jsonl")

    assert mr.main([jsonl]) == 0
    out = capsys.readouterr().out
    assert "tokens/s" in out and "loss" in out

    summary_path = str(tmp_path / "summary.json")
    assert mr.main([jsonl, "--json", summary_path]) == 0
    summary = json.loads(open(summary_path).read())
    assert summary["records"] == 3 and summary["loss"]["mean"] > 0

    # malformed record → non-zero exit (the bench gate)
    bad = str(tmp_path / "bad.jsonl")
    with open(jsonl) as f, open(bad, "w") as g:
        g.write(f.readline())
        g.write('{"step": "oops"}\n')
    assert mr.main([bad]) != 0
    # empty file → non-zero
    empty = str(tmp_path / "empty.jsonl")
    open(empty, "w").close()
    assert mr.main([empty]) != 0
    # missing file → non-zero
    assert mr.main([str(tmp_path / "nope.jsonl")]) != 0


def test_observability_disabled_is_noop(tmp_path, devices8):
    obs = Observability(None)
    assert not obs.enabled and obs.sinks == [] and obs.tracer is None
    with obs.span("x"):
        pass
    with obs.timed_span("y"):
        pass
    obs.emit({"loss": 1.0})
    obs.flush(), obs.close()
    assert not (tmp_path / "telemetry").exists()


def test_inference_latency_histogram(tmp_path, devices8):
    import jax.export  # noqa: F401 — registers the lazy jax.export submodule
    import jax.numpy as jnp

    from fleetx_tpu.core.engine.inference_engine import InferenceEngine
    from fleetx_tpu.utils.export import export_model

    def fn(params, x):
        return x * params["w"]

    export_model(fn, (jnp.zeros((2, 3), jnp.float32),),
                 str(tmp_path / "exported"), {"w": jnp.float32(2.0)},
                 platforms=("cpu",))
    eng = InferenceEngine(str(tmp_path / "exported"))
    eng.metrics.reset()
    for _ in range(3):
        out = eng.predict([np.ones((2, 3), np.float32)])
    np.testing.assert_allclose(out[0], 2.0)
    assert eng.metrics.counter("requests_total").value == 3
    # first (compile) call is tracked separately from warm requests
    assert eng.metrics.histogram("request_compile_latency").summary()["count"] == 1
    warm = eng.latency_summary()
    assert warm["count"] == 2
    assert {"p50", "p95", "p99"} <= set(warm)


# ----------------------------------------------- flight recorder (gang obs)

def test_flight_recorder_ring_bounds_and_atomic_dump(tmp_path):
    from fleetx_tpu.observability import FlightRecorder

    rec = FlightRecorder(str(tmp_path), rank=3, world=4, capacity=16)
    for i in range(40):
        rec.record("span", f"e{i}", i=i)
    events = rec.events()
    assert len(events) == 16                       # bounded ring
    assert events[0]["name"] == "e24"              # oldest fell off
    assert events[-1]["name"] == "e39"

    path = rec.dump("unit-test")
    assert path.endswith("flight_rank3.json")
    data = json.load(open(path))
    assert data["rank"] == 3 and data["world"] == 4
    assert data["reason"] == "unit-test"
    assert data["recorded_total"] == 40 and len(data["events"]) == 16
    # atomic publish: nothing but the dump itself on disk
    assert os.listdir(tmp_path) == ["flight_rank3.json"]

    rec.record("vote", "later")
    rec.dump("second")                             # overwrite, newest wins
    data2 = json.load(open(path))
    assert data2["reason"] == "second"
    assert data2["events"][-1]["name"] == "later"
    assert rec.dump_count == 2


def test_flight_module_helpers_noop_without_recorder(tmp_path):
    from fleetx_tpu.observability import FlightRecorder, flight

    flight.install(None)
    flight.note("k", "n")                          # silent no-op
    assert flight.dump("x") is None

    rec = FlightRecorder(str(tmp_path), rank=0, world=2)
    prev = flight.install(rec)
    try:
        flight.note("vote", "loop_flags", round=1)
        assert flight.dump("r") == rec.path
        events = json.load(open(rec.path))["events"]
        assert events[0]["kind"] == "vote" and events[0]["round"] == 1
    finally:
        flight.install(prev)


def test_span_feeds_flight_ring(tmp_path):
    from fleetx_tpu.observability import FlightRecorder, flight

    rec = FlightRecorder(str(tmp_path))
    prev = flight.install(rec)
    try:
        with span("phase_x", step=2):
            pass
        # span args that collide with event fields must stay harmless:
        # they ride nested under "args", never clobbering the timestamp
        with span("phase_y", kind="full", t=0):
            pass
    finally:
        flight.install(prev)
    events = rec.events()
    assert events[0]["kind"] == "span"
    assert events[0]["name"] == "phase_x" and events[0]["args"] == {"step": 2}
    assert events[0]["dur_ms"] >= 0.0
    assert events[1]["kind"] == "span" and events[1]["t"] > 1e9
    assert events[1]["args"] == {"kind": "full", "t": 0}


# -------------------------------------------------- rank skew (gang obs)

def test_derived_metrics_rank_skew_ewma():
    d = DerivedMetrics(ewma_alpha=0.5)
    assert d.rank_skew() == {} and d.slowest_rank() is None
    d.update_arrivals({0: 100.0, 1: 100.5})
    # two-rank median is the midpoint: skew splits ±0.25
    assert d.rank_skew()[1] == pytest.approx(0.25)
    assert d.rank_skew()[0] == pytest.approx(-0.25)
    d.update_arrivals({0: 200.0, 1: 200.1})
    assert d.rank_skew()[1] == pytest.approx(0.5 * 0.05 + 0.5 * 0.25)
    assert d.slowest_rank() == 1
    # a one-rank census carries no cross-rank information
    before = d.rank_skew()
    d.update_arrivals({0: 1.0})
    assert d.rank_skew() == before


# ----------------------------------------------- snapshot merge (gang obs)

def _window_record(step, *, step_time, tps, loss, mfu=None, skew=None):
    rec = {"ts": 10.0 + step, "step": step, "loss": loss,
           "step_time": step_time, "tokens_per_sec": tps,
           "samples_per_sec": tps / 32.0 if tps else None, "mfu": mfu,
           "global_batch_size": 16}
    if skew is not None:
        rec["rank_skew"] = skew
    return rec


def test_merge_snapshots_sums_counters_and_attributes_extremes():
    from fleetx_tpu.observability import gang

    reg0, reg1 = MetricsRegistry(), MetricsRegistry()
    reg0.counter("rollbacks_total").inc(1)
    reg1.counter("rollbacks_total").inc(2)
    reg1.counter("nonfinite_skips").inc(5)
    reg0.histogram("barrier_wait_ms").record(10.0)
    reg1.histogram("barrier_wait_ms").record(30.0)
    reg1.histogram("barrier_wait_ms").record(50.0)
    s0 = gang.snapshot(_window_record(5, step_time=0.1, tps=1000.0,
                                      loss=2.0, mfu=0.4, skew=-0.01),
                       reg0, rank=0, window=0)
    s1 = gang.snapshot(_window_record(5, step_time=0.3, tps=400.0,
                                      loss=2.5, skew=0.2),
                       reg1, rank=1, window=0)
    merged = gang.merge_snapshots({0: [s0], 1: [s1]}, world=2)
    assert len(merged) == 1
    m = merged[0]
    assert m["scope"] == "gang" and m["world"] == 2
    assert m["ranks_reported"] == 2 and m["schema_version"] == 2
    # counters summed across ranks
    assert m["rollbacks_total"] == 3.0
    assert m["nonfinite_skips"] == 5.0
    # step-time spread with rank attribution; the slowest rank IS the
    # fleet's effective rate in a lockstep gang
    assert m["step_time"] == 0.3
    assert m["step_time_min"] == 0.1 and m["step_time_max"] == 0.3
    assert m["step_time_median"] == pytest.approx(0.2)
    assert m["step_time_min_rank"] == 0 and m["step_time_max_rank"] == 1
    assert m["tokens_per_sec"] == 400.0
    assert m["loss"] == pytest.approx(2.25)
    assert m["mfu"] == 0.4                         # mean of the non-nulls
    assert m["rank_skew_max"] == 0.2 and m["rank_skew_max_rank"] == 1
    # wait histograms pooled: count-weighted mean, extreme with its rank
    assert m["barrier_wait_ms_mean"] == pytest.approx((10 + 30 + 50) / 3)
    assert m["barrier_wait_ms_max"] == 50.0
    assert m["barrier_wait_ms_max_rank"] == 1
    # gang records ride the same schema as step records
    assert validate_record(m) == [], validate_record(m)


def test_merge_snapshots_aligns_windows_and_tolerates_partial():
    from fleetx_tpu.observability import gang

    reg = MetricsRegistry()
    snaps = {
        0: [gang.snapshot(_window_record(1, step_time=0.1, tps=10.0,
                                         loss=1.0), reg, 0, 0),
            gang.snapshot(_window_record(2, step_time=0.1, tps=10.0,
                                         loss=0.9), reg, 0, 1)],
        1: [gang.snapshot(_window_record(1, step_time=0.2, tps=10.0,
                                         loss=1.1), reg, 1, 0)],
    }
    merged = gang.merge_snapshots(snaps, world=2)
    assert [m["step"] for m in merged] == [1, 2]   # window order
    assert merged[0]["ranks_reported"] == 2
    assert merged[1]["ranks_reported"] == 1        # partial, not dropped


# ---------------------------------------------- gang-mode facade behaviour

def test_gang_mode_stamps_records_and_rank_suffixes_sinks(tmp_path):
    obs = Observability({"enable": True, "gang": True, "sinks": ["jsonl"],
                         "output_dir": str(tmp_path),
                         "trace": {"enable": False}})
    try:
        assert obs.gang_enabled and obs.flight is not None
        obs.emit(_window_record(1, step_time=0.1, tps=10.0, loss=1.0))
        path = tmp_path / "metrics.rank0.jsonl"
        assert path.exists()                       # rank-suffixed file
        rec = json.loads(path.read_text().splitlines()[0])
        assert rec["rank"] == 0 and rec["world"] == 1
        assert rec["schema_version"] == 2
        assert validate_record(rec) == []
        # stash/take cycle: the vote payload drains the pending snapshots
        obs.gang_stash(rec)
        pending = obs.gang_take_pending()
        assert len(pending) == 1 and pending[0]["w"] == 0
        assert obs.gang_take_pending() == []
    finally:
        obs.close()
    from fleetx_tpu.observability import flight as flight_mod
    assert flight_mod.get_recorder() is None       # close releases it


def test_gang_off_keeps_pre_gang_layout(tmp_path, devices8):
    """The acceptance pin: with ``Observability.gang`` off, the emitted
    records carry EXACTLY the pre-gang key set and the pre-gang file
    names — no rank stamps, no per-rank suffixes, no gang stream."""
    eng = _obs_engine(tmp_path, devices8[:1], max_steps=2)
    eng.fit(_batches(2))
    eng.obs.close()
    telemetry = tmp_path / "telemetry"
    names = sorted(os.listdir(telemetry))
    assert "metrics.jsonl" in names
    assert not any("rank" in n or "gang" in n for n in names), names
    pre_gang_keys = {
        "ts", "step", "epoch", "loss", "step_time", "tokens_per_sec",
        "mfu", "lr", "global_batch_size", "engine", "step_time_ewma",
        "samples_per_sec", "data_stall_frac", "grad_norm",
        # HBM attribution keys (PR 10, docs/observability.md) — carried by
        # every record, gang or not; the pin guards against GANG leakage
        # (rank/world/schema_version stamps), not against new telemetry
        "hbm_stats", "hbm_peak_bytes", "hbm_model_error",
    }
    for line in (telemetry / "metrics.jsonl").read_text().splitlines():
        assert set(json.loads(line)) == pre_gang_keys


# ------------------------------------------------ log rank-prefix satellite

def test_log_rank_prefix_only_on_gangs():
    from fleetx_tpu.utils.log import _ColorFormatter, set_rank_context

    handler = logging.StreamHandler(io.StringIO())
    fmt = _ColorFormatter("%(message)s", stream=handler)
    rec = logging.LogRecord("t", logging.INFO, __file__, 1, "hello", (),
                            None)
    try:
        set_rank_context(0, 1)
        assert fmt.format(rec) == "hello"          # byte-identical solo
        set_rank_context(1, 2)
        assert fmt.format(rec) == "[r1/2] hello"   # attributable in gangs
        set_rank_context(0, 2)
        assert fmt.format(rec) == "[r0/2] hello"
    finally:
        set_rank_context(0, 1)


# ------------------------------------------- metrics_report rank satellites

def _rank_record(step, rank=None, tps=100.0):
    rec = {"step": step, "ts": float(step), "loss": 2.0, "step_time": 0.1,
           "tokens_per_sec": tps, "mfu": None}
    if rank is not None:
        rec.update(rank=rank, world=2, schema_version=2)
    return rec


def test_metrics_report_directory_merges_rank_files(tmp_path, capsys):
    import tools.metrics_report as mr

    for rank in (0, 1):
        with open(tmp_path / f"metrics.rank{rank}.jsonl", "w") as f:
            for step in (1, 2):
                f.write(json.dumps(_rank_record(
                    step, rank, tps=100.0 * (rank + 1))) + "\n")
    assert mr.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "metrics.rank0.jsonl" in out and "metrics.rank1.jsonl" in out
    assert "merged" in out and "offline merge" in out

    # rank 0's merged gang stream, when present, IS the merged view
    with open(tmp_path / "metrics.gang.jsonl", "w") as f:
        for step in (1, 2):
            rec = dict(_rank_record(step, 0, tps=100.0), scope="gang",
                       world=2, ranks_reported=2)
            f.write(json.dumps(rec) + "\n")
    summary_path = str(tmp_path / "s.json")
    assert mr.main([str(tmp_path), "--json", summary_path]) == 0
    out = capsys.readouterr().out
    assert "metrics.gang.jsonl" in out
    summary = json.loads(open(summary_path).read())
    assert summary["records"] == 2
    assert set(summary["per_rank"]) == {"metrics.rank0.jsonl",
                                        "metrics.rank1.jsonl"}

    # a directory holding ONLY the merged gang stream (rank 0's copied
    # evidence) is a valid run, not a refusal
    gang_only = tmp_path / "gang_only"
    gang_only.mkdir()
    (gang_only / "metrics.gang.jsonl").write_text(
        (tmp_path / "metrics.gang.jsonl").read_text())
    assert mr.main([str(gang_only)]) == 0
    capsys.readouterr()


def test_metrics_report_refuses_schema_version_mix(tmp_path, capsys):
    import tools.metrics_report as mr

    with open(tmp_path / "metrics.rank0.jsonl", "w") as f:
        f.write(json.dumps(_rank_record(1, rank=0)) + "\n")
    with open(tmp_path / "metrics.rank1.jsonl", "w") as f:
        f.write(json.dumps(_rank_record(1)) + "\n")   # version-1 record
    assert mr.main([str(tmp_path)]) == 2
    err = capsys.readouterr().err
    assert "schema-version mismatch" in err

    # a single file interleaving versions is refused too
    mixed = tmp_path / "mixed.jsonl"
    with open(mixed, "w") as f:
        f.write(json.dumps(_rank_record(1, rank=0)) + "\n")
        f.write(json.dumps(_rank_record(2)) + "\n")
    assert mr.main([str(mixed)]) == 2
    assert "mixes schema versions" in capsys.readouterr().err


# ----------------------------------------------------- postmortem satellite

def _write_flight(tmp_path, rank, events, reason, world=2):
    d = tmp_path / f"rank{rank}"
    d.mkdir(exist_ok=True)
    with open(d / f"flight_rank{rank}.json", "w") as f:
        json.dump({"rank": rank, "world": world, "reason": reason,
                   "dumped_at": 100.0 + rank,
                   "recorded_total": len(events), "capacity": 512,
                   "events": events}, f)


def test_postmortem_census_names_first_diverging_rank(tmp_path, capsys):
    import tools.postmortem as pm

    _write_flight(tmp_path, 0, [
        {"t": 1.0, "kind": "span", "name": "train_step"},
        {"t": 9.0, "kind": "coord_timeout", "name": "loop_flags#2",
         "missing": [1], "arrived": [0]},
    ], "crash:CoordinationTimeout")
    _write_flight(tmp_path, 1, [
        {"t": 1.0, "kind": "span", "name": "train_step"},
    ], "crash:InjectedFault")
    assert pm.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "first-diverging rank: 1" in out
    assert "coordination-timeout census" in out
    assert "crash:InjectedFault" in out            # per-rank last words


def test_postmortem_last_event_heuristic_and_json(tmp_path, capsys):
    import tools.postmortem as pm

    # no census recorded: the rank whose stream stops first diverged
    _write_flight(tmp_path, 0, [
        {"t": 1.0, "kind": "span", "name": "train_step"},
        {"t": 8.0, "kind": "span", "name": "train_step"},
    ], "crash:RuntimeError")
    _write_flight(tmp_path, 1, [
        {"t": 1.0, "kind": "span", "name": "train_step"},
        {"t": 2.5, "kind": "span", "name": "data_fetch"},
    ], "crash:OSError")
    report_path = str(tmp_path / "report.json")
    assert pm.main([str(tmp_path), "--json", report_path]) == 0
    rep = json.loads(open(report_path).read())
    assert rep["first_diverging_rank"] == 1
    assert rep["diverging_evidence"] == "earliest last-recorded event"
    assert rep["ranks"] == [0, 1] and rep["world"] == 2
    # merged timeline is time-sorted and rank-tagged
    ts = [e["t"] for e in rep["timeline_tail"]]
    assert ts == sorted(ts)
    assert {e["rank"] for e in rep["timeline_tail"]} == {0, 1}
    # no dumps anywhere → usage error, not a silent empty report
    assert pm.main([str(tmp_path / "nowhere")]) == 2

# ------------------------------------- HBM monitor (observability/memory.py)

def test_sample_memory_stats_none_on_cpu():
    from fleetx_tpu.observability.memory import sample_memory_stats

    # the graceful-degradation contract this whole layer leans on: the
    # CPU backend reports nothing, and that must surface as None (never
    # a fake zero)
    assert sample_memory_stats() is None


def test_memory_monitor_unavailable_marker():
    from fleetx_tpu.observability.memory import MemoryMonitor

    mon = MemoryMonitor(predicted_bytes=1 << 30, stats_fn=lambda: None)
    assert mon.sample("post_compile") is None
    assert mon.available is False
    assert mon.record_keys() == {"hbm_stats": "unavailable",
                                 "hbm_peak_bytes": None,
                                 "hbm_model_error": None}
    snap = mon.snapshot()
    assert snap["available"] is False and snap["model_error"] is None


def test_memory_monitor_model_error():
    from fleetx_tpu.observability.memory import MemoryMonitor

    reg = MetricsRegistry()
    samples = iter([
        {"bytes_in_use": 800, "peak_bytes_in_use": 900,
         "bytes_limit": 2000},
        {"bytes_in_use": 700, "peak_bytes_in_use": 1100,
         "bytes_limit": 2000},
    ])
    mon = MemoryMonitor(registry=reg, predicted_bytes=1000.0,
                        stats_fn=lambda: next(samples))
    mon.sample("post_compile")
    assert mon.peak_bytes == 900
    assert mon.model_error() == pytest.approx(-0.1)
    mon.sample("steady_state")
    assert mon.peak_bytes == 1100  # monotone max across phases
    assert mon.model_error() == pytest.approx(0.1)
    keys = mon.record_keys()
    assert keys["hbm_stats"] == "ok" and keys["hbm_peak_bytes"] == 1100
    assert keys["hbm_model_error"] == pytest.approx(0.1)
    assert reg.gauge("hbm_peak_bytes").value == 1100
    assert reg.gauge("hbm_model_error").value == pytest.approx(0.1)
    assert reg.gauge("hbm_peak_bytes.steady_state").value == 1100
    assert mon.snapshot()["phases"]["post_compile"]["bytes_in_use"] == 800


def test_memory_monitor_flaky_read_keeps_available():
    from fleetx_tpu.observability.memory import MemoryMonitor

    samples = iter([{"peak_bytes_in_use": 10}, None,
                    {"peak_bytes_in_use": 20}])
    mon = MemoryMonitor(stats_fn=lambda: next(samples))
    mon.sample("a")
    mon.sample("b")  # one failed read must not demote the backend
    assert mon.available is True
    mon.sample("c")
    assert mon.peak_bytes == 20


def test_predicted_step_bytes_degrees():
    from fleetx_tpu.parallel.auto_layout import (estimate_memory_terms,
                                                 predicted_step_bytes)

    model = {"hidden_size": 1024, "num_layers": 24, "vocab_size": 50304,
             "max_position_embeddings": 1024}
    flat = predicted_step_bytes(model, {}, micro_batch=8, recompute="dots")
    assert flat == pytest.approx(
        sum(estimate_memory_terms(model, 8, "dots").values()))
    # stage-2 fsdp sharding shrinks moments+grads, not weights/act
    sharded = predicted_step_bytes(
        model, {"fsdp_degree": 8,
                "sharding": {"sharding_stage": 2, "sharding_degree": 8}},
        micro_batch=8, recompute="dots")
    assert sharded < flat


def test_cpu_fit_records_unavailable_marker(tmp_path, devices8):
    """The acceptance path: a CPU-mesh fit (memory_stats() is None) emits
    the explicit unavailable marker, schema-valid, with the auto_layout
    prediction still computed."""
    from fleetx_tpu.observability.schema import validate_jsonl

    eng = _obs_engine(tmp_path, devices8[:1], max_steps=2)
    eng.fit(_batches(2))
    eng.obs.close()
    assert eng.mem is not None and eng.mem.available is False
    assert eng.mem.predicted_bytes and eng.mem.predicted_bytes > 0
    path = str(tmp_path / "telemetry" / "metrics.jsonl")
    count, errors = validate_jsonl(path)
    assert errors == [] and count == 2
    for rec in (json.loads(l) for l in open(path)):
        assert rec["hbm_stats"] == "unavailable"
        assert rec["hbm_peak_bytes"] is None
        assert rec["hbm_model_error"] is None


def test_cpu_fit_records_model_error_with_stats(tmp_path, devices8,
                                                monkeypatch):
    """With a stats-reporting backend (faked on the CPU mesh) every
    window record carries hbm_model_error — the loop-closure on the
    auto_layout memory model."""
    import fleetx_tpu.observability.memory as memory_mod

    eng = _obs_engine(tmp_path, devices8[:1], max_steps=2)
    fake = {"bytes_in_use": 1 << 20, "peak_bytes_in_use": 1 << 21,
            "bytes_limit": 1 << 30}
    monkeypatch.setattr(memory_mod, "sample_memory_stats",
                        lambda device=None: dict(fake))
    eng.fit(_batches(2))
    eng.obs.close()
    assert eng.mem.available is True
    expected = (float(1 << 21) - eng.mem.predicted_bytes) \
        / eng.mem.predicted_bytes
    records = [json.loads(l) for l in
               open(tmp_path / "telemetry" / "metrics.jsonl")]
    for rec in records:
        assert rec["hbm_stats"] == "ok"
        assert rec["hbm_peak_bytes"] == 1 << 21
        assert rec["hbm_model_error"] == pytest.approx(expected, abs=1e-3)
    assert eng.obs.registry.gauge("hbm_model_error").value \
        == pytest.approx(expected, abs=1e-4)


# ------------------------------------------------- one table, one reporter

def test_peak_flops_agrees_with_the_benchmark():
    """The MFU of a run's own ``metrics.jsonl`` (``utils.hardware``) and
    ``train_mfu_pct`` in the ledger (``benchmarks/peaks.json``) divide by
    the same peak: a device kind on both tables has one figure."""
    from types import SimpleNamespace

    from fleetx_tpu.utils.hardware import peak_flops

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmarks", "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks, "benchmarks/peaks.json names no device"
    for kind, entry in peaks.items():
        device = SimpleNamespace(device_kind=kind, platform="tpu")
        assert peak_flops(device) == entry["bf16_flops_per_s"], kind


def test_metrics_report_compare_is_gone(capsys):
    """The reporter summarizes a run's own records and compares them with
    no committed figure: the ledger is the one record of speed."""
    import tools.metrics_report as mr

    with pytest.raises(SystemExit) as exc:
        mr.main(["run.jsonl", "--compare", "x"])
    assert exc.value.code == 2
    assert "unrecognized arguments: --compare" in capsys.readouterr().err
