"""Serving-fleet observability: timelines, SLO registry, router merge.

In-process units pin the PR 16 observability arithmetic — request
lifecycle rings + attribution, the SLO attainment/burn math, the fleet
snapshot merge (summed counters, pooled histograms, worst-replica
attribution, partial-poll tolerance), the router journal, and the
report/gate tools. The subprocess drill drives the REAL machinery: two
replicas behind a ``--fleet-out`` router, one SIGTERM'd mid-stream — the
re-dispatched request's merged trace must show the drain refusal and the
second dispatch, the fleet JSONL must stay schema-valid through the
coverage drop, and ``tools/slo_report.py`` must gate on it.

Named ``test_zz_*`` so it collects last (same stance as the other zz
suites).
"""

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import pytest

import jax
import jax.numpy as jnp

from fleetx_tpu.models.gpt.model import GPTForPretraining, config_from_dict
from fleetx_tpu.observability.flight import EventRing
from fleetx_tpu.observability.metrics import MetricsRegistry
from fleetx_tpu.observability.schema import (SLO_METRIC_NAMES,
                                             validate_fleet_record,
                                             validate_jsonl,
                                             validate_serving_record)
from fleetx_tpu.observability.slo import SLORegistry, validate_slo_block
from fleetx_tpu.serving import ServingConfig, ServingEngine
from fleetx_tpu.serving.router import (ROUTER_COUNTERS, RequestJournal,
                                       Router, merge_fleet_snapshots)

pytestmark = pytest.mark.serving

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SERVE = os.path.join(REPO, "tools", "serve.py")

MODEL_DICT = dict(vocab_size=97, hidden_size=64, num_layers=2,
                  num_attention_heads=4, max_position_embeddings=64,
                  hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
                  use_flash_attention=False, dtype="float32",
                  param_dtype="float32")
EOS = 96


def _loopback_available() -> bool:
    """Subprocess socket drills need a bindable loopback (sandbox gate)."""
    try:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
    except OSError:
        return False
    return True


needs_net = pytest.mark.skipif(not _loopback_available(),
                               reason="loopback networking unavailable")


@pytest.fixture(scope="module")
def small_model():
    """The tiny f32 GPT shared by the engine-level tests."""
    from flax.core import meta

    cfg = config_from_dict(MODEL_DICT)
    model = GPTForPretraining(cfg)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, 8), jnp.int32), None,
                        deterministic=True)["params"]
    return cfg, meta.unbox(params)


def _engine(small_model, **serving_over):
    cfg, params = small_model
    serving = dict(max_batch=4, page_size=4, num_pages=33, max_seq_len=32,
                   prefill_chunk=4)
    serving.update(serving_over)
    eng = ServingEngine(cfg, params, ServingConfig(**serving),
                        eos_token_id=EOS)
    eng.reset_stats()
    return eng


# ---------------------------------------------------------------------------
# lifecycle timelines
# ---------------------------------------------------------------------------

def test_event_ring_bounded_with_drop_accounting():
    ring = EventRing(capacity=4)
    for i in range(10):
        ring.append({"i": i})
    snap = ring.snapshot()
    assert [e["i"] for e in snap] == [6, 7, 8, 9]
    assert ring.total == 10 and ring.dropped == 6


def test_request_timeline_events_and_attribution(small_model):
    """A completed request's timeline walks the taxonomy in order and its
    attribution decomposes TTFT into queue + prefill."""
    eng = _engine(small_model)
    req = eng.submit([5, 9, 23, 41, 7, 3], 4, request_id="tl")
    eng.run_until_drained()
    tr = eng.request_trace("tl")
    assert tr is not None and tr["state"] == "finished"
    names = [e["name"] for e in tr["events"]]
    assert names[0] == "queued"
    assert names.index("queued") < names.index("admitted") \
        < names.index("first_token") < names.index("finished")
    # 6-token prompt over chunk=4 → 2 prefill chunks, both recorded
    assert names.count("prefill_chunk") == 2
    assert names.count("decode_tick") == len(req.tokens) - 1
    att = tr["attribution"]
    for key in ("queue_s", "prefill_s", "decode_s", "ttft_s", "total_s"):
        assert att[key] is not None and att[key] >= 0.0, (key, att)
    assert att["ttft_s"] == pytest.approx(att["queue_s"] + att["prefill_s"])
    assert att["pages"] >= 1 and att["prefill_chunks"] == 2
    # unknown ids stay None (the server maps that to an error payload)
    assert eng.request_trace("nope") is None


def test_timeline_eviction_keeps_attribution(small_model):
    """Long decodes evict the oldest ring events (counted) but the pinned
    milestone timestamps keep the phase decomposition exact."""
    eng = _engine(small_model, trace_events=8)
    eng.submit([5, 9, 23], 16, request_id="long")
    eng.run_until_drained()
    tr = eng.request_trace("long")
    assert tr["events_dropped"] > 0
    assert tr["events_total"] == \
        tr["events_dropped"] + len(tr["events"])
    names = [e["name"] for e in tr["events"]]
    assert "queued" not in names  # the head fell off the ring...
    att = tr["attribution"]
    assert att["queue_s"] is not None  # ...but the milestones survive
    assert att["ttft_s"] is not None and att["total_s"] is not None


def test_refused_request_timeline(small_model):
    eng = _engine(small_model)
    eng.begin_drain()
    req = eng.submit([1, 2], 2, request_id="late")
    assert req.state == "refused"
    tr = eng.request_trace("late")
    assert tr["state"] == "refused"
    assert [e["name"] for e in tr["events"]] == ["queued", "refused"]
    assert tr["attribution"]["total_s"] is not None
    assert tr["attribution"]["decode_s"] is None  # never decoded


def test_request_ids_unique_across_stats_reset(small_model):
    """Regression: rids were minted from a counter that reset_stats()
    zeroed, so a bench warmup + reset made the next request reuse an id —
    corrupting its predecessor's timeline. The mint is monotonic now."""
    eng = _engine(small_model)
    first = eng.submit([5, 9], 2)
    eng.run_until_drained()
    eng.reset_stats()
    second = eng.submit([5, 9], 2)
    eng.run_until_drained()
    assert first.id != second.id
    # both timelines remain individually retrievable
    assert eng.request_trace(first.id)["id"] == first.id
    assert eng.request_trace(second.id)["id"] == second.id


# ---------------------------------------------------------------------------
# snapshot gauges + schema round-trips
# ---------------------------------------------------------------------------

def test_gauges_null_with_marker_until_first_step(small_model):
    """Satellite (b): a never-stepped engine must say "unavailable" with
    null gauges (the hbm_stats convention), never a fake-zero occupancy."""
    eng = _engine(small_model)
    snap = eng.serving_snapshot()
    assert snap["scheduler_gauges"] == "unavailable"
    for key in ("queue_depth", "active_requests", "page_occupancy",
                "kv_fragmentation"):
        assert snap[key] is None, key
    assert validate_serving_record(snap) == []
    eng.submit([5, 9], 2)
    eng.run_until_drained()
    snap = eng.serving_snapshot()
    assert snap["scheduler_gauges"] == "ok"
    assert isinstance(snap["queue_depth"], int)
    assert isinstance(snap["page_occupancy"], float)
    assert validate_serving_record(snap) == []


def test_extended_serving_record_round_trips(small_model):
    eng = _engine(small_model)
    eng.submit([5, 9, 23], 3)
    eng.run_until_drained()
    snap = eng.serving_snapshot()
    assert validate_serving_record(snap) == []
    # the PR 16 extensions are present and typed
    assert isinstance(snap["ttft"], dict) and snap["ttft"]["count"] == 1
    assert isinstance(snap["itl"], dict)
    assert snap["chips"] == 1
    assert snap["requests_per_chip"] == pytest.approx(1.0)
    # negatives: a stringly queue depth and a bool chips must not validate
    assert validate_serving_record(dict(snap, queue_depth="3"))
    assert validate_serving_record(dict(snap, chips=True))
    assert validate_serving_record(
        dict(snap, slo_attainment=float("nan")))


def _snap(ts, admitted, completed, refused, tokens, tps, occ, ttft, itl,
          chips=1, att=None, qd=0):
    return {"ts": ts, "scope": "serving", "requests_admitted": admitted,
            "requests_completed": completed, "requests_refused": refused,
            "tokens_total": tokens, "tokens_per_sec": tps,
            "queue_depth": qd, "active_requests": 0,
            "page_occupancy": occ, "chips": chips, "ttft": ttft,
            "itl": itl, "slo_attainment": att}


def test_fleet_merge_sums_pools_and_attributes():
    snaps = {
        "127.0.0.1:9000": _snap(10.0, 6, 5, 1, 50, 25.0, 0.4,
                                {"count": 4, "mean": 0.10, "p99": 0.20},
                                {"count": 40, "mean": 0.010, "p99": 0.015},
                                att=1.0),
        "127.0.0.1:9001": _snap(11.0, 4, 3, 0, 30, 15.0, 0.7,
                                {"count": 2, "mean": 0.40, "p99": 0.90},
                                {"count": 20, "mean": 0.040, "p99": 0.060},
                                att=0.9),
    }
    counters = {n: 0 for n in ROUTER_COUNTERS}
    counters["dispatched_total"] = 10
    counters["drain_refusals_total"] = 2
    rec = merge_fleet_snapshots(snaps, replicas_total=2,
                                router_counters=counters)
    assert validate_fleet_record(rec) == []
    assert rec["ts"] == 11.0 and rec["scope"] == "fleet"
    assert rec["replicas_total"] == 2 and rec["replicas_reported"] == 2
    # counters summed
    assert rec["requests_admitted"] == 10
    assert rec["requests_completed"] == 8
    assert rec["requests_refused"] == 1
    assert rec["tokens_total"] == 80
    assert rec["tokens_per_sec"] == pytest.approx(40.0)
    # fleet economics
    assert rec["chips_total"] == 2
    assert rec["requests_per_chip"] == pytest.approx(4.0)
    # histograms pooled count-weighted; the tail names its replica
    assert rec["ttft_mean_s"] == pytest.approx((4 * 0.1 + 2 * 0.4) / 6)
    assert rec["ttft_p99_s"] == pytest.approx(0.90)
    assert rec["ttft_p99_replica"] == "127.0.0.1:9001"
    assert rec["itl_p99_replica"] == "127.0.0.1:9001"
    # occupancy mean + max with attribution
    assert rec["page_occupancy_mean"] == pytest.approx(0.55)
    assert rec["page_occupancy_max"] == pytest.approx(0.7)
    assert rec["page_occupancy_max_replica"] == "127.0.0.1:9001"
    # SLO attainment is the fleet MINIMUM (worst class anywhere)
    assert rec["slo_attainment"] == pytest.approx(0.9)
    # router counters ride along
    assert rec["dispatched_total"] == 10
    assert rec["drain_refusals_total"] == 2


def test_fleet_merge_tolerates_partial_poll_and_null_gauges():
    """A draining replica doesn't report; a never-stepped one reports
    null gauges — neither poisons the merge with fake zeros."""
    fresh = _snap(5.0, 0, 0, 0, 0, 0.0, None,
                  {"count": 0}, {"count": 0})
    fresh["queue_depth"] = None
    fresh["active_requests"] = None
    rec = merge_fleet_snapshots({"a": fresh}, replicas_total=3)
    assert validate_fleet_record(rec) == []
    assert rec["replicas_total"] == 3 and rec["replicas_reported"] == 1
    assert rec["queue_depth"] is None  # null, not a summed fake zero
    assert "page_occupancy_mean" not in rec
    assert "ttft_mean_s" not in rec  # zero-count histograms pool nothing
    # nobody reporting at all still yields a valid (empty) record
    empty = merge_fleet_snapshots({}, replicas_total=2)
    assert validate_fleet_record(empty) == []
    assert empty["replicas_reported"] == 0
    assert empty["tokens_per_sec"] is None
    assert empty["requests_per_chip"] is None


# ---------------------------------------------------------------------------
# SLO registry
# ---------------------------------------------------------------------------

def test_slo_block_validation_rejects_typos_eagerly():
    classes = validate_slo_block(
        {"interactive": {"ttft_p99_s": 0.5, "objective": 0.95,
                         "windows": [60, 12, 12]}})
    assert classes[0].name == "interactive"
    assert classes[0].windows == (12, 60)  # sorted, deduped
    # flat shorthand wraps as one implicit "default" class
    flat = validate_slo_block({"itl_p99_s": 0.05})
    assert flat[0].name == "default" and flat[0].objective == 0.99
    assert validate_slo_block(None) == []
    for bad in (
            {"default": {"ttft_p99": 0.5}},            # unknown target key
            {"default": {"ttft_p99_s": -1.0}},         # negative threshold
            {"default": {"ttft_p99_s": True}},         # bool threshold
            {"default": {"ttft_p99_s": 0.5,
                         "objective": 1.5}},           # objective out of (0,1)
            {"default": {"ttft_p99_s": 0.5,
                         "windows": [0]}},             # non-positive window
            {"default": {"objective": 0.99}},          # no targets at all
            ["ttft_p99_s"],                            # not a mapping
    ):
        with pytest.raises(ValueError):
            validate_slo_block(bad)


def test_slo_attainment_burn_and_breach_math():
    reg = SLORegistry.from_config(
        {"ttft_p99_s": 1.0, "objective": 0.9, "windows": [4]},
        registry=MetricsRegistry())
    base = {"requests_refused": 0, "requests_admitted": 10}
    for v in (0.5, 0.5, 0.5):
        report = reg.observe(dict(base, ttft_p99_s=v))
    assert report["attainment"] == 1.0 and not report["breached"]
    report = reg.observe(dict(base, ttft_p99_s=5.0))  # one breach in 4
    t = report["classes"]["default"]["ttft_p99_s"]
    assert t["met"] is False and report["attainment"] == pytest.approx(0.75)
    # burn = (1 - 0.75) / (1 - 0.9) = 2.5× the error budget
    assert t["burn_rate"]["4"] == pytest.approx(2.5)
    assert t["breached"] and report["breached"]
    # mirrored into the registry under the SLO_METRIC_NAMES stems
    assert reg.metrics.gauge("slo_attainment").value == pytest.approx(0.75)
    assert reg.metrics.counter("slo_breaches_total").value == 1
    assert reg.metrics.counter("slo_evaluations_total").value == 4
    assert all(n in SLO_METRIC_NAMES for n in
               ("slo_attainment", "slo_burn_rate", "slo_breaches_total"))


def test_router_import_path_is_jax_free():
    """The fleet front must start in milliseconds: the router plus every
    module it reuses at runtime (sinks, schema, slo) import WITHOUT jax —
    the serving/utils/observability packages resolve their jax-heavy
    exports lazily (docs/serving.md). A regression here costs every
    router launch a multi-second engine import."""
    code = (
        "import sys\n"
        "import fleetx_tpu.serving.router\n"
        "from fleetx_tpu.observability.sinks import JsonlSink\n"
        "from fleetx_tpu.observability.schema import validate_fleet_record\n"
        "from fleetx_tpu.observability.slo import SLORegistry\n"
        "assert 'jax' not in sys.modules, sorted(\n"
        "    m for m in sys.modules if m.startswith('fleetx_tpu'))\n"
    )
    env = dict(os.environ, PYTHONPATH=REPO)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env)
    assert r.returncode == 0, r.stderr


def test_slo_skips_unmeasured_targets_and_derives_refusal_rate():
    reg = SLORegistry.from_config(
        {"ttft_p99_s": 1.0, "refusal_rate": 0.2, "windows": [8]},
        registry=MetricsRegistry())
    # quantiles null before the first completion: no deque growth, no
    # breach — but the refusal rate still evaluates off the counters
    report = reg.observe({"ttft_p99_s": None, "requests_refused": 1,
                          "requests_admitted": 1})
    t = report["classes"]["default"]["ttft_p99_s"]
    assert t["measured"] is None and t["attainment"]["8"] is None
    r = report["classes"]["default"]["refusal_rate"]
    assert r["measured"] == pytest.approx(0.5) and r["met"] is False
    # an empty block means "no SLOs": from_config returns None
    assert SLORegistry.from_config(None, registry=MetricsRegistry()) is None


def test_engine_snapshot_carries_slo_attainment(small_model):
    cfg, params = small_model
    eng = ServingEngine(
        cfg, params,
        ServingConfig(max_batch=4, page_size=4, num_pages=33,
                      max_seq_len=32, prefill_chunk=4,
                      slo={"ttft_p99_s": 60.0, "refusal_rate": 0.99}),
        eos_token_id=EOS)
    eng.reset_stats()
    eng.submit([5, 9, 23], 3)
    eng.run_until_drained()
    snap = eng.serving_snapshot()
    assert validate_serving_record(snap) == []
    assert snap["slo_attainment"] == 1.0  # 60s TTFT budget: trivially met


# ---------------------------------------------------------------------------
# router journal + counters (stubbed transport)
# ---------------------------------------------------------------------------

def test_request_journal_bounded_per_id_and_across_ids():
    j = RequestJournal(max_requests=2, events_per_request=8)
    for i in range(12):
        j.note("r1", "dispatch", attempt=i)
    assert len(j.events("r1")) == 8  # per-id ring
    assert j.events("r1")[0]["attempt"] == 4
    j.note("r2", "dispatch")
    j.note("r3", "dispatch")  # evicts r1 (insertion-ordered, 2 ids max)
    assert j.events("r1") == [] and j.events("r3")
    j.note(None, "dispatch")  # un-id'd requests are simply unjournaled


def test_router_counters_and_journal_on_drain_redispatch(monkeypatch):
    """A drain refusal must penalise, count, journal, and re-dispatch —
    the fleet record's counters and the merged trace both come from
    here."""
    router = Router([("127.0.0.1", 1), ("127.0.0.1", 2)])

    def fake_forward(backend, payload):
        if backend.addr[1] == 1:
            return {"id": payload.get("id"), "error": "draining"}
        return {"id": payload.get("id"), "tokens": [1, 2]}

    monkeypatch.setattr(Router, "_forward",
                        staticmethod(lambda b, p: fake_forward(b, p)))
    resp = router.dispatch({"id": "r1", "prompt": [1], "max_new_tokens": 2})
    assert resp["tokens"] == [1, 2]
    c = router.router_counters()
    assert c["dispatched_total"] == 2 and c["redispatched_total"] == 1
    assert c["penalties_total"] == 1 and c["drain_refusals_total"] == 1
    assert c["completed_total"] == 1 and c["no_backend_total"] == 0
    names = [e["name"] for e in router.journal.events("r1")]
    assert names == ["dispatch", "drain_refusal", "dispatch", "completed"]
    events = router.journal.events("r1")
    assert events[1]["backend"] == "127.0.0.1:1"
    assert events[3]["backend"] == "127.0.0.1:2"
    assert all(e["source"] == "router" for e in events)
    # with no live replicas the trace is the router's journal alone
    tr = router.trace("r1")
    assert tr["sources"] == ["router"]
    assert [e["name"] for e in tr["events"]] == names
    assert router.trace("ghost") == {"id": "ghost",
                                     "error": "unknown request id"}


def test_router_counts_transport_retries(monkeypatch):
    router = Router([("127.0.0.1", 1), ("127.0.0.1", 2)])
    calls = []

    def fake_forward(backend, payload):
        calls.append(backend.addr[1])
        if backend.addr[1] == 1:
            raise ConnectionError("replica died")
        return {"id": payload.get("id"), "tokens": [3]}

    monkeypatch.setattr(Router, "_forward",
                        staticmethod(lambda b, p: fake_forward(b, p)))
    resp = router.dispatch({"id": "x", "prompt": [1], "max_new_tokens": 1})
    assert resp["tokens"] == [3] and calls == [1, 2]
    c = router.router_counters()
    assert c["penalties_total"] == 1 and c["drain_refusals_total"] == 0
    names = [e["name"] for e in router.journal.events("x")]
    assert names == ["dispatch", "transport_retry", "dispatch", "completed"]


def test_poll_fleet_merges_what_reports(monkeypatch):
    router = Router([("127.0.0.1", 1), ("127.0.0.1", 2)])
    good = _snap(9.0, 2, 2, 0, 20, 10.0, 0.25,
                 {"count": 2, "mean": 0.1, "p99": 0.2},
                 {"count": 10, "mean": 0.01, "p99": 0.02})

    def fake_ask(addr, payload, timeout=10.0):
        if addr[1] == 1:
            return dict(good)
        raise ConnectionError("draining replica does not report")

    monkeypatch.setattr(Router, "_ask",
                        staticmethod(lambda a, p, timeout=10.0:
                                     fake_ask(a, p, timeout)))
    rec = router.poll_fleet()
    assert validate_fleet_record(rec) == []
    assert rec["replicas_total"] == 2 and rec["replicas_reported"] == 1
    assert rec["requests_completed"] == 2
    assert router.last_fleet is rec
    for name in ROUTER_COUNTERS:
        assert rec[name] == 0


# ---------------------------------------------------------------------------
# report + gate tools
# ---------------------------------------------------------------------------

def _write_serving_jsonl(path, n=6, ttft=0.1):
    recs = []
    for i in range(n):
        recs.append({"ts": float(i), "scope": "serving",
                     "requests_admitted": 10, "requests_completed": 9,
                     "requests_refused": 0, "queue_depth": 0,
                     "active_requests": 1, "page_occupancy": 0.4,
                     "scheduler_gauges": "ok", "tokens_total": 100,
                     "tokens_per_sec": 50.0, "ttft_p50_s": ttft / 2,
                     "ttft_p99_s": ttft, "itl_p50_s": 0.01,
                     "itl_p99_s": 0.02})
    path.write_text("".join(json.dumps(r) + "\n" for r in recs))
    return str(path)


def test_slo_report_exit_codes(tmp_path, capsys):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import slo_report

    met = _write_serving_jsonl(tmp_path / "met.jsonl", ttft=0.1)
    slo = json.dumps({"ttft_p99_s": 0.5, "windows": [4]})
    assert slo_report.main([met, "--slo", slo]) == 0
    out = capsys.readouterr().out
    assert "met" in out and "attainment" in out

    breach = _write_serving_jsonl(tmp_path / "breach.jsonl", ttft=5.0)
    assert slo_report.main([breach, "--slo", slo]) == 1
    assert "BREACH" in capsys.readouterr().out

    # usage errors: bad slo JSON, a non-serving stream, an empty file
    assert slo_report.main([met, "--slo", "{nope"]) == 2
    step = tmp_path / "step.jsonl"
    step.write_text(json.dumps({"step": 0, "ts": 1.0, "loss": 1.0,
                                "step_time": 0.1, "tokens_per_sec": 1.0,
                                "mfu": None}) + "\n")
    assert slo_report.main([str(step), "--slo", slo]) == 2
    (tmp_path / "empty.jsonl").write_text("")
    assert slo_report.main([str(tmp_path / "empty.jsonl"),
                            "--slo", slo]) == 2


def test_slo_report_reads_config_block(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import slo_report

    met = _write_serving_jsonl(tmp_path / "m.jsonl", ttft=0.1)
    cfg = tmp_path / "cfg.yaml"
    cfg.write_text("Serving:\n  slo:\n    default:\n"
                   "      ttft_p99_s: 0.5\n      windows: [4]\n")
    out = tmp_path / "report.json"
    assert slo_report.main([met, "-c", str(cfg), "--json", str(out)]) == 0
    report = json.loads(out.read_text())
    assert report["classes"]["default"]["ttft_p99_s"]["breached"] is False
    # a config without the block is a usage error, not a silent pass
    bare = tmp_path / "bare.yaml"
    bare.write_text("Serving:\n  max_batch: 4\n")
    assert slo_report.main([met, "-c", str(bare)]) == 2


def test_metrics_report_dispatches_serving_and_fleet_scopes(tmp_path,
                                                           capsys):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import metrics_report

    serving = _write_serving_jsonl(tmp_path / "serving.jsonl")
    assert metrics_report.main([serving]) == 0
    assert "serving stream" in capsys.readouterr().out

    fleet = tmp_path / "fleet.jsonl"
    rec = merge_fleet_snapshots(
        {"a": _snap(1.0, 2, 2, 0, 20, 10.0, 0.3,
                    {"count": 2, "mean": 0.1, "p99": 0.2},
                    {"count": 8, "mean": 0.01, "p99": 0.02})},
        replicas_total=2,
        router_counters={n: 0 for n in ROUTER_COUNTERS})
    fleet.write_text(json.dumps(rec) + "\n")
    assert metrics_report.main([str(fleet)]) == 0
    out = capsys.readouterr().out
    assert "fleet stream" in out and "replicas: 1(min)/2" in out

    # schema violations still exit non-zero (the validate-or-die stance)
    bad = tmp_path / "bad_fleet.jsonl"
    bad.write_text(json.dumps(dict(rec, replicas_reported="two")) + "\n")
    assert metrics_report.main([str(bad)]) == 1

    # mixing scopes in one invocation is refused like schema versions
    step = tmp_path / "metrics.rank0.jsonl"
    step.write_text(json.dumps({"step": 0, "ts": 1.0, "loss": 1.0,
                                "step_time": 0.1, "tokens_per_sec": 1.0,
                                "mfu": None}) + "\n")
    mixed = tmp_path / "metrics.rank1.jsonl"
    mixed.write_text((tmp_path / "serving.jsonl").read_text())
    assert metrics_report.main([str(tmp_path / "metrics.rank*.jsonl")]) == 2


def test_serving_config_validation_in_config_pipeline(tmp_path):
    """process_serving_config fails a typo'd SLO key at config time."""
    from fleetx_tpu.utils import config as config_mod

    good = config_mod.AttrDict(
        {"Serving": {"slo": {"ttft_p99_s": 0.5}, "trace_requests": 16}})
    config_mod.process_serving_config(good)  # no raise
    with pytest.raises(ValueError, match="unknown SLO target"):
        config_mod.process_serving_config(config_mod.AttrDict(
            {"Serving": {"slo": {"ttft_p99": 0.5}}}))
    with pytest.raises(ValueError, match="trace_events"):
        config_mod.process_serving_config(config_mod.AttrDict(
            {"Serving": {"trace_events": 0}}))
    # no Serving block at all is fine (training configs)
    config_mod.process_serving_config(config_mod.AttrDict({}))


def test_shipped_recipe_slo_block_round_trips():
    """The committed serving yaml's slo/trace knobs must survive
    ServingConfig.from_dict AND eager validation."""
    from fleetx_tpu.utils import config as config_mod

    cfg = config_mod.parse_config(os.path.join(
        REPO, "fleetx_tpu", "configs", "nlp", "gpt",
        "serving_gpt_345M.yaml"))
    config_mod.process_serving_config(cfg)
    sc = ServingConfig.from_dict(dict(cfg.get("Serving") or {}))
    assert sc.slo and "default" in sc.slo
    classes = validate_slo_block(sc.slo)
    assert classes[0].targets["ttft_p99_s"] == 2.0
    assert sc.trace_requests == 256 and sc.trace_events == 128


# ---------------------------------------------------------------------------
# subprocess drill: 2-replica fleet with --fleet-out, SIGTERM drain,
# traces through the router, slo_report gating
# ---------------------------------------------------------------------------

def _serve_yaml(tmp_path):
    import yaml

    cfg = {"Model": MODEL_DICT,
           "Serving": dict(max_batch=2, page_size=4, num_pages=17,
                           max_seq_len=32, prefill_chunk=4,
                           slo={"ttft_p99_s": 120.0, "refusal_rate": 0.99,
                                "windows": [4]}),
           "Generation": {"decode_strategy": "greedy_search",
                          "eos_token_id": EOS, "pad_token_id": 0},
           "Global": {"seed": 7}}
    path = tmp_path / "serving.yaml"
    path.write_text(yaml.safe_dump(cfg))
    return str(path)


def _subprocess_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.update(extra)
    return env


def _wait_ready(path, proc, timeout=120.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if os.path.exists(path):
            try:
                with open(path) as f:
                    return json.load(f)
            except ValueError:
                pass  # torn write — retry
        if proc.poll() is not None:
            raise AssertionError(
                f"replica died before ready (rc={proc.returncode})")
        time.sleep(0.1)
    raise AssertionError("replica never became ready")


def _ask(port, payload, timeout=90.0):
    from fleetx_tpu.serving.server import request

    return request(("127.0.0.1", port), payload, timeout=timeout)


def _wait_fleet_record(path, pred, timeout=60.0):
    """Poll the fleet JSONL until a record satisfies ``pred``."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if os.path.exists(path):
            for line in open(path).read().splitlines():
                if not line.strip():
                    continue
                rec = json.loads(line)
                if pred(rec):
                    return rec
        time.sleep(0.2)
    raise AssertionError(f"no fleet record matching {pred} in {path}")


@needs_net
def test_fleet_observer_drain_traces_and_slo_gate(tmp_path):
    """The PR 16 acceptance drill: two replicas behind a ``--fleet-out``
    router. Phase 1 pins the healthy fleet — schema-valid merged records
    with full coverage and a completed request's timeline served through
    the router. Phase 2 SIGTERMs one replica mid-stream: a probe request
    must surface the drain refusal + re-dispatch in its merged trace
    (and still complete), coverage must drop to 1 without breaking the
    record stream, and ``tools/slo_report.py`` must pass the met SLO and
    fail a synthetic breach on the same file.

    Both replicas AND the router run under ``FLEETX_TSAN=1``: the runtime
    lock sanitizer wraps the real serving locks (router placement/journal,
    engine timelines), asserts one global acquisition order and flags
    cross-thread engine access — a lock-order inversion anywhere on the
    drill's dispatch/drain/poll paths turns into a hard failure here."""
    cfg_path = _serve_yaml(tmp_path)
    readys = [tmp_path / f"ready{i}.json" for i in range(2)]
    fleet_path = tmp_path / "fleet.jsonl"
    replicas = []
    for i in range(2):
        replicas.append(subprocess.Popen(
            [sys.executable, SERVE, "-c", cfg_path,
             "--ready-file", str(readys[i]), "--preemption-code", "75"],
            env=_subprocess_env(
                FLEETX_FLIGHT_DIR=str(tmp_path / f"flight{i}"),
                FLEETX_TSAN="1"),
            stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT))
    router = None
    try:
        infos = [_wait_ready(str(r), p) for r, p in zip(readys, replicas)]
        router = subprocess.Popen(
            [sys.executable, SERVE, "--router", "--port", "0",
             "--backends",
             f"127.0.0.1:{infos[0]['port']},127.0.0.1:{infos[1]['port']}",
             "--fleet-out", str(fleet_path), "--poll-interval", "0.25"],
            env=_subprocess_env(FLEETX_TSAN="1"),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        line = router.stdout.readline()
        assert "listening on" in line, line
        router_port = int(line.split(":")[-1].split()[0])

        # ---- phase 1: healthy fleet --------------------------------------
        results = {}

        def ask(rid, prompt):
            results[rid] = _ask(router_port,
                                {"id": rid, "prompt": prompt,
                                 "max_new_tokens": 6}, timeout=150.0)

        warm = [threading.Thread(target=ask, args=(f"w{i}", [5 + i, 9, 23]))
                for i in range(4)]
        for t in warm:
            t.start()
        for t in warm:
            t.join(timeout=180)
        for rid in (f"w{i}" for i in range(4)):
            assert results[rid].get("tokens"), (rid, results[rid])

        # a completed request's lifecycle comes back THROUGH the router:
        # router journal (dispatch → completed) + the replica's timeline
        tr = _ask(router_port, {"verb": "trace", "id": "w0"})
        names = [e["name"] for e in tr["events"]]
        assert "dispatch" in names and "completed" in names
        for name in ("queued", "admitted", "first_token", "finished"):
            assert name in names, (name, names)
        assert "router" in tr["sources"] and len(tr["sources"]) >= 2
        assert tr["attribution"]["ttft_s"] is not None
        srcs = {e["source"] for e in tr["events"]}
        assert "router" in srcs and any(s != "router" for s in srcs)

        # the poll loop is writing schema-valid full-coverage records
        rec = _wait_fleet_record(
            str(fleet_path),
            lambda r: r["replicas_reported"] == 2
            and r["requests_completed"] >= 4)
        assert rec["completed_total"] >= 4
        assert rec["slo_attainment"] == 1.0

        # the router's own stats verb answers a fresh fleet record
        stats = _ask(router_port, {"verb": "stats"})
        assert stats["scope"] == "fleet"
        assert validate_fleet_record(stats) == []

        # ---- phase 2: SIGTERM replica 0, catch the drain re-dispatch -----
        # long-ish work keeps replica 0's drain window open while probes
        # land on it and get the explicit refusal
        busy = [threading.Thread(target=ask, args=(f"b{i}",
                                                   [3 + i, 7, 11, 2]))
                for i in range(6)]
        for t in busy:
            t.start()
        time.sleep(0.3)  # let the head of the burst get dispatched
        os.kill(infos[0]["pid"], signal.SIGTERM)

        preempted_rid = None
        deadline = time.monotonic() + 45.0
        k = 0
        while preempted_rid is None and time.monotonic() < deadline:
            rid = f"p{k}"
            k += 1
            ask(rid, [9, 5, 2])
            tr = _ask(router_port, {"verb": "trace", "id": rid})
            if any(e["name"] == "drain_refusal" for e in tr["events"]):
                preempted_rid = rid
        for t in busy:
            t.join(timeout=180)
        assert preempted_rid is not None, \
            "no probe ever saw the drain refusal"
        # the preempted request still completed (loss-free re-dispatch)...
        assert results[preempted_rid].get("tokens")
        for i in range(6):
            assert results[f"b{i}"].get("tokens"), results[f"b{i}"]
        # ...and its merged trace tells the whole story in time order:
        # dispatch → drain_refusal → dispatch (attempt 2) → completed,
        # with the surviving replica's lifecycle events interleaved
        tr = _ask(router_port, {"verb": "trace", "id": preempted_rid})
        names = [e["name"] for e in tr["events"]]
        refusal_at = names.index("drain_refusal")
        assert "dispatch" in names[refusal_at + 1:], \
            (names, "no re-dispatch after the refusal")
        attempts = [e["attempt"] for e in tr["events"]
                    if e["name"] == "dispatch"]
        assert max(attempts) >= 2
        assert "completed" in names and "finished" in names
        ts = [e["t"] for e in tr["events"]]
        assert ts == sorted(ts)  # merged stream is time-ordered

        # replica 0 exits with the preemption code; coverage drops to 1
        # without breaking the fleet stream
        rc0 = replicas[0].wait(timeout=120)
        assert rc0 == 75, f"expected preemption exit 75, got {rc0}"
        _wait_fleet_record(str(fleet_path),
                           lambda r: r["replicas_reported"] == 1
                           and r.get("drain_refusals_total", 0) >= 1)

        # every record the router ever wrote is schema-valid
        count, errors = validate_jsonl(str(fleet_path),
                                       validator=validate_fleet_record)
        assert count >= 2 and errors == [], errors

        # ---- phase 3: slo_report gates on the fleet stream ---------------
        met = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "slo_report.py"),
             str(fleet_path), "--slo",
             json.dumps({"ttft_p99_s": 120.0, "windows": [4]})],
            capture_output=True, text=True, env=_subprocess_env())
        assert met.returncode == 0, met.stdout + met.stderr
        breach = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "slo_report.py"),
             str(fleet_path), "--slo",
             json.dumps({"ttft_p99_s": 1e-9, "windows": [4]})],
            capture_output=True, text=True, env=_subprocess_env())
        assert breach.returncode == 1, breach.stdout + breach.stderr
    finally:
        if router is not None and router.poll() is None:
            router.kill()
        for p in replicas:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in replicas:
            if p.poll() is None:
                try:
                    p.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait(timeout=30)
