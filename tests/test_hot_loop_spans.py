"""The phase spans of the two host loops (``ServingEngine.step``,
``EagerEngine.fit``), the three waits of a first token, ``serving_tick`` and
the slow-tick line (docs/observability.md "Hot-loop spans")."""

from __future__ import annotations

import ast
import os
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks import trace_reduce  # noqa: E402
from fleetx_tpu.observability.metrics import Histogram  # noqa: E402
from fleetx_tpu.observability.trace import HOT_LOOP_SPANS  # noqa: E402

MODEL_DICT = dict(vocab_size=97, hidden_size=64, num_layers=2,
                  num_attention_heads=4, max_position_embeddings=64,
                  hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
                  use_flash_attention=False, dtype="float32",
                  param_dtype="float32")
EOS = 96
SERVE_SPANS = sorted(n for n in HOT_LOOP_SPANS if n.startswith("serve."))
FIT_SPANS = ["data_fetch", "shard_batch", "train_step", "fit.fetch_metrics",
             "fit.log"]


@pytest.fixture(scope="module")
def small_model():
    import jax.numpy as jnp
    from flax.core import meta

    from fleetx_tpu.models.gpt.model import (GPTForPretraining,
                                             config_from_dict)

    cfg = config_from_dict(MODEL_DICT)
    params = GPTForPretraining(cfg).init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32),
        None, deterministic=True)["params"]
    return cfg, meta.unbox(params)


def _engine(small_model, **serving_kw):
    from fleetx_tpu.serving import ServingConfig, ServingEngine

    cfg, params = small_model
    kw = dict(max_batch=4, page_size=4, num_pages=33, max_seq_len=32,
              prefill_chunk=4)
    kw.update(serving_kw)
    eng = ServingEngine(cfg, params, ServingConfig(**kw), eos_token_id=EOS)
    eng.reset_stats()        # the registry is process-global
    return eng


def _traced(tmp_path, body):
    """Run ``body`` under a CPU profiler session (no Python call tracer, as
    the benchmark's) and return the host events of its trace, each with the
    line (thread) it sits on."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    planes = trace_reduce.load(trace_reduce.newest_xplane(str(tmp_path)))
    return [dict(e, line=line["name"]) for p in planes for line in p["lines"]
            for e in line["events"]]


# ------------------------------------------------------------ (a) the spans
@pytest.mark.parametrize("telemetry", [False, True])
def test_serving_tick_spans_reach_the_profiler_nested(tmp_path, small_model,
                                                      telemetry):
    """The one ``span`` path: the same names, nesting and arguments reach
    the profiler whether or not a Chrome tracer and a flight recorder are
    installed; with them the Chrome trace holds the spans too, and the
    flight ring none of them (nine a tick would empty it)."""
    from fleetx_tpu.observability import flight
    from fleetx_tpu.observability.trace import Tracer, set_tracer

    eng = _engine(small_model)
    reqs = [eng.submit([5, 9, 23, 41, 7, 3][:n], 4, request_id=f"r{n}")
            for n in (3, 6, 5)]
    ticks_before = eng.steps
    tracer = Tracer() if telemetry else None
    recorder = flight.FlightRecorder(str(tmp_path)) if telemetry else None
    prev_tracer, prev_recorder = set_tracer(tracer), flight.install(recorder)
    try:
        events = _traced(tmp_path, eng.run_until_drained)
    finally:
        set_tracer(prev_tracer)
        flight.install(prev_recorder)
    if telemetry:
        chrome = [e for e in tracer.events if e["name"] in HOT_LOOP_SPANS]
        assert sorted({e["name"] for e in chrome}) == SERVE_SPANS
        assert {e["args"]["rid"] for e in chrome
                if e["name"] == "serve.prefill"} == {"r3", "r6", "r5"}
        noted = [e for e in recorder.events() if e.get("kind") == "span"]
        assert not noted, "the tick's spans stay out of the flight ring"
        assert [e for e in recorder.events() if e.get("kind") == "serving"]
    assert all(r.state == "finished" for r in reqs)
    mine = [e for e in events if e["name"] in HOT_LOOP_SPANS]
    assert sorted({e["name"] for e in mine}) == SERVE_SPANS
    assert len({e["line"] for e in mine}) == 1, "one thread: the caller's"
    ticks = [e for e in mine if e["name"] == "serve.tick"]
    assert len(ticks) == eng.steps - ticks_before
    assert [e["args"]["tick"] for e in ticks] == \
        list(range(ticks_before, eng.steps))
    inner = [e for e in mine if e["name"] != "serve.tick"]
    for e in inner:
        holders = [t for t in ticks if t["ts"] <= e["ts"] and
                   e["ts"] + e["dur"] <= t["ts"] + t["dur"]]
        assert len(holders) == 1, e
    # a tick dispatches (prefill, decode) before it fetches (decode.wait,
    # then, inside emit, prefill.wait): its phases in that order, each once
    order = ["serve.admit", "serve.prefill", "serve.schedule", "serve.decode",
             "serve.decode.wait", "serve.emit", "serve.prefill.wait",
             "serve.gauges"]
    for t in ticks:
        held = [e for e in inner if t["ts"] <= e["ts"] < t["ts"] + t["dur"]]
        assert 1 + len(held) <= 10
        names = [e["name"] for e in sorted(held, key=lambda e: e["ts"])]
        assert names == [n for n in order if n in names], names
        assert names[0] == "serve.admit" and names[-1] == "serve.gauges"
    both = [t for t in ticks if {"serve.decode", "serve.decode.wait"} <= {
        e["name"] for e in inner if t["ts"] <= e["ts"] < t["ts"] + t["dur"]}]
    assert len(both) >= 3, "steps were dispatched with one still unfetched"
    chunks = [e for e in mine if e["name"] == "serve.prefill"]
    assert {e["args"]["rid"] for e in chunks} == {"r3", "r6", "r5"}
    assert sorted(e["args"]["chunk"] for e in chunks
                  if e["args"]["rid"] == "r6") == [0, 1]
    waits = [e for e in mine if e["name"] == "serve.prefill.wait"]
    assert len(waits) == 3      # one a prompt: its last chunk's device_get
    emits = [e for e in mine if e["name"] == "serve.emit"]
    for w in waits:     # after this tick's chunk, inside this tick's emit
        assert any(c["ts"] <= w["ts"] and
                   w["ts"] + w["dur"] <= c["ts"] + c["dur"] for c in emits)
        tick = next(t for t in ticks
                    if t["ts"] <= w["ts"] < t["ts"] + t["dur"])
        assert any(tick["ts"] <= c["ts"] and c["ts"] + c["dur"] <= w["ts"]
                   for c in chunks)
    # nothing else of the program's carries a hot-loop prefix
    assert not [e["name"] for e in events
                if e["name"].startswith(("serve.", "fit."))
                and e["name"] not in HOT_LOOP_SPANS]


def test_fit_spans_reach_the_profiler_without_an_observability_block(
        tmp_path, devices8):
    from test_engine import build_engine, make_batches, tiny_cfg

    from fleetx_tpu.parallel.mesh import build_mesh

    cfg = tiny_cfg()
    assert "Observability" not in cfg
    cfg["Engine"]["max_steps"] = 4
    eng = build_engine(cfg, build_mesh({}, devices=devices8[:1]))
    assert not eng.obs.enabled
    os.environ["FLEETX_PREFETCH_OFF"] = "1"
    try:
        events = _traced(tmp_path, lambda: eng.fit(make_batches(4)))
    finally:
        del os.environ["FLEETX_PREFETCH_OFF"]
    mine = sorted((e for e in events if e["name"] in HOT_LOOP_SPANS),
                  key=lambda e: e["ts"])
    assert sorted({e["name"] for e in mine}) == sorted(FIT_SPANS)
    assert len({e["line"] for e in mine}) == 1
    steps = [e for e in mine if e["name"] == "train_step"]
    assert [e["args"]["step"] for e in steps] == [0, 1, 2, 3]
    # the five follow each other, never overlap, in the loop's order
    for a, b in zip(mine, mine[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1e-3, (a["name"], b["name"])
    order = [e["name"] for e in mine]
    first = order.index("train_step")
    assert order[first:first + 4] == ["train_step", "fit.fetch_metrics",
                                      "fit.log", "data_fetch"]
    assert len([n for n in order if n == "fit.log"]) == 4, \
        "the last fit.log is closed at the loop's end"
    # fit.log runs up to the next data_fetch: no stretch between them
    logs = [e for e in mine if e["name"] == "fit.log"]
    fetches = [e for e in mine if e["name"] == "data_fetch"]
    for log in logs[:-1]:
        nxt = min((f for f in fetches if f["ts"] >= log["ts"]),
                  key=lambda f: f["ts"])
        assert nxt["ts"] - (log["ts"] + log["dur"]) < 200.0    # us


def test_a_disabled_facade_gives_the_span_alone_no_note_and_no_timer(
        tmp_path):
    from fleetx_tpu.observability import (Observability, flight,
                                          get_registry, span)

    obs = Observability(None)
    before = get_registry().histogram("data_fetch").total_count
    recorder = flight.FlightRecorder(str(tmp_path))
    prev = flight.install(recorder)
    try:
        for cm in (obs.span("train_step", step=1),
                   obs.timed_span("data_fetch")):
            assert isinstance(cm, span) and not cm.flight_note
            with cm:
                pass
        with span("load", path="x"):        # any other span still notes
            pass
    finally:
        flight.install(prev)
    assert get_registry().histogram("data_fetch").total_count == before
    assert [e["name"] for e in recorder.events()
            if e.get("kind") == "span"] == ["load"]


def test_fit_log_is_closed_when_the_loop_is_left_by_an_exception(devices8):
    """``fit.log`` is entered by hand across the iteration boundary: a hook
    that raises inside it (as ``preemption_exit`` or ``TrainingAborted``
    would) still leaves every opened span closed."""
    from test_engine import build_engine, make_batches, tiny_cfg

    from fleetx_tpu.parallel.mesh import build_mesh

    cfg = tiny_cfg()
    cfg["Engine"]["max_steps"] = 4
    eng = build_engine(cfg, build_mesh({}, devices=devices8[:1]))
    opened = []
    real_span = eng.obs.span

    class Watched:
        def __init__(self, inner):
            self.inner, self.open = inner, False

        def __enter__(self):
            self.open = True
            return self.inner.__enter__()

        def __exit__(self, *exc):
            self.open = False
            return self.inner.__exit__(*exc)

    def watched_span(name, **args):
        cm = real_span(name, **args)
        if name != "fit.log":
            return cm
        opened.append(Watched(cm))
        return opened[-1]

    eng.obs.span = watched_span
    seen = []

    def hook(log_dict):
        seen.append(log_dict)
        if len(seen) == 2:
            raise RuntimeError("hook failed")

    eng.module.training_step_end = hook
    with pytest.raises(RuntimeError, match="hook failed"):
        eng.fit(make_batches(4))
    assert len(opened) == 2 and not any(w.open for w in opened)


# ------------------------------------------- (b) the three waits, last(n)
def _waits(req):
    return (req.admitted_at - req.submitted_at,
            req.prefill_started_at - req.admitted_at,
            req.first_token_at - req.prefill_started_at)


def test_three_waits_sum_to_ttft_for_every_request(small_model):
    eng = _engine(small_model, max_batch=2)
    reqs = [eng.submit([5 + i, 9, 23, 41, 7][:3 + i % 3], 3,
                       request_id=f"w{i}") for i in range(5)]
    eng.run_until_drained()
    m = eng.metrics
    parts = [m.histogram(f"serving_{p}") for p in
             ("queue_wait", "prefill_wait", "prefill_run")]
    assert [h.total_count for h in parts] == [5, 5, 5]
    for req in reqs:
        assert req.state == "finished"
        assert sum(_waits(req)) == pytest.approx(req.ttft_s, abs=1e-9)
        assert all(w >= 0 for w in _waits(req))
    # one sample each per first token, in the order of the first tokens
    order = sorted(reqs, key=lambda r: r.first_token_at)
    for h, k in zip(parts, range(3)):
        assert h.last(5) == pytest.approx([_waits(r)[k] for r in order])
    ttft = m.histogram("serving_ttft").last(5)
    assert [sum(x) for x in zip(*(h.last(5) for h in parts))] == \
        pytest.approx(ttft, abs=1e-9)
    # two slots, five requests: the later ones queued for admission
    assert max(_waits(r)[0] for r in reqs) > min(_waits(r)[0] for r in reqs)
    snap = eng.serving_snapshot()["first_token_waits"]
    assert set(snap) == {"queue_wait", "prefill_wait", "prefill_run"}
    assert all(set(v) == {"p50", "p95", "count"} and v["count"] == 5
               for v in snap.values())
    att = eng.timelines.get("w4").attribution()
    assert att["prefill_wait_s"] + att["prefill_run_s"] == \
        pytest.approx(att["prefill_s"])
    assert att["queue_s"] + att["prefill_s"] == pytest.approx(att["ttft_s"])


def test_a_preempted_request_keeps_its_first_marks(small_model):
    """A pool too small for three growing requests: the youngest is swapped
    out and runs again; its marks stay those of its first pass, and the
    three parts still sum to its (second) first token's wait."""
    eng = _engine(small_model, max_batch=3, num_pages=9, alloc_watermark=0)
    reqs = [eng.submit([5 + i, 9, 23, 41], 12, request_id=f"p{i}")
            for i in range(3)]
    first_marks = {}
    for _ in range(400):
        if not eng.has_work():
            break
        eng.step()
        for r in reqs:
            if r.prefill_started_at is not None and r.id not in first_marks:
                first_marks[r.id] = (r.admitted_at, r.prefill_started_at)
    assert all(r.state == "finished" for r in reqs)
    victims = [r for r in reqs if r.preemptions]
    assert victims, "the drill needs a preemption"
    for r in reqs:
        assert (r.admitted_at, r.prefill_started_at) == first_marks[r.id]
        assert sum(_waits(r)) == pytest.approx(
            r.first_token_at - r.submitted_at, abs=1e-9)
    # serving_ttft and its parts are recorded together, at every first token
    m = eng.metrics
    n = m.histogram("serving_ttft").total_count
    assert n >= 3 + len(victims) - 1
    assert m.histogram("serving_prefill_run").total_count == n


def test_histogram_last():
    h = Histogram("x", window=4)
    assert h.last(3) == []
    for v in range(6):
        h.record(v)
    assert h.last(2) == [4.0, 5.0]
    assert h.last(10) == [2.0, 3.0, 4.0, 5.0]      # the window holds four
    assert h.last(0) == [] and h.last(-1) == []
    assert h.total_count == 6


# ------------------------------------------------ (c) names from one table
def _span_names(path, within):
    """String literals given as a span's name in ``path``, inside the
    function or class ``within``: ``span`` / ``timed_span`` calls, the
    serving engine's ``_tick_span`` (a ``span`` with no flight note) and its
    ``_Phase`` (which prefixes ``serve.``)."""
    with open(os.path.join(REPO, path)) as f:
        tree = ast.parse(f.read())
    tree = next(n for n in ast.walk(tree)
                if isinstance(n, (ast.FunctionDef, ast.ClassDef))
                and n.name == within)
    found = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        name = fn.attr if isinstance(fn, ast.Attribute) else \
            getattr(fn, "id", "")
        texts = [a.value for a in node.args
                 if isinstance(a, ast.Constant) and isinstance(a.value, str)]
        if name in ("span", "timed_span", "_tick_span") and texts:
            found.add(texts[0])
        elif name == "_Phase" and texts:
            found.add("serve." + texts[0])
    return found


def test_the_engines_open_no_span_outside_the_table():
    serve = _span_names("fleetx_tpu/serving/engine.py",
                        within="ServingEngine")
    assert serve == set(SERVE_SPANS)
    fit = _span_names("fleetx_tpu/core/engine/eager_engine.py", within="fit")
    assert set(FIT_SPANS) <= fit <= set(HOT_LOOP_SPANS)
    assert fit | serve == set(HOT_LOOP_SPANS)
    for name, (kind, what) in HOT_LOOP_SPANS.items():
        assert kind in ("working", "waiting") and what, name
    assert {n for n, (k, _) in HOT_LOOP_SPANS.items() if k == "waiting"} == {
        "serve.prefill.wait", "serve.decode.wait", "fit.fetch_metrics",
        "sdc_sentinel"}


# ------------------------------------- (e) serving_tick, the slow-tick line
class _Clock:
    """A clock that moves 1 ms at every reading, and by leaps on demand."""

    def __init__(self):
        self.now = 100.0

    def __call__(self):
        self.now += 1e-3
        return self.now


def test_slow_tick_names_its_phase(small_model, caplog):
    from fleetx_tpu.utils.log import logger as fx_logger

    eng = _engine(small_model)
    clock = eng._clock = _Clock()
    eng.submit([5, 9, 23], 24, request_id="long")
    fx_logger.addHandler(caplog.handler)
    try:
        for _ in range(12):
            assert eng.step()
        assert "slow tick" not in caplog.text
        tick = eng.metrics.histogram("serving_tick")
        assert tick.total_count == 12
        # from one drain to the next: every reading of a tick, once
        assert all(0.005 < s < 0.02 for s in tick.last(12))
        assert tick.last(10) == pytest.approx([0.013] * 10, abs=1e-9)
        assert eng.metrics.histogram("serving_chunk_tick").total_count == 1
        assert eng.last_tick["tick"] == pytest.approx(
            sum(v for k, v in eng.last_tick.items() if k != "tick"),
            abs=0.012)              # the readings between phases
        assert set(eng.last_tick) == {"tick", "admit", "schedule", "decode",
                                      "decode.wait", "emit", "gauges"}

        stalled = eng._update_gauges

        def stall():
            clock.now += 2.5
            stalled()

        eng._update_gauges = stall
        assert eng.step()
        eng._update_gauges = stalled
        lines = [r.getMessage() for r in caplog.records
                 if "slow tick" in r.getMessage()]
        assert len(lines) == 1
        assert lines[0].startswith("slow tick 2.5")
        assert lines[0].split(": ")[1].startswith("gauges 2.50, ")
        assert eng.last_tick["gauges"] == pytest.approx(2.501, abs=2e-3)
        # the stall came after the drain: it delays the NEXT token, so the
        # next period (drain to drain) holds it, this one does not; the line
        # is said once, by the tick whose phase stalled
        assert tick.last(1)[0] < 0.02
        assert eng.step()
        assert tick.last(1)[0] == pytest.approx(2.513, abs=2e-3)
        assert len([r for r in caplog.records
                    if "slow tick" in r.getMessage()]) == 1
    finally:
        fx_logger.removeHandler(caplog.handler)


def test_projection_prices_a_chunk_at_the_mean_chunk_tick(small_model):
    eng = _engine(small_model)
    assert eng.projected_completion_s(8, 4) == (None, None)
    eng.submit([5, 9, 23, 41, 7], 3, request_id="warm")
    eng.run_until_drained()
    m = eng.metrics
    tick = m.histogram("serving_tick")
    chunk = m.histogram("serving_chunk_tick")
    itl = m.histogram("serving_inter_token")
    # five prompt tokens in chunks of four: two of the periods held a chunk
    # (nothing was in flight: the first ends where its dispatch did, the
    # second at the first token's fetch)
    assert tick.total_count == eng.steps > chunk.total_count == 2
    assert chunk.last(2) == tick.last(tick.total_count)[:2]
    mean_chunk = chunk.total_sum / chunk.total_count
    mean_itl = itl.total_sum / itl.total_count
    service, eta = eng.projected_completion_s(8, 4)
    assert service == pytest.approx(2 * mean_chunk + 4 * mean_itl)
    # a tick that only decodes does not move a chunk's price
    tick.record(100.0)
    assert eng.projected_completion_s(8, 4)[0] == pytest.approx(service)
    assert eta == pytest.approx(service)
    eng.reset_stats()
    assert eng.projected_completion_s(8, 4) == (None, None)
    for gone in ("serving_prefill_step", "serving_decode_step"):
        assert gone not in m.snapshot() or \
            m.histogram(gone).total_count == 0


def test_a_chunk_is_priced_in_the_period_that_ends_at_the_next_drain(
        small_model):
    """With a step in flight, a chunk runs on the device BEHIND that step:
    the period in which the device ran it ends at the drain of the tick
    after its dispatch, and that period is the one ``serving_chunk_tick``
    (so ``projected_completion_s``) takes."""
    eng = _engine(small_model)
    eng._clock = _Clock()
    eng.submit([5, 9, 23], 24, request_id="long")
    for _ in range(4):
        eng.step()
    m = eng.metrics
    tick = m.histogram("serving_tick")
    chunk = m.histogram("serving_chunk_tick")
    assert chunk.total_count == 1
    eng.submit([7, 3, 11, 2, 8, 4, 19, 33, 6], 2, request_id="late")
    dispatched, priced = [], []
    for _ in range(6):
        before = chunk.total_count
        assert eng.step()
        dispatched.append("prefill" in eng.last_tick)
        priced.append(chunk.total_count - before)
        if priced[-1]:
            assert chunk.last(1) == tick.last(1)
    assert dispatched == [True, True, True, False, False, False]
    assert priced == [0, 1, 1, 1, 0, 0]
    # a period that held a chunk is longer than one that only decoded (on
    # this clock by a phase's two readings: the chunk's dispatch or, for the
    # last, its first token's fetch, which comes after that tick's drain)
    assert chunk.last(3) == pytest.approx([0.015] * 3, abs=1e-9)
    assert tick.last(2) == pytest.approx([0.013] * 2, abs=1e-9)
    service, _ = eng.projected_completion_s(8, 1)
    itl = m.histogram("serving_inter_token")
    assert service == pytest.approx(
        2 * chunk.total_sum / chunk.total_count
        + itl.total_sum / itl.total_count)


def test_router_trace_keeps_the_attribution_with_a_first_token():
    """A replica that held the id without serving it (a hedge's cancelled
    loser) answers last: the winner's attribution must survive."""
    from fleetx_tpu.serving.router import Router

    answers = {
        ("a", 1): {"events": [{"name": "finished", "t": 2.0}],
                   "attribution": {"ttft_s": 0.25, "queue_s": 0.01}},
        ("b", 2): {"events": [{"name": "refused", "t": 1.0}],
                   "attribution": {"ttft_s": None, "queue_s": None}},
    }
    router = Router([("a", 1), ("b", 2)])
    router._ask = lambda addr, msg, **kw: answers[tuple(addr)]
    got = router.trace("w0")
    assert got["attribution"]["ttft_s"] == 0.25
    assert [e["name"] for e in got["events"]] == ["refused", "finished"]
    answers[("a", 1)], answers[("b", 2)] = answers[("b", 2)], answers[("a", 1)]
    assert router.trace("w0")["attribution"]["ttft_s"] == 0.25
