"""Host-side span tracer emitting Chrome-trace-event JSON.

Two layers, both cheap enough to leave on in production:

- ``span("name")`` — a context manager / decorator that records a Chrome
  "complete" event (``ph: "X"``) into the active ``Tracer`` AND enters
  ``jax.profiler.TraceAnnotation``, so when a ``jax.profiler`` window is
  open the host spans line up with the XLA timeline (the per-stage traces
  the MPMD pipeline work, arXiv:2412.14374, uses to find bubbles).
- ``ProfilerWindow`` — the config-gated ``jax.profiler`` trace window that
  used to live as inline flags in ``eager_engine.fit``. The inline version
  had two bugs this class fixes: (1) ``profiler_enabled = False`` after one
  window made a second ``fit()`` on the same engine silently unprofilable —
  the window is now re-armed per fit; (2) ``stop_trace`` ran without
  draining in-flight device work, truncating the tail of the trace —
  ``maybe_stop`` blocks on a sync value first.

The Chrome JSON (``{"traceEvents": [...]}``) loads directly in
https://ui.perfetto.dev or ``chrome://tracing``. Timestamps/durations are
microseconds per the trace-event spec; ``pid`` is the JAX process index so
multi-host traces merge cleanly.
"""

from __future__ import annotations

import functools
import json
import os
import threading
import time
from typing import Any, Optional

from fleetx_tpu.observability import flight
from fleetx_tpu.utils.log import logger

# jax is imported inside the functions that touch the profiler/backend so
# importing this module (and the observability package) stays jax-free —
# the stdlib-only serving router reuses the package's sinks/schema


def _process_index() -> int:
    try:
        import jax

        return jax.process_index()
    except (ImportError, RuntimeError):  # backend not initialised yet
        return 0


class Tracer:
    """Collects span events; ``save()`` writes one Chrome-trace JSON file."""

    def __init__(self, max_events: int = 200_000):
        self._events: list[dict] = []
        self._lock = threading.Lock()
        self._max_events = int(max_events)
        self._dropped = 0

    def add_event(self, name: str, ts_us: float, dur_us: float,
                  args: Optional[dict] = None) -> None:
        """Record one complete ('X') event; drops past the event cap."""
        evt = {
            "name": name, "ph": "X", "ts": ts_us, "dur": dur_us,
            "pid": _process_index(), "tid": threading.get_ident() & 0xFFFF,
        }
        if args:
            evt["args"] = args
        with self._lock:
            if len(self._events) >= self._max_events:
                self._dropped += 1
                return
            self._events.append(evt)

    @property
    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._dropped = 0

    def to_chrome_trace(self) -> dict:
        """The Perfetto/chrome://tracing JSON object for all events."""
        meta = {"dropped_events": self._dropped} if self._dropped else {}
        return {"traceEvents": self.events, "displayTimeUnit": "ms",
                **({"otherData": meta} if meta else {})}

    def save(self, path: str) -> str:
        """Write the trace (rank-0 file naming is the caller's concern —
        each process writes its own events; pids disambiguate on merge)."""
        if self._dropped:
            logger.warning("tracer dropped %d events past the %d-event cap",
                           self._dropped, self._max_events)
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        logger.info("chrome trace written: %s (%d events — open in "
                    "https://ui.perfetto.dev)", path, len(self._events))
        return path


# Active tracer: span() records into it when set. Default None keeps span()
# at pure-TraceAnnotation cost for code paths with observability off.
_active_tracer: Optional[Tracer] = None


def set_tracer(tracer: Optional[Tracer]) -> Optional[Tracer]:
    """Install the active tracer; returns the previous one (restorable)."""
    global _active_tracer
    prev = _active_tracer
    _active_tracer = tracer
    return prev


def get_tracer() -> Optional[Tracer]:
    return _active_tracer


#: Every span the two host loops open — ``ServingEngine.step`` and the step
#: loop of ``EagerEngine.fit`` — with what it covers and whether the host is
#: *working* in it or *waiting* on the device. The engines open no span
#: outside this table (tests hold them to it) and the benchmark's readers
#: (``benchmarks/program_spans.py``) import it. Spans nest strictly, on the
#: loop's own thread: ``serve.tick`` holds every other ``serve.*`` span and
#: ``serve.emit`` holds ``serve.prefill.wait``; the ``fit`` spans follow
#: each other. A tick dispatches before it fetches (docs/serving.md "The
#: tick"): the two waits are for work dispatched EARLIER, while the device
#: already holds this tick's.
HOT_LOOP_SPANS: dict = {
    "serve.tick": ("working", "one ServingEngine.step(): every serve.* "
                              "span below lies inside it"),
    "serve.admit": ("working", "waiting requests take a slot and pages"),
    "serve.prefill": ("working", "build one prompt chunk and dispatch the "
                                 "prefill program (rid=, chunk=); a last "
                                 "chunk joins the decode batch here"),
    "serve.prefill.wait": ("waiting", "device_get of the token of a last "
                                      "chunk dispatched in this tick, after "
                                      "the decode step behind it was: the "
                                      "device runs what was queued before "
                                      "the chunk, and the chunk"),
    "serve.schedule": ("working", "shed expired requests, grow block tables "
                                  "or preempt, list the rows that decode"),
    "serve.decode": ("working", "dispatch the decode program on the tokens "
                                "the device holds; lengths advance here"),
    "serve.decode.wait": ("waiting", "device_get of the tokens of the step "
                                     "dispatched in the tick BEFORE: the "
                                     "device finishes that step while this "
                                     "tick's work is queued behind it"),
    "serve.emit": ("working", "per row of the fetched step that still is "
                              "what it ran: inter-token sample, timeline "
                              "note, the token, finish; then a last "
                              "chunk's first token (prefill.wait inside)"),
    "serve.gauges": ("working", "queue, slot, page and fragmentation gauges"),
    "data_fetch": ("working", "next batch from the loader or the device "
                              "prefetcher"),
    "shard_batch": ("working", "host batch onto the mesh (device_put)"),
    "train_step": ("working", "the call of the jitted train step: dispatch, "
                              "not device time (step=)"),
    "sdc_sentinel": ("waiting", "a sentinel round's replay of the step and "
                                "its comparison (Resilience, off by default)"),
    "fit.fetch_metrics": ("waiting", "device_get of the step's metrics: the "
                                     "device finishes the step"),
    "fit.log": ("working", "from the metrics on the host to the next "
                           "data_fetch: training_step_end, the train record, "
                           "guard, eval and save triggers, and the loop's "
                           "control ahead of data_fetch (the gang's vote, "
                           "its idle rounds)"),
}


class span:
    """``with span("train_step", step=3): ...`` or ``@span("load")``.

    Records a complete event into the active tracer (if any) and nests the
    region under ``jax.profiler.TraceAnnotation`` so host work is visible
    inside XLA profiler windows. Nesting falls out of the trace-event model:
    an inner span's ``[ts, ts+dur]`` lies within its parent's on the same
    tid, which Perfetto renders as a nested slice. ``args`` ride on all
    three: the profiler annotation, the Chrome event, the flight note.

    ``flight_note=False`` keeps the span out of the flight ring: the
    serving tick's nine spans would push the serving events out of a
    replica's 512-event ring, and a loop whose telemetry is off notes
    nothing. With no tracer and no recorder installed a span is the bare
    annotation (a flag check while no profiler session is live) and two
    clock reads.
    """

    __slots__ = ("name", "args", "flight_note", "_t0", "_ts", "_annotation")

    def __init__(self, name: str, *, flight_note: bool = True, **args: Any):
        self.name = name
        self.args = args or None
        self.flight_note = flight_note

    def __enter__(self):
        import jax

        self._annotation = jax.profiler.TraceAnnotation(
            self.name, **(self.args or {}))
        self._annotation.__enter__()
        # wall-clock anchor captured at ENTRY (multi-process traces share
        # the epoch, and an outer span's ts always precedes its children's);
        # duration from perf_counter for sub-µs stability
        self._ts = time.time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = time.perf_counter() - self._t0
        self._annotation.__exit__(exc_type, exc, tb)
        tracer = _active_tracer
        if tracer is not None:
            tracer.add_event(self.name, self._ts * 1e6, dur * 1e6, self.args)
        # spans are the flight recorder's timeline backbone: a crash dump
        # shows exactly which phase each rank was in (no-op when no
        # recorder is installed — one None check). Span args ride NESTED:
        # span() accepts arbitrary keywords, and a user arg named "kind"
        # or "t" must not collide with the event's own fields.
        if self.flight_note and flight.get_recorder() is not None:
            extra = {"args": self.args} if self.args else {}
            flight.note("span", self.name,
                        dur_ms=round(dur * 1000.0, 3), **extra)
        return False

    def __call__(self, fn):
        @functools.wraps(fn)
        def wrapper(*a, **kw):
            with span(self.name, flight_note=self.flight_note,
                      **(self.args or {})):
                return fn(*a, **kw)
        return wrapper


class ProfilerWindow:
    """Config-gated ``jax.profiler`` trace window, re-armable per fit.

    States: ``armed`` → (step >= start) → ``active`` → (step >= stop) →
    ``done``; ``arm()`` at the top of every ``fit()`` resets ``done`` back
    to ``armed`` so each fit gets its own window (the old inline flags
    cleared ``profiler_enabled`` forever after one window).
    """

    def __init__(self, cfg: Optional[dict] = None):
        prof = dict(cfg or {})
        self.enabled = bool(prof.get("enable"))
        sched = list(prof.get("scheduler") or [])

        def _int(key, default):
            v = prof.get(key, default)
            return default if v is None else int(v)

        self.start_step = _int("start_step", int(sched[0]) if sched else 3)
        self.stop_step = _int("stop_step", int(sched[1]) if len(sched) > 1
                              else self.start_step + 5)
        self.output_dir = (prof.get("output_dir")
                           or prof.get("profiler_log") or "./profiler_log")
        # reference Profiler's "detailed" flag: also emit a standalone
        # perfetto trace file next to the xplane dump
        self.detailed = bool(prof.get("detailed"))
        self._active = False
        self._done = False

    @property
    def active(self) -> bool:
        return self._active

    def arm(self) -> None:
        """Reset for a new fit: a completed window may run again."""
        self._done = False

    def maybe_start(self, step: int) -> bool:
        """Open the window when armed and ``step`` has reached start_step."""
        if (not self.enabled or self._active or self._done
                or step < self.start_step):
            return False
        import jax

        jax.profiler.start_trace(self.output_dir,
                                 create_perfetto_trace=self.detailed)
        self._active = True
        logger.info("profiler trace started → %s", self.output_dir)
        return True

    def maybe_stop(self, step: int, sync: Any = None) -> bool:
        """Close the window once ``step`` passes stop_step (drains first)."""
        if not self._active or step < self.stop_step:
            return False
        self.stop(sync=sync)
        return True

    def stop(self, sync: Any = None) -> None:
        """Close an open window, draining device work first so the trace
        tail isn't truncated (the old inline stop skipped the sync)."""
        if not self._active:
            return
        import jax

        if sync is not None:
            jax.block_until_ready(sync)
        jax.profiler.stop_trace()
        self._active = False
        self._done = True
        logger.info("profiler trace written to %s", self.output_dir)
