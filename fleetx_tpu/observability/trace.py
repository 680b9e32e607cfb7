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
import glob
import json
import os
import re
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


#: Every scope the device programs open — the train step and the two serving
#: programs of every family — with what it covers. ``device_scope(name)``
#: opens ``jax.named_scope("fx.<name>")``; a scope is opened where the work
#: is written and the innermost one an instruction lies in is its scope, so
#: a kernel wrapper in ``ops/`` opens none (its caller's holds it). The
#: engines and models open no ``fx.`` scope outside this table (tests hold
#: them to it) and the benchmark's readers (``benchmarks/program_scopes.py``)
#: put a trace's device time under these names through
#: ``compiled_programs()``.
DEVICE_SCOPES: dict = {
    "embed": "token (and position) embedding look-up; its backward is the "
             "embedding gradient's scatter-add",
    "norm": "a layer's pre-norms (layer norm or RMS norm, fused or not) "
            "and the residual add a fused norm holds",
    "attn.proj": "query / key / value / latent / output products, their "
                 "biases, the latents' norms, rotary, gates",
    "attn.core": "the flash, latent-flash or paged kernel, or the XLA "
                 "scores where no kernel runs (a prefill's folded key "
                 "blocks of a request's pages or a slot's ring, a decode "
                 "fallback's gathered page view)",
    "attn.cache": "the pool's row scatter, a ring's write, a dense decode "
                  "cache's update",
    "gdn.proj": "a linear-attention layer's products (queries, keys, "
                "values, the output gate, decay and write strength), its "
                "gates, the output's norm and gate, the output product",
    "gdn.conv": "the causal depth-wise convolution over [q; k; v] with its "
                "SiLU, and the read and write of the slot's last inputs",
    "gdn.core": "the gated delta rule: the chunked form of a prefill chunk "
                "(the gdn_chunk kernel: triangular systems and carried "
                "state), the one-token step (gdn_decode), the heads' L2 "
                "norms, the state's read and write",
    "attn.cross": "a walk of the one key-value pool that several layers "
                  "read, as a decode row reads it: the full layer's own in "
                  "a decode step, every cross layer's in both programs "
                  "(their products are attn.proj's; the window layers' "
                  "rings and a prefill chunk's fold are attn.core's)",
    "ssm.proj": "a selective-scan layer's products: into the scan's input "
                "and its gate, into the step, B and C, the step's "
                "projection with its softplus, the gate and the out product",
    "ssm.conv": "the causal depth-wise convolution over the scan's input "
                "with its bias and SiLU, and the read and write of the "
                "slot's last inputs (the tail)",
    "ssm.core": "the selective scan: a chunk's tokens from the slot's state "
                "(ssm_chunk), the one-token step over the live slots "
                "(ssm_decode), the state's read and write",
    "ssm.norm": "the RMS norms INSIDE a selective-scan layer's input path: "
                "on the step's input, B and C, between the product that "
                "makes them and the ones that use them",
    "gmu": "a gated memory unit: the gate's product, its SiLU times the "
           "last scan layer's output, the out product",
    "conv.proj": "a short-convolution layer's two products: into the two "
                 "gates and the convolution's input, and out",
    "conv.mix": "the gates' products with the input and the output, the "
                "depth-wise taps, and the read and write of the slot's "
                "last inputs (the tail)",
    "mlp": "dense MLP, shared expert, and the block's closing residual add",
    "moe.route": "router product and scoring, top-k, plan_rows' counts and "
                 "its scatter, the gathers into expert order, the combine's "
                 "scatter-add, the load-bias step",
    "moe.experts": "the grouped products over the held experts and the "
                   "activation between them",
    "stack": "what a layer loop does around its layers: slices of the "
             "stacked weights, writes and reads of the activations saved "
             "for the backward, the stacked gradients, the loop's counters",
    "head": "final norm and the logits product",
    "loss": "log-sum-exp, the label's logit, the masked mean",
    "mtp": "the multi-token-prediction module's own work: the join of "
           "the next token's embedding with the hidden state, and its "
           "projection",
    "sample": "the fresh row's merge, argmax or the sampling chain",
    "optimizer": "global norm, clip, AdamW, apply; the ZeRO gather of the "
                 "parameters and scatter of the gradients; loss-scale and "
                 "finite-step bookkeeping",
}

SCOPE_PREFIX = "fx."
_FX = re.compile(re.escape(SCOPE_PREFIX) + r"([a-z]+(?:\.[a-z]+)*)")
DIRECTIONS = ("fwd", "bwd", "remat")
UNSCOPED = ("", "")


def device_scope(name: str):
    """``with device_scope("attn.core"): ...`` — the one place the package
    opens a ``jax.named_scope``: ``fx.<name>`` on every instruction traced
    inside, for ``device_scope_table`` to find. Metadata only: the compiled
    program is the one it would be without."""
    if name not in DEVICE_SCOPES:
        raise KeyError(f"{name!r} is not in DEVICE_SCOPES")
    import jax

    return jax.named_scope(SCOPE_PREFIX + name)


_COMPUTATION = re.compile(r"^(ENTRY )?%?([^\s(]+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?(\S+) = ")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REFERS_TO = re.compile(r"%([^\s,(){}]+)")
_CALLED = re.compile(
    r"(?:body|condition|to_apply|calls|true_computation|false_computation)"
    r"=%?([^\s,)}]+)|branch_computations=\{([^}]*)\}")
#: opcodes whose called computations run as instructions of their own on
#: the device's timeline (a fusion's or a reduce's do not)
_STEPS_INTO = frozenset({"while", "conditional", "call", "async-start"})


def scope_of(op_name: str) -> tuple:
    """``(scope, direction)`` of one ``op_name``: the innermost ``fx.``
    component; ``remat`` under ``rematted_computation`` (whatever else
    surrounds it), ``bwd`` under ``transpose(``, else ``fwd``. Of several
    names joined by ``;`` (instructions the compiler merged) the first that
    has a scope. ``("", "")`` without one."""
    for part in op_name.split(";"):
        found = _FX.findall(part)
        if found:
            return found[-1], ("remat" if "rematted_computation" in part
                               else "bwd" if "transpose(" in part else "fwd")
    return UNSCOPED


def hlo_instructions(hlo_text: str):
    """``(name, opcode, op_name, operands)`` of every instruction of an
    optimised program's text (``compiled.as_text()``) that runs on the
    device's timeline as an instruction of its own: the entry computation and the
    ``while`` / ``conditional`` / ``call`` bodies under it — what a device
    trace's ``XLA Ops`` line shows — not the insides of a fusion or a
    reducer. Names as the trace spells them, without ``%``; ``op_name`` is
    the instruction's own (a fusion's is its root's), ``""`` without one;
    ``operands`` the ``%`` names its text refers to."""
    computations: dict = {}
    entry, current = None, None
    for line in hlo_text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = computations.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
        elif line.startswith("}"):
            current = None
        elif _INSTRUCTION.match(line):
            current.append(line)
    todo, seen = [entry], {entry}
    while todo:
        for line in computations.get(todo.pop(), ()):
            name = _INSTRUCTION.match(line).group(1)
            rest = line.split(" = ", 1)[1]
            found = _OPCODE.search(" " + rest)
            opcode = found.group(1) if found else ""
            op_name = _OP_NAME.search(rest)
            yield (name, opcode, op_name.group(1) if op_name else "",
                   _REFERS_TO.findall(rest))
            if opcode not in _STEPS_INTO:
                continue
            for one, many in _CALLED.findall(rest):
                for called in [one] if one else re.findall(r"%?([^\s,]+)",
                                                           many):
                    if called not in seen:
                        seen.add(called)
                        todo.append(called)


def device_scope_table(hlo_text: str) -> dict:
    """``{instruction: (scope, direction)}`` for an optimised program's
    text: every instruction ``hlo_instructions`` yields, by ``scope_of``
    its ``op_name``. An instruction WITHOUT a name of the program's (no
    ``op_name``, or one that is no path: a parameter's, a compiler pass's
    own) is one the compiler made for another — a prefetch's ``copy-start``
    / ``copy-done``, a relayout's ``copy``, a hoisted ``convert``, the
    window sums a ``cumsum`` becomes — and goes where the work it serves
    goes: to the first scope down its chain of users (through further
    unnamed ones); else up its operands, to the first that has a scope or,
    unnamed itself, serves one (a prefetch for the loop's next turn hangs
    on the value the loop carries) — not through a ``parameter`` or a
    ``tuple``, which everything a loop carries shares; ``("", "")`` when
    there is none."""
    rows = list(hlo_instructions(hlo_text))
    # the program's own names are paths (``jit(step)/…``); a name without
    # a ``/`` is a parameter's or one a compiler pass made up
    own = {name: scope_of(op_name) if "/" in op_name else None
           for name, _, op_name, _ in rows}
    shared = {name for name, opcode, _, _ in rows
              if opcode in ("parameter", "tuple")}
    operands = {name: [o for o in refs if o in own]
                for name, _, _, refs in rows}
    users: dict = {}
    for name, made_from in operands.items():
        for o in made_from:
            users.setdefault(o, []).append(name)

    def serves(name: str, seen: set) -> tuple:
        """The first scope down the chain of users, depth first."""
        for user in users.get(name, ()):
            if user in seen:
                continue
            seen.add(user)
            got = own[user] if own[user] is not None else serves(user, seen)
            if got != UNSCOPED:
                return got
        return UNSCOPED

    def nearest(name: str) -> tuple:
        got = serves(name, {name})
        seen, todo = {name}, [name]
        while got == UNSCOPED and todo:
            for o in operands[todo.pop(0)]:
                if o in seen:
                    continue
                seen.add(o)
                if own[o] is not None:
                    got = own[o]
                elif o not in shared:
                    got = serves(o, {o, name})
                if got != UNSCOPED:
                    break
                if own[o] is None:
                    todo.append(o)
        return got

    return {name: nearest(name) if got is None else got
            for name, got in own.items()}


#: module name as a device trace prints it (``jit_train_step``) -> the
#: optimised HLO modules of the newest program of that name ``log_compile``
#: compiled (host objects: no device buffer, no executable), or, once
#: ``compiled_programs`` was asked, its table
_programs: dict = {}
_programs_lock = threading.Lock()


def keep_compiled(hlo_modules: list) -> None:
    """Remember a compiled program's optimised HLO modules
    (``compiled.runtime_executable().hlo_modules()``) under their name.
    Nothing is printed or parsed here: ``compiled_programs`` does that when
    asked. The newest program of a name replaces the older."""
    if hlo_modules:
        with _programs_lock:
            _programs[hlo_modules[0].name] = list(hlo_modules)


def compiled_programs() -> dict:
    """``{module: {instruction: (scope, direction)}}`` for every program
    ``utils.env.log_compile`` compiled in this process, newest of each
    name. The text is printed and parsed on the first request and only the
    table kept. It is the executable's OWN metadata: a program loaded from
    the persistent compile cache says what it was compiled with, so table
    and trace always agree with each other."""
    with _programs_lock:
        for name, kept in list(_programs.items()):
            if isinstance(kept, list):
                table: dict = {}
                for module in kept:
                    table.update(device_scope_table(module.to_string()))
                _programs[name] = table
        return dict(_programs)



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
        self._write_device_scopes()

    def _write_device_scopes(self) -> None:
        """``device_scopes.json`` beside the ``.xplane.pb`` just closed:
        ``{module: {instruction: [scope, direction]}}`` from
        ``compiled_programs()``, to join to the trace's ``XLA Ops`` line
        (docs/profiler.md). Nothing where nothing was compiled through
        ``log_compile`` or no trace file is found."""
        written = glob.glob(os.path.join(self.output_dir, "plugins",
                                         "profile", "*", "*.xplane.pb"))
        programs = compiled_programs()
        if not written or not programs:
            return
        path = os.path.join(
            os.path.dirname(max(written, key=os.path.getmtime)),
            "device_scopes.json")
        with open(path, "w") as f:
            json.dump(programs, f)
        logger.info("device scopes of %d programs written to %s",
                    len(programs), path)
