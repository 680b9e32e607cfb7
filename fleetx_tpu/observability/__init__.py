"""Unified telemetry: metrics registry, span tracer, and sinks.

The package the ROADMAP's "as fast as the hardware allows" goal measures
itself with (docs/observability.md). Three layers:

- ``metrics``  — counters/gauges/windowed histograms + derived
  tokens-per-sec / step-time EWMA / data-stall / MFU arithmetic;
- ``trace``    — ``span()`` host spans emitting Chrome-trace JSON, nested
  under ``jax.profiler.TraceAnnotation``, plus the re-armable
  ``ProfilerWindow`` for XLA traces;
- ``sinks``    — rank-0-gated JSONL / CSV / Prometheus-textfile emitters.

``Observability`` ties them together for the engines: built from the
``Observability:`` YAML block (``utils/config.py``), it owns the tracer
lifecycle, the sink fan-out and the derived-metric state, and is a cheap
no-op when the block is absent or disabled.
"""

from __future__ import annotations

import contextlib
import os
from typing import Any, Optional

from fleetx_tpu.observability import flight as flight_mod
from fleetx_tpu.observability import gang as gang_mod
from fleetx_tpu.observability.flight import FlightRecorder  # noqa: F401
from fleetx_tpu.observability.memory import (  # noqa: F401
    MemoryMonitor, sample_memory_stats)
from fleetx_tpu.observability.metrics import (  # noqa: F401
    Counter, DerivedMetrics, Gauge, Histogram, MetricsRegistry, get_registry,
    mfu)
from fleetx_tpu.observability.sinks import (  # noqa: F401
    CsvSink, JsonlSink, PrometheusTextfileSink, Sink, build_sinks)
from fleetx_tpu.observability.trace import (  # noqa: F401
    HOT_LOOP_SPANS, ProfilerWindow, Tracer, _process_index, get_tracer,
    set_tracer, span)
from fleetx_tpu.utils.log import logger

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "DerivedMetrics",
    "get_registry", "mfu", "Sink", "JsonlSink", "CsvSink",
    "PrometheusTextfileSink", "build_sinks", "Tracer", "ProfilerWindow",
    "span", "get_tracer", "set_tracer", "Observability", "FlightRecorder",
    "MemoryMonitor", "sample_memory_stats",
]


def _process_count() -> int:
    try:
        import jax  # deferred: package import stays jax-free (router reuse)

        return jax.process_count()
    except (ImportError, RuntimeError):  # backend not initialised yet
        return 1


class Observability:
    """Engine-facing facade over registry + tracer + sinks.

    ``Observability(cfg_block)`` with a falsy/disabled block yields an
    object whose every method is a no-op, so the engines call it
    unconditionally and pay nothing when telemetry is off.
    """

    def __init__(self, cfg: Optional[dict] = None,
                 default_output_dir: str = "./output"):
        cfg = dict(cfg or {})
        self.enabled = bool(cfg.get("enable"))
        self.output_dir = str(cfg.get("output_dir")
                              or os.path.join(default_output_dir, "telemetry"))
        # explicit None checks: ewma_alpha 0 (no smoothing) is a valid value
        alpha = cfg.get("ewma_alpha")
        self.ewma_alpha = 0.1 if alpha is None else float(alpha)
        # the process-wide registry: checkpoint.py and the inference path
        # record into the same one, so engine records see their timings
        self.registry = get_registry()
        self.sinks: list[Sink] = []
        self.tracer: Optional[Tracer] = None
        self._trace_path: Optional[str] = None
        self.derived: Optional[DerivedMetrics] = None
        # gang mode (docs/observability.md "Multi-host"): per-rank sinks +
        # cross-rank merging piggybacked on the loop-control vote; OFF by
        # default so single-process records stay byte-identical to PR 1
        self.gang_enabled = bool(cfg.get("gang"))
        self.rank = _process_index()
        self.world = _process_count()
        self._gang_sink: Optional[Sink] = None
        self._pending_snaps: list[dict] = []
        self._stash_window = 0
        # crash flight recorder: on whenever telemetry is (an in-memory
        # ring that only touches disk when the run dies); a disabled
        # facade clears any previously-installed recorder, mirroring the
        # Resilience facade's engine-scoped-globals stance
        flight_cfg = dict(cfg.get("flight") or {})
        flight_on = flight_cfg.get("enable")
        self.flight: Optional[FlightRecorder] = None
        if self.enabled and (True if flight_on is None else bool(flight_on)):
            flight_dir = (os.environ.get(flight_mod.ENV_DIR)
                          or os.path.join(self.output_dir, "flight"))
            self.flight = FlightRecorder(
                flight_dir, rank=self.rank, world=self.world,
                capacity=int(flight_cfg.get("capacity")
                             or flight_mod.DEFAULT_CAPACITY))
        flight_mod.install(self.flight)
        if not self.enabled:
            return
        window = cfg.get("histogram_window")
        self.registry.set_default_window(1024 if window is None
                                         else int(window))
        self.sinks = build_sinks(
            cfg.get("sinks") or ["jsonl"], self.output_dir,
            # gang mode: every rank writes its own rank-suffixed files
            # (the per-rank inputs tools/metrics_report.py merges) instead
            # of the rank-0-gated single file
            rank0_only=not self.gang_enabled,
            suffix=f".rank{self.rank}" if self.gang_enabled else "")
        trace_cfg = dict(cfg.get("trace") or {})
        if trace_cfg.get("enable", True):
            self.tracer = Tracer(
                max_events=int(trace_cfg.get("max_events") or 200_000))
            fname = str(trace_cfg.get("path") or "trace.json")
            path = (fname if os.path.isabs(fname)
                    else os.path.join(self.output_dir, fname))
            rank = _process_index()
            if rank:
                # each host writes its own file (shared storage: same path
                # from every process would clobber); merge in Perfetto by pid
                root, ext = os.path.splitext(path)
                path = f"{root}.rank{rank}{ext or '.json'}"
            self._trace_path = path
            set_tracer(self.tracer)
        logger.info("observability enabled → %s (sinks: %s%s)",
                    self.output_dir,
                    [type(s).__name__ for s in self.sinks],
                    ", tracing" if self.tracer else "")

    # -- spans ---------------------------------------------------------------
    def span(self, name: str, **args: Any):
        """The one ``span``. Disabled, it leaves no flight note (and this
        facade installed no tracer), so what is left is the profiler
        annotation — a flag check while no session is live: the loop's
        phases reach a trace whether or not telemetry is configured."""
        return span(name, flight_note=self.enabled, **args)

    def timed_span(self, name: str, **args: Any):
        """Span composed with ``registry.timer``: one region feeds the trace,
        the ``name`` histogram and the ``<name>_seconds_total`` counter.
        Disabled, it is ``span`` alone: no timer."""
        if not self.enabled:
            return self.span(name, **args)
        stack = contextlib.ExitStack()
        stack.enter_context(span(name, **args))
        stack.enter_context(self.registry.timer(name))
        return stack

    # -- derived metrics -----------------------------------------------------
    def init_derived(self, flops_per_token: Optional[float],
                     n_devices: int) -> None:
        """Create the DerivedMetrics layer once the module/mesh are known."""
        import jax

        from fleetx_tpu.utils.hardware import peak_flops

        self.derived = DerivedMetrics(
            flops_per_token=flops_per_token,
            peak_flops_per_chip=peak_flops(jax.devices()[0]),
            n_devices=n_devices, ewma_alpha=self.ewma_alpha)
        # the registry is process-wide: baseline the stall integral so a
        # fresh engine's first window doesn't inherit prior engines' stalls
        self.derived._last_stall_total = self.stall_seconds_total()

    def stall_seconds_total(self) -> float:
        """Monotone host-blocked time: data fetch + host-to-device copy."""
        return (self.registry.counter("data_fetch_seconds_total").value
                + self.registry.counter("shard_batch_seconds_total").value)

    # -- record fan-out ------------------------------------------------------
    def emit(self, record: dict) -> None:
        """Fan one step record out to every sink (never raises).

        Gang mode stamps the record with this rank's identity and the
        schema version before it lands in the rank-suffixed files, and
        mirrors a slim form into the flight ring so a crash dump shows
        the final windows' numbers next to the final spans.
        """
        if not self.enabled:
            return
        if self.gang_enabled:
            from fleetx_tpu.observability.schema import SCHEMA_VERSION

            record = dict(record, rank=self.rank, world=self.world,
                          schema_version=SCHEMA_VERSION)
        if self.flight is not None:
            self.flight.record(
                "metrics", "window", step=record.get("step"),
                loss=record.get("loss"),
                step_time=record.get("step_time"))
        for sink in self.sinks:
            try:
                sink.emit(record)
            except OSError as e:  # a full disk must not kill training
                logger.warning("sink %s emit failed: %s",
                               type(sink).__name__, e)

    # -- gang aggregation (docs/observability.md "Multi-host") ---------------
    def gang_stash(self, record: dict) -> None:
        """Queue one window's record for the next loop-control vote.

        The stash counter is the window-alignment key: lockstep loop
        iterations mean every rank's N-th stash describes the same gang
        window even when step counters diverge under the in-step skip.
        """
        self._pending_snaps.append(gang_mod.snapshot(
            record, self.registry, self.rank, self._stash_window))
        self._stash_window += 1

    def gang_take_pending(self) -> list:
        """Drain the stashed snapshots (the vote payload's ``obs`` field)."""
        pending, self._pending_snaps = self._pending_snaps, []
        return pending

    def gang_merge_emit(self, votes: dict) -> None:
        """Rank 0: merge every rank's piggybacked snapshots into
        gang-scoped records and append them to ``metrics.gang.jsonl``.

        A separate file rather than interleaving with rank 0's local
        records: the merged stream has different aggregation semantics
        (summed counters, slowest-rank throughput) and mixing the two
        would double-count in any downstream summary.
        """
        snaps = {r: f.get("obs") for r, f in votes.items()
                 if isinstance(f, dict) and f.get("obs")}
        if not snaps:
            return
        merged = gang_mod.merge_snapshots(snaps, world=self.world)
        if not merged:
            return
        if self._gang_sink is None:
            self._gang_sink = JsonlSink(
                os.path.join(self.output_dir, "metrics.gang.jsonl"))
        for record in merged:
            try:
                self._gang_sink.emit(record)
            except OSError as e:  # a full disk must not kill training
                logger.warning("gang sink emit failed: %s", e)

    def install_arrival_hook(self) -> None:
        """Route coordination arrival censuses into the skew estimator
        (call once the DerivedMetrics layer exists)."""
        if self.derived is None:
            return

        def _on_arrivals(arrivals: dict) -> None:
            self.derived.update_arrivals(arrivals)
            own = self.derived.rank_skew().get(self.rank)
            if own is not None:
                self.registry.gauge("rank_skew").set(own)

        self._arrival_hook = _on_arrivals
        gang_mod.set_arrival_hook(_on_arrivals)

    def own_skew(self) -> Optional[float]:
        """This rank's rolling arrival skew in seconds (None off-gang)."""
        if self.derived is None:
            return None
        return self.derived.rank_skew().get(self.rank)

    def flight_dump(self, reason: str) -> None:
        """Dump the flight ring (no-op without a recorder; never raises)."""
        if self.flight is not None:
            flight_mod.dump(reason)

    def flush(self) -> None:
        """Durable-ize sinks and write the Chrome trace snapshot."""
        if not self.enabled:
            return
        for sink in self.sinks:
            sink.flush()
        if self._gang_sink is not None:
            self._gang_sink.flush()
        if self.tracer is not None and self._trace_path and \
                self.tracer.events:
            self.tracer.save(self._trace_path)

    def close(self) -> None:
        """Flush + close sinks, release the tracer and the gang hooks."""
        if not self.enabled:
            return
        self.flush()
        for sink in self.sinks:
            sink.close()
        self.sinks = []
        if self._gang_sink is not None:
            self._gang_sink.close()
            self._gang_sink = None
        if get_tracer() is self.tracer:
            set_tracer(None)
        if flight_mod.get_recorder() is self.flight:
            flight_mod.install(None)
        # identity-guarded like the tracer/recorder: closing an old facade
        # must not uninstall a newer engine's skew hook
        if gang_mod.get_arrival_hook() is getattr(self, "_arrival_hook",
                                                  None):
            gang_mod.set_arrival_hook(None)
