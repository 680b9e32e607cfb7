"""Process-local metrics registry + derived training metrics.

The reference FleetX logs only formatted per-step lines
(``language_module.py:58-67``); nothing downstream can consume them. Here
every signal is a first-class, machine-readable metric:

- ``Counter`` / ``Gauge`` / ``Histogram`` primitives collected in a
  ``MetricsRegistry`` (one per process; a module-level default registry is
  shared by the engines, ``core/checkpoint.py`` and the inference path).
- ``Histogram`` keeps a bounded sample window and reports p50/p95/p99 —
  enough for request latencies and step-time spread without a t-digest dep.
- ``DerivedMetrics`` turns raw window measurements into the quantities the
  ROADMAP's "fast as the hardware allows" goal needs tracked: tokens/sec,
  step-time EWMA, data-stall fraction, and MFU from
  ``utils/hardware.py``'s ``peak_flops`` / ``gpt_flops_per_token``
  (arXiv:2204.06514 treats MFU as the primary tracked quantity).

Everything here is host-side Python — nothing is jitted, nothing touches
device state, so recording a metric costs nanoseconds against a
multi-millisecond train step.
"""

from __future__ import annotations

import itertools
import math
import threading
import time
from collections import deque
from typing import Any, Optional


class Counter:
    """Monotonically increasing count (events, tokens, bytes)."""

    __slots__ = ("name", "_value")

    def __init__(self, name: str):
        self.name = name
        self._value = 0.0

    def inc(self, amount: float = 1.0) -> None:
        self._value += amount  # fleetx: noqa[FX014] -- documented lock-free design (module docstring): a float += under the GIL may at worst lose a tick; metrics tolerate that, a per-inc lock on the train-loop hot path does not

    @property
    def value(self) -> float:
        return self._value

    def reset(self) -> None:
        self._value = 0.0


class Gauge:
    """Last-written value (loss scale, queue depth, HBM headroom)."""

    __slots__ = ("name", "_value")

    def __init__(self, name: str):
        self.name = name
        self._value: Optional[float] = None

    def set(self, value: float) -> None:
        self._value = float(value)

    @property
    def value(self) -> Optional[float]:
        return self._value

    def reset(self) -> None:
        self._value = None


class Histogram:
    """Windowed sample buffer reporting count/mean/min/max and quantiles.

    The window is a bounded deque: old samples fall off, so long runs report
    recent behaviour rather than an all-time average. Totals (``total_count``
    / ``total_sum``) survive window eviction and ``reset()`` only clears the
    window, so rates stay computable across flushes.
    """

    __slots__ = ("name", "_window", "total_count", "total_sum")

    def __init__(self, name: str, window: int = 1024):
        self.name = name
        self._window: deque = deque(maxlen=max(int(window), 1))
        self.total_count = 0
        self.total_sum = 0.0

    def record(self, value: float) -> None:
        """Append one sample to the window and the all-time totals."""
        v = float(value)
        self._window.append(v)
        self.total_count += 1
        self.total_sum += v

    def last(self, n: int) -> list:
        """The newest ``n`` samples of the window, oldest first (fewer when
        the window holds fewer)."""
        tail = list(itertools.islice(reversed(self._window), max(int(n), 0)))
        tail.reverse()
        return tail

    def quantile(self, q: float) -> Optional[float]:
        """Linear-interpolated quantile over the current window."""
        if not self._window:
            return None
        xs = sorted(self._window)
        if len(xs) == 1:
            return xs[0]
        pos = q * (len(xs) - 1)
        lo = int(math.floor(pos))
        hi = min(lo + 1, len(xs) - 1)
        frac = pos - lo
        return xs[lo] * (1.0 - frac) + xs[hi] * frac

    def summary(self) -> dict:
        """count/mean/min/max/p50/p95/p99 of the current window."""
        xs = list(self._window)
        if not xs:
            return {"count": 0}
        return {
            "count": len(xs),
            "mean": sum(xs) / len(xs),
            "min": min(xs),
            "max": max(xs),
            "p50": self.quantile(0.50),
            "p95": self.quantile(0.95),
            "p99": self.quantile(0.99),
        }

    def reset(self) -> None:
        self._window.clear()


class MetricsRegistry:
    """Get-or-create home for every metric in a process.

    Thread-safe on creation (the async-checkpoint thread and the train loop
    may both touch it); individual updates are plain float ops and need no
    lock under the GIL.
    """

    def __init__(self, histogram_window: int = 1024):
        self._lock = threading.Lock()
        self._histogram_window = int(histogram_window)
        self._counters: dict[str, Counter] = {}
        self._gauges: dict[str, Gauge] = {}
        self._histograms: dict[str, Histogram] = {}

    # -- get-or-create -------------------------------------------------------
    def counter(self, name: str) -> Counter:
        with self._lock:
            if name not in self._counters:
                self._counters[name] = Counter(name)
            return self._counters[name]

    def gauge(self, name: str) -> Gauge:
        with self._lock:
            if name not in self._gauges:
                self._gauges[name] = Gauge(name)
            return self._gauges[name]

    def histogram(self, name: str, window: Optional[int] = None) -> Histogram:
        with self._lock:
            if name not in self._histograms:
                self._histograms[name] = Histogram(
                    name, window or self._histogram_window)
            return self._histograms[name]

    def set_default_window(self, window: int) -> None:
        """Default window for histograms created from now on (the shared
        registry outlives any one Observability config)."""
        with self._lock:
            self._histogram_window = max(int(window), 1)

    # -- convenience ---------------------------------------------------------
    def timer(self, name: str):
        """``with registry.timer("phase"): ...`` records seconds into the
        ``phase`` histogram and bumps the ``phase_seconds_total`` counter
        (the counter is what data-stall fractions integrate over)."""
        return _Timer(self, name)

    # -- snapshots -----------------------------------------------------------
    def snapshot(self) -> dict:
        """Flat, JSON-ready view: counters/gauges as scalars, histograms as
        their summary dicts."""
        out: dict[str, Any] = {}
        # the lock covers the dict iteration: counter()/histogram() insert
        # from the watchdog thread, and a resize mid-iteration raises
        with self._lock:
            for c in self._counters.values():
                out[c.name] = c.value
            for g in self._gauges.values():
                out[g.name] = g.value
            for h in self._histograms.values():
                out[h.name] = h.summary()
        return out

    def reset_window(self) -> None:
        """Clear histogram windows (counters and gauges persist)."""
        with self._lock:
            for h in self._histograms.values():
                h.reset()

    def reset(self) -> None:
        """Full reset — counters, gauges and histogram windows."""
        with self._lock:
            for c in self._counters.values():
                c.reset()
            for g in self._gauges.values():
                g.reset()
            for h in self._histograms.values():
                h.reset()
                h.total_count = 0
                h.total_sum = 0.0


class _Timer:
    __slots__ = ("_registry", "_name", "_t0")

    def __init__(self, registry: MetricsRegistry, name: str):
        self._registry = registry
        self._name = name

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._registry.histogram(self._name).record(dt)
        self._registry.counter(self._name + "_seconds_total").inc(dt)
        return False


# ---------------------------------------------------------------------------
# Default per-process registry (checkpoint.py and the engines share it)
# ---------------------------------------------------------------------------

_default_registry: Optional[MetricsRegistry] = None
_default_lock = threading.Lock()


def get_registry() -> MetricsRegistry:
    """The shared per-process registry (lazily created)."""
    global _default_registry
    with _default_lock:
        if _default_registry is None:
            _default_registry = MetricsRegistry()
        return _default_registry


# ---------------------------------------------------------------------------
# Derived metrics: throughput / EWMA / stall fraction / MFU
# ---------------------------------------------------------------------------

def mfu(tokens_per_sec: Optional[float], flops_per_token: Optional[float],
        peak_flops_per_chip: Optional[float], n_devices: int) -> Optional[float]:
    """Model FLOPs utilisation: achieved model FLOP/s over the fleet's peak.

    ``None`` when any input is unknown — on CPU ``peak_flops`` has no entry,
    and a non-LM module has no FLOPs-per-token estimate. Null, not 0: an
    unknown utilisation must never read as a measured-zero regression.
    """
    if not tokens_per_sec or not flops_per_token or not peak_flops_per_chip:
        return None
    return (tokens_per_sec * flops_per_token
            / (peak_flops_per_chip * max(n_devices, 1)))


class DerivedMetrics:
    """Per-logging-window derivation of throughput/MFU/stall signals.

    The engine feeds one ``update()`` per logging window with raw
    measurements; this layer owns the EWMA state and the stall-time
    bookkeeping so the engine stays free of metric arithmetic.
    """

    def __init__(self, flops_per_token: Optional[float] = None,
                 peak_flops_per_chip: Optional[float] = None,
                 n_devices: int = 1, ewma_alpha: float = 0.1):
        self.flops_per_token = flops_per_token
        self.peak_flops_per_chip = peak_flops_per_chip
        self.n_devices = max(int(n_devices), 1)
        self.ewma_alpha = float(ewma_alpha)
        self._ewma: Optional[float] = None
        self._last_stall_total = 0.0
        # per-rank arrival-skew EWMAs (gang mode): rank → seconds behind
        # the median arrival at collective rendezvous points
        self._skew: dict[int, float] = {}

    def update(self, step_time: float, global_batch_size: int,
               tokens_per_sample: Optional[int] = None,
               steps_in_window: int = 1,
               stall_seconds_total: float = 0.0) -> dict:
        """Derive one record's worth of metrics.

        ``step_time`` — mean seconds per optimizer step over the window;
        ``stall_seconds_total`` — a monotone counter of host-blocked seconds
        (data fetch + host-to-device transfer); the delta since the previous
        window, spread over the window's wall time, is the stall fraction.
        """
        step_time = max(float(step_time), 1e-12)
        a = self.ewma_alpha
        self._ewma = (step_time if self._ewma is None
                      else a * step_time + (1.0 - a) * self._ewma)

        samples_per_sec = global_batch_size / step_time
        tokens_per_sec = (samples_per_sec * tokens_per_sample
                          if tokens_per_sample else None)

        window_wall = step_time * max(int(steps_in_window), 1)
        stall_delta = max(stall_seconds_total - self._last_stall_total, 0.0)
        self._last_stall_total = stall_seconds_total
        data_stall_frac = min(stall_delta / max(window_wall, 1e-12), 1.0)

        return {
            "step_time": step_time,
            "step_time_ewma": self._ewma,
            "samples_per_sec": samples_per_sec,
            "tokens_per_sec": tokens_per_sec,
            "data_stall_frac": data_stall_frac,
            "mfu": mfu(tokens_per_sec, self.flops_per_token,
                       self.peak_flops_per_chip, self.n_devices),
        }

    # -- cross-rank skew (docs/observability.md "Multi-host") ---------------
    def update_arrivals(self, arrivals: dict) -> None:
        """Fold one collective rendezvous' arrival census into the rolling
        per-rank skew estimate.

        ``arrivals`` maps rank → publish wall-clock timestamp at one
        agreement (``resilience/coordination.py`` feeds these through the
        ``observability.gang`` arrival hook). Skew is the EWMA of each
        rank's offset from the *median* arrival: a persistently positive
        skew names a straggler while the run is still healthy, instead of
        the post-mortem census a 600 s ``CoordinationTimeout`` yields
        after the run is already dead.
        """
        if not arrivals or len(arrivals) < 2:
            return
        ts = sorted(float(t) for t in arrivals.values())
        mid = len(ts) // 2
        median = ts[mid] if len(ts) % 2 else (ts[mid - 1] + ts[mid]) / 2.0
        a = self.ewma_alpha if self.ewma_alpha > 0 else 1.0
        for rank, t in arrivals.items():
            skew = float(t) - median
            prev = self._skew.get(int(rank))
            self._skew[int(rank)] = (skew if prev is None
                                     else a * skew + (1.0 - a) * prev)

    def rank_skew(self) -> dict:
        """rank → rolling seconds behind (+) / ahead (−) of the median."""
        return dict(self._skew)

    def slowest_rank(self) -> Optional[int]:
        """The rank with the largest positive skew, or None before any
        arrival census has been observed."""
        if not self._skew:
            return None
        return max(self._skew, key=lambda r: self._skew[r])
