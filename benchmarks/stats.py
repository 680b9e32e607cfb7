"""Metric arithmetic on timestamps: the yardstick, kept with the benchmark.

All times are seconds on one host clock (``time.monotonic``, the clock the
serving engine stamps its requests with).
"""

from __future__ import annotations

import math
from typing import Iterable, Optional


def percentile(values: Iterable[float], p: float) -> Optional[float]:
    """Linear-interpolated ``p``-th percentile of all ``values``."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * p / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def mean(values: Iterable[float]) -> Optional[float]:
    """Arithmetic mean, or None of nothing."""
    xs = list(values)
    return sum(xs) / len(xs) if xs else None


def in_window(t: float, t0: float, t1: float) -> bool:
    return t0 < t <= t1


def window_rate(count_at_open: float, count_at_close: float,
                t0: float, t1: float) -> float:
    """A rate over all the work and all the time of the window."""
    return (count_at_close - count_at_open) / (t1 - t0)


class TokenLog:
    """Every token of every request, by the time it was emitted.

    ``note(request_id, times)`` appends the emit times of a request's new
    tokens; gaps and first-token latencies are read per window afterwards,
    so a gap counts where it ends and a first token where it falls.
    """

    def __init__(self):
        self.times: dict = {}      # request id -> [emit times]
        self.start: dict = {}      # request id -> the time latency runs from

    def open(self, rid: str, start_s: float) -> None:
        """Start logging request ``rid``, whose latency runs from ``start_s``."""
        self.times[rid] = []
        self.start[rid] = start_s

    def note(self, rid: str, times: list) -> None:
        self.times[rid].extend(times)

    def tokens_in(self, t0: float, t1: float) -> int:
        return sum(in_window(t, t0, t1)
                   for ts in self.times.values() for t in ts)

    def gaps_in(self, t0: float, t1: float) -> list:
        """Gaps between consecutive tokens of one request that END in the
        window."""
        return [b - a for ts in self.times.values()
                for a, b in zip(ts, ts[1:]) if in_window(b, t0, t1)]

    def first_token_latencies_in(self, t0: float, t1: float) -> list:
        """First token minus start (submission in a closed loop, the due
        time in an open one), for first tokens that fall in the window."""
        return [ts[0] - self.start[rid] for rid, ts in self.times.items()
                if ts and in_window(ts[0], t0, t1)]


def generator_lateness(due: list, sent: list) -> list:
    """How late an open-loop generator sent each request (seconds >= 0)."""
    return [max(s - d, 0.0) for d, s in zip(due, sent)]


def steps_in_window(step_ends: list, t0: float, seconds: float) -> tuple:
    """Training: the steps that end inside ``(t0, t0 + seconds]`` and the
    time of the last of them — ``(n_steps, t_last)``; the rate is taken
    over their own wall time, device drained at both edges."""
    inside = [t for t in step_ends if in_window(t, t0, t0 + seconds)]
    return (len(inside), inside[-1]) if inside else (0, t0)
