"""Host spans recorded from the benchmark's own files, around the calls
into each layer. Kept in memory; with tracing on, each span is also a
``jax.profiler.TraceAnnotation`` (named ``bench:<name>``) so that it sits
on the device trace's clock and idle gaps can be attributed to it."""

from __future__ import annotations

import contextlib
import time


class Spans:
    """Named host spans of one run, in memory."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.records: list = []      # (name, start_s, end_s) on time.monotonic
        self._open: dict = {}

    @contextlib.contextmanager
    def span(self, name: str):
        """``with spans.span(name):`` around one call into a layer."""
        self.begin(name)
        try:
            yield
        finally:
            self.end(name)

    def begin(self, name: str) -> None:
        """Open ``name`` (for spans that a ``with`` block cannot bracket)."""
        ann = None
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation("bench:" + name)
            ann.__enter__()
        self._open[name] = (time.monotonic(), ann)

    def end(self, name: str) -> None:
        """Close ``name`` and record it."""
        if name not in self._open:
            return
        start, ann = self._open.pop(name)
        if ann is not None:
            ann.__exit__(None, None, None)
        self.records.append((name, start, time.monotonic()))
