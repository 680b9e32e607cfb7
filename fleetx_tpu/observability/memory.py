"""HBM attribution: measured device memory vs the planner's prediction.

The ``auto_layout`` memory model (``parallel/auto_layout.py``) decides
offload and ZeRO-stage escalation from a first-order byte estimate that —
until this module — was never checked against what the runtime actually
allocates. Here the engine samples ``device.memory_stats()`` at phase
boundaries (post-compile, steady-state step, checkpoint save, eval),
emits peak/live HBM gauges, and computes

    ``hbm_model_error`` = (measured peak − predicted) / predicted

so every profiled run scores the model that plans its layout. Backends
without memory stats (the CPU) degrade gracefully: sampling
returns ``None`` and records carry an explicit ``hbm_stats:
"unavailable"`` marker instead of a fake zero — an unknown peak must
never read as a measured regression (same stance as null MFU).
"""

from __future__ import annotations

from typing import Callable, Optional

__all__ = ["sample_memory_stats", "device_placement", "MemoryMonitor"]

#: normalized stat keys → the PJRT ``memory_stats()`` fields they read
_STAT_KEYS = {
    "bytes_in_use": "bytes_in_use",
    "peak_bytes_in_use": "peak_bytes_in_use",
    # the compiled programs' temporaries: the TPU runtime reserves them
    # apart from the buffers "in use" (a 2 GiB-temp program on the v5e
    # moved this by 2 GiB and peak_bytes_in_use by nothing, PERF.md)
    "peak_bytes_reserved": "peak_bytes_reserved",
    "bytes_limit": "bytes_limit",
}


def sample_memory_stats(device=None) -> Optional[dict]:
    """Normalized memory stats for a device, or None when unsupported.

    ``{"bytes_in_use", "peak_bytes_in_use", "peak_bytes_reserved",
    "bytes_limit"}`` (absent fields omitted). ``None`` covers every unsupported shape: CPU returns
    None from ``memory_stats()``, some plugins raise, some return a dict
    with none of the known keys.
    """
    if device is None:
        import jax

        devices = jax.local_devices()
        if not devices:
            return None
        device = devices[0]
    try:
        raw = device.memory_stats()
    except Exception:  # noqa: BLE001 — backends without memory_stats
        return None
    if not raw:
        return None
    out = {norm: int(raw[key]) for norm, key in _STAT_KEYS.items()
           if key in raw}
    return out or None


def device_placement(trees: dict) -> list[dict]:
    """Where a run's state lives: for each local device, the bytes of every
    named pytree in ``trees`` resident on it (summed over the device's
    shards) and the peaks the backend reports (None where it does not):
    buffers in use, and the programs' temporaries reserved beside them.
    One log line of this is the evidence that a layout spread the state
    the way its YAML asked."""
    import jax

    report = []
    for dev in jax.local_devices():
        row = {"device": dev.id}
        for name, tree in trees.items():
            row[f"{name}_bytes"] = sum(
                shard.data.nbytes
                for leaf in jax.tree.leaves(tree) if hasattr(
                    leaf, "addressable_shards")
                for shard in leaf.addressable_shards if shard.device == dev)
        stats = sample_memory_stats(dev) or {}
        row["peak_bytes"] = stats.get("peak_bytes_in_use")
        row["peak_reserved_bytes"] = stats.get("peak_bytes_reserved")
        report.append(row)
    return report


class MemoryMonitor:
    """Phase-boundary HBM sampler + model-error scorer for one engine.

    ``sample(phase)`` is cheap (one host call, no device work) and never
    raises; gauges land in the shared registry (``hbm_bytes_in_use``,
    ``hbm_peak_bytes``, ``hbm_model_error``) and per-phase peaks are kept
    for the report/record surface (``snapshot()``). ``predicted_bytes``
    is the ``auto_layout.predicted_step_bytes`` figure for the active
    config; without it (non-GPT modules) the error stays None.
    """

    def __init__(self, registry=None, predicted_bytes: Optional[float] = None,
                 stats_fn: Optional[Callable[[], Optional[dict]]] = None):
        self.registry = registry
        self.predicted_bytes = (float(predicted_bytes)
                                if predicted_bytes else None)
        # injectable for tests and for backends where the interesting
        # device is not local_devices()[0]
        self._stats_fn = stats_fn or sample_memory_stats
        self.available: Optional[bool] = None  # unknown until first sample
        self.phases: dict[str, dict] = {}
        self.peak_bytes: Optional[int] = None

    def sample(self, phase: str) -> Optional[dict]:
        """Record one phase-boundary sample; returns it (or None)."""
        try:
            stats = self._stats_fn()
        except Exception:  # noqa: BLE001 — sampling must never kill a run
            stats = None
        if stats is None:
            # remember unavailability only if nothing ever succeeded: one
            # flaky read must not demote a backend that does report
            if self.available is None:
                self.available = False
            return None
        self.available = True
        self.phases[phase] = dict(stats)
        peak = stats.get("peak_bytes_in_use", stats.get("bytes_in_use"))
        if peak is not None:
            self.peak_bytes = max(self.peak_bytes or 0, int(peak))
        if self.registry is not None:
            if stats.get("bytes_in_use") is not None:
                self.registry.gauge("hbm_bytes_in_use").set(
                    stats["bytes_in_use"])
            if self.peak_bytes is not None:
                self.registry.gauge("hbm_peak_bytes").set(self.peak_bytes)
                self.registry.gauge(f"hbm_peak_bytes.{phase}").set(
                    int(peak) if peak is not None else self.peak_bytes)
            err = self.model_error()
            if err is not None:
                self.registry.gauge("hbm_model_error").set(err)
        return stats

    def model_error(self) -> Optional[float]:
        """(measured peak − predicted) / predicted, or None.

        Positive = the planner UNDER-estimated (the dangerous direction:
        a layout it approved can OOM); negative = headroom it left on the
        table. None whenever either side is unknown.
        """
        if not self.predicted_bytes or self.peak_bytes is None:
            return None
        return (self.peak_bytes - self.predicted_bytes) / \
            self.predicted_bytes

    def record_keys(self) -> dict:
        """The HBM keys one step record carries (schema-typed).

        ``hbm_stats`` is the explicit availability marker: ``"ok"`` when
        the backend reports, ``"unavailable"`` when it never has —
        downstream tooling can distinguish "no regression" from "nothing
        measured" without guessing from nulls.
        """
        if not self.available:
            return {"hbm_stats": "unavailable", "hbm_peak_bytes": None,
                    "hbm_model_error": None}
        err = self.model_error()
        return {"hbm_stats": "ok", "hbm_peak_bytes": self.peak_bytes,
                "hbm_model_error": None if err is None else round(err, 4)}

    def snapshot(self) -> dict:
        """Full JSON-ready view: availability, per-phase samples, peak,
        prediction and error."""
        return {
            "available": bool(self.available),
            "peak_bytes": self.peak_bytes,
            "predicted_bytes": (None if self.predicted_bytes is None
                                else int(self.predicted_bytes)),
            "model_error": self.model_error(),
            "phases": {k: dict(v) for k, v in self.phases.items()},
        }
