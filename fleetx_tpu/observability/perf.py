"""Automated trace decomposition + roofline MFU-gap attribution.

Mechanizes the hand-done "Step-time decomposition from the committed
trace" analysis in BENCHMARKS.md (ROADMAP item 3): given the
Chrome-trace/Perfetto JSON a ``jax.profiler`` window dumps (the artifact
committed as ``bench_artifacts/trace_gpt.tar.gz``), this module

- classifies every device XLA-op event into a small category taxonomy
  (matmul / flash kernel / dynamic-update-slice traffic / copy /
  collective per mesh axis / elementwise / rng), name-first then
  ``hlo_category`` — a fused matmul whose root is a
  ``dynamic-update-slice`` into a scan-stacked buffer is DUS traffic,
  exactly as the hand analysis counted it;
- aggregates per train step and per scan region: the layer scans show up
  as ``while`` ops, their trip count (= layers) is inferred from repeated
  per-iteration kernels, yielding the fwd/bwd ms-per-layer table
  BENCHMARKS.md derived by eye;
- scores the result against a roofline (``utils/hardware.roofline`` —
  calibrated matmul FLOP/s + HBM bytes/s) into an MFU-gap report naming
  the top-k gap contributors, each with the ms/step it costs and what
  would recover it.

Everything here is stdlib + the trace JSON — this module never imports
jax, so the offline CLI (``tools/trace_report.py``) runs on the committed
artifacts with no live backend, and the engine hook
(``ProfilerWindow.on_stop``) adds no device work.

The methodology follows "Scalable Training of Language Models using JAX
pjit and TPUv4" (arXiv:2204.06514): MFU as the tracked quantity, with
the gap to the roofline decomposed into attributable mechanisms; the
per-mesh-axis collective attribution anticipates the DCN slice axis the
MPMD work (arXiv:2412.14374) motivates (ROADMAP item 2).
"""

from __future__ import annotations

import gzip
import json
import os
import re
import tarfile
from typing import Any, Optional

__all__ = [
    "load_trace", "classify_event", "decompose", "mfu_gap", "analyze",
    "CATEGORIES",
]

#: event-category taxonomy (docs/performance.md): the classifier's output
#: values, in the order reports render them. Collectives carry a
#: ``collective:<axis>`` suffix when the mesh axis is attributable.
CATEGORIES = ("matmul", "flash", "fused_norm", "dus", "copy", "collective",
              "elementwise", "rng", "host_gap")

# name substrings that mark a Pallas/Mosaic attention kernel (the repo's
# flash fwd/dq/dkv custom calls are named attn._core_attn.*)
_FLASH_MARKERS = ("attn", "flash")
# the fused residual+LayerNorm kernels (ops/fused_norm.py) name their
# pallas_calls fused_norm_fwd / fused_norm_bwd — matched NAME-FIRST, before
# any hlo_category test, so the passes never fold back into `elementwise`
# (whose deletion is exactly what the kernel's A/B measures)
_FUSED_NORM_MARKER = "fused_norm"
_COLLECTIVE_MARKERS = ("all-reduce", "all-gather", "reduce-scatter",
                       "all-to-all", "collective-permute",
                       "collective-broadcast")
# hlo_category values that are data movement, not compute
_COPY_CATEGORIES = ("data formatting", "copy", "copy-start", "copy-done",
                    "async-start", "async-done")


# ---------------------------------------------------------------------------
# loading
# ---------------------------------------------------------------------------

def _read_json(data: bytes) -> dict:
    if data[:2] == b"\x1f\x8b":  # gzip magic
        data = gzip.decompress(data)
    return json.loads(data.decode("utf-8", errors="replace"))


def load_trace(source: Any) -> dict:
    """Resolve ``source`` to the Chrome-trace JSON dict.

    Accepts: an already-parsed dict; a ``.json`` / ``.json.gz`` file; a
    ``.tar.gz`` artifact like ``bench_artifacts/trace_gpt.tar.gz``; or a
    ``jax.profiler`` output DIRECTORY (the newest
    ``plugins/profile/*/**.trace.json.gz`` dump inside it wins).
    """
    if isinstance(source, dict):
        return source
    path = str(source)
    if os.path.isdir(path):
        hits = []
        for root, _dirs, files in os.walk(path):
            hits.extend(os.path.join(root, f) for f in files
                        if f.endswith(".trace.json.gz")
                        or f.endswith(".trace.json"))
        if not hits:
            raise FileNotFoundError(
                f"no *.trace.json[.gz] under {path} — was the profiler "
                f"window ever closed?")
        path = max(hits, key=os.path.getmtime)
    if tarfile.is_tarfile(path):
        with tarfile.open(path) as tar:
            members = [m for m in tar.getmembers()
                       if m.name.endswith(".trace.json.gz")
                       or m.name.endswith(".trace.json")]
            if not members:
                raise FileNotFoundError(
                    f"no *.trace.json[.gz] member in {path}")
            f = tar.extractfile(members[-1])
            assert f is not None
            return _read_json(f.read())
    with open(path, "rb") as f:
        return _read_json(f.read())


# ---------------------------------------------------------------------------
# classification
# ---------------------------------------------------------------------------

def _group_size(long_name: str) -> Optional[int]:
    """Size of the first replica group in an HLO ``long_name``, or None."""
    m = re.search(r"replica_groups=\{?\{([0-9, ]+)\}", long_name)
    if not m:
        m = re.search(r"replica_groups=\[\[([0-9, ]+)\]", long_name)
    if not m:
        return None
    return len([t for t in m.group(1).split(",") if t.strip()])


def classify_event(name: str, hlo_category: str = "",
                   long_name: str = "",
                   axis_sizes: Optional[dict] = None) -> str:
    """Category for one device XLA-op event.

    Name takes precedence over ``hlo_category``: XLA reports a fused
    matmul-into-stacked-buffer as ``convolution fusion``, but its cost is
    the ``dynamic-update-slice`` traffic the fusion is named after
    (BENCHMARKS.md counts those five fusions as the backward's DUS tax).
    Collectives map to ``collective:<axis>`` by matching the replica-group
    size in ``long_name`` against ``axis_sizes`` (mesh axis → degree);
    ambiguous or unmatched sizes stay plain ``collective``.
    """
    n = name.lower()
    cat = (hlo_category or "").lower()
    if any(m in n for m in _COLLECTIVE_MARKERS) or \
            any(m in cat for m in _COLLECTIVE_MARKERS):
        size = _group_size(long_name or "")
        if size and axis_sizes:
            axes = [a for a, d in axis_sizes.items() if int(d) == size]
            if len(axes) == 1:
                return f"collective:{axes[0]}"
        return "collective"
    if _FUSED_NORM_MARKER in n:
        return "fused_norm"
    if "dynamic-update-slice" in n or "dynamic-slice" in n or \
            cat == "dynamic-update-slice":
        return "dus"
    if cat == "custom-call" and any(m in n for m in _FLASH_MARKERS):
        return "flash"
    if "convolution" in cat or cat == "custom fusion" or " dot(" in long_name:
        return "matmul"
    if cat in _COPY_CATEGORIES:
        return "copy"
    if cat == "rng-bit-generator":
        return "rng"
    return "elementwise"


# ---------------------------------------------------------------------------
# timeline extraction
# ---------------------------------------------------------------------------

def _device_timeline(trace: dict) -> dict:
    """Steps / XLA-op events / name of the FIRST device process in a trace.

    Single-program SPMD means every device runs the same timeline; the
    first device's decomposition is the fleet's (per-device skew is the
    gang-observability layer's business, not the trace's).
    """
    events = trace.get("traceEvents") or []
    proc_names: dict[int, str] = {}
    thread_names: dict[tuple, str] = {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            proc_names[e["pid"]] = e.get("args", {}).get("name", "")
        elif e.get("name") == "thread_name":
            thread_names[(e["pid"], e.get("tid"))] = \
                e.get("args", {}).get("name", "")
    device_pids = sorted(p for p, n in proc_names.items()
                         if n.startswith("/device:"))
    if not device_pids:
        raise ValueError("trace has no '/device:*' process — not a "
                         "jax.profiler device trace")
    pid = device_pids[0]
    steps, ops = [], []
    for e in events:
        if e.get("ph") != "X" or e.get("pid") != pid:
            continue
        tname = thread_names.get((pid, e.get("tid")), "")
        if tname == "Steps":
            steps.append(e)
        elif tname == "XLA Ops":
            ops.append(e)
    steps.sort(key=lambda e: e["ts"])
    ops.sort(key=lambda e: e["ts"])
    return {"pid": pid, "device": proc_names[pid], "steps": steps,
            "ops": ops, "n_devices": len(device_pids)}


def _covered_us(intervals: list) -> float:
    """Total µs covered by the union of (start, end) intervals."""
    total, cur_start, cur_end = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_end is None or s > cur_end:
            if cur_end is not None:
                total += cur_end - cur_start
            cur_start, cur_end = s, e
        else:
            cur_end = max(cur_end, e)
    if cur_end is not None:
        total += cur_end - cur_start
    return total


# ---------------------------------------------------------------------------
# decomposition
# ---------------------------------------------------------------------------

def decompose(trace: Any, num_layers: Optional[int] = None,
              axis_sizes: Optional[dict] = None) -> dict:
    """Decompose a device trace into per-category / per-scan-region time.

    Returns a JSON-ready dict: mean ``step_ms``, per-category ms/step and
    HBM bytes/step, and a ``phases`` table (``fwd_scan`` / ``bwd_scan`` /
    ``outside``) with per-layer times for the scan regions — the
    BENCHMARKS.md decomposition table, reproduced mechanically.
    ``num_layers`` overrides the inferred scan trip count (needed only
    for traces whose scans carry no repeated per-iteration kernels).
    """
    tl = _device_timeline(load_trace(trace))
    steps, ops = tl["steps"], tl["ops"]
    if not steps:
        # fall back to treating the whole op timeline as one step
        if not ops:
            raise ValueError("trace has no device step or op events")
        t0 = min(e["ts"] for e in ops)
        t1 = max(e["ts"] + e.get("dur", 0.0) for e in ops)
        steps = [{"name": "all", "ts": t0, "dur": t1 - t0}]
    n_steps = len(steps)

    whiles = [e for e in ops
              if (e.get("args") or {}).get("hlo_category") == "while"]
    leaves = [e for e in ops
              if (e.get("args") or {}).get("hlo_category") != "while"]

    # label scan regions per step: first while = forward scan, the longest
    # of the rest = backward (it carries ~2x the FLOPs); anything else
    # (unrolled tails, pipeline sub-scans) aggregates as extra_scan
    regions: list[tuple[float, float, str]] = []
    for s in steps:
        s0, s1 = s["ts"], s["ts"] + s["dur"]
        inside = sorted((w for w in whiles if s0 <= w["ts"] < s1),
                        key=lambda w: w["ts"])
        if not inside:
            continue
        rest = inside[1:]
        bwd = max(rest, key=lambda w: w["dur"]) if rest else None
        for w in inside:
            label = ("fwd_scan" if w is inside[0]
                     else "bwd_scan" if w is bwd else "extra_scan")
            regions.append((w["ts"], w["ts"] + w["dur"], label))
    regions.sort()

    def region_of(e) -> str:
        ts = e["ts"]
        for r0, r1, label in regions:
            if r0 <= ts < r1:
                return label
        return "outside"

    cat_ms: dict[str, float] = {}
    cat_bytes: dict[str, float] = {}
    phase_cat_ms: dict[str, dict[str, float]] = {}
    phase_flash_names: dict[str, dict[str, int]] = {}
    intervals = []
    for e in leaves:
        args = e.get("args") or {}
        cat = classify_event(e.get("name", ""), args.get("hlo_category", ""),
                             args.get("long_name", ""), axis_sizes)
        dur_ms = e.get("dur", 0.0) / 1000.0
        cat_ms[cat] = cat_ms.get(cat, 0.0) + dur_ms
        try:
            cat_bytes[cat] = cat_bytes.get(cat, 0.0) + \
                float(args.get("bytes_accessed") or 0)
        except (TypeError, ValueError):
            pass
        ph = region_of(e)
        phase_cat_ms.setdefault(ph, {})
        phase_cat_ms[ph][cat] = phase_cat_ms[ph].get(cat, 0.0) + dur_ms
        if cat == "flash":
            counts = phase_flash_names.setdefault(ph, {})
            counts[e["name"]] = counts.get(e["name"], 0) + 1
        intervals.append((e["ts"], e["ts"] + e.get("dur", 0.0)))

    step_ms = sum(s["dur"] for s in steps) / n_steps / 1000.0
    covered_ms = _covered_us(intervals) / 1000.0 / n_steps
    host_gap = max(step_ms - covered_ms, 0.0)

    # per-region trip count (= layers): the max per-step repetition of any
    # single op name inside the region — robust to unroll (each unrolled
    # copy is a distinct op name that still repeats trip-count times)
    region_ms: dict[str, float] = {}
    for r0, r1, label in regions:
        region_ms[label] = region_ms.get(label, 0.0) + (r1 - r0) / 1000.0
    name_counts: dict[str, dict[str, int]] = {}
    for e in leaves:
        ph = region_of(e)
        if ph == "outside":
            continue
        d = name_counts.setdefault(ph, {})
        d[e["name"]] = d.get(e["name"], 0) + 1

    phases: dict[str, dict] = {}
    for label in sorted(set(list(region_ms) + list(phase_cat_ms))):
        entry: dict[str, Any] = {
            "ms_per_step": round(
                (region_ms.get(label, 0.0)
                 if label != "outside" else
                 sum(phase_cat_ms.get("outside", {}).values())) / n_steps, 4),
            "categories_ms_per_step": {
                k: round(v / n_steps, 4)
                for k, v in sorted(phase_cat_ms.get(label, {}).items(),
                                   key=lambda kv: -kv[1])},
        }
        if label != "outside":
            counts = name_counts.get(label, {})
            trips = (max(counts.values()) // n_steps) if counts else 0
            layers = int(num_layers or trips)
            entry["layers"] = layers
            if layers:
                entry["ms_per_layer"] = round(
                    entry["ms_per_step"] / layers, 4)
            flash_n = sum(phase_flash_names.get(label, {}).values())
            if layers and flash_n:
                entry["flash_passes_per_layer"] = round(
                    flash_n / n_steps / layers, 2)
        phases[label] = entry

    return {
        "device": tl["device"],
        "n_devices": tl["n_devices"],
        "n_steps": n_steps,
        "step_ms": round(step_ms, 4),
        "categories_ms_per_step": {
            k: round(v / n_steps, 4)
            for k, v in sorted(cat_ms.items(), key=lambda kv: -kv[1])},
        "categories_bytes_per_step": {
            k: int(v / n_steps) for k, v in cat_bytes.items()},
        "host_gap_ms_per_step": round(host_gap, 4),
        "phases": phases,
    }


# ---------------------------------------------------------------------------
# roofline scoring
# ---------------------------------------------------------------------------

def _bwd_flash_stats(decomp: dict) -> tuple[float, float]:
    """(backward flash passes/layer, backward flash ms/step)."""
    bwd = decomp.get("phases", {}).get("bwd_scan", {})
    return (float(bwd.get("flash_passes_per_layer") or 0.0),
            float(bwd.get("categories_ms_per_step", {}).get("flash", 0.0)))


def mfu_gap(decomp: dict, flops_per_step: Optional[float] = None,
            roofline: Optional[dict] = None, top_k: int = 5) -> dict:
    """Score a decomposition against the roofline → top-k gap report.

    ``flops_per_step`` is the model FLOPs of the batch the TRACE'S
    devices process per step (per-host on multi-host runs — the trace
    only carries local devices); ``ideal_step_ms`` is then
    ``flops_per_step / (matmul_flops × n_devices)``, the compute
    roofline floor of the decomposed single-device timeline. The gap to
    the measured device step time is attributed to contributors that
    sum to it:

    - ``flash_recompute`` — backward flash-kernel passes beyond the dq/dkv
      pair (a 3rd pass = the remat policy replaying the forward kernel to
      regenerate unsaved residuals — the BENCHMARKS.md finding);
    - ``dus_traffic`` / ``copy_traffic`` — scan-stacked-buffer DUS fusions
      and copies/formatting: HBM bandwidth, not FLOPs, with the
      bytes-at-calibrated-bandwidth floor reported alongside;
    - ``collective[:axis]`` — per-mesh-axis collective time;
    - ``elementwise`` / ``rng`` — non-matmul compute;
    - ``matmul_inefficiency`` — math time above the roofline floor;
    - ``host_gap`` — device idle inside the step span.

    With ``flops_per_step`` or ``roofline`` unknown the report still
    ranks the raw category costs (ideal/gap/MFU fields null).
    """
    rl = roofline or {}
    cats = dict(decomp.get("categories_ms_per_step") or {})
    bytes_per_step = decomp.get("categories_bytes_per_step") or {}
    step_ms = float(decomp["step_ms"])
    peak = rl.get("peak_flops")
    matmul_peak = rl.get("matmul_flops") or peak
    hbm_bw = rl.get("hbm_bytes_per_s")
    # the decomposed timeline is ONE device's; flops_per_step covers the
    # whole batch the trace's devices share, so both the ideal time and
    # the MFU denominator divide by the device count — without this the
    # gap report is only right on a single chip
    n_dev = max(int(decomp.get("n_devices") or 1), 1)

    passes, bwd_flash_ms = _bwd_flash_stats(decomp)
    recompute_ms = 0.0
    if passes > 2 and bwd_flash_ms:
        recompute_ms = bwd_flash_ms * (passes - 2.0) / passes

    ideal_ms = mfu_measured = gap_ms = None
    if flops_per_step and matmul_peak:
        ideal_ms = flops_per_step / (matmul_peak * n_dev) * 1000.0
        gap_ms = max(step_ms - ideal_ms, 0.0)
    if flops_per_step and peak:
        mfu_measured = flops_per_step / (step_ms / 1000.0) / \
            (peak * n_dev)

    def bw_floor(cat: str) -> Optional[float]:
        if not hbm_bw or cat not in bytes_per_step:
            return None
        return round(bytes_per_step[cat] / hbm_bw * 1000.0, 4)

    contributors = []

    def add(name: str, ms: float, detail: str, **extra) -> None:
        if ms <= 0.0:
            return
        contributors.append({"name": name, "ms_per_step": round(ms, 4),
                             "detail": detail, **extra})

    add("flash_recompute", recompute_ms,
        f"{passes:.0f} backward flash passes/layer where dq+dkv need 2 — "
        "the remat policy replays the forward kernel; save the (out, lse) "
        "residuals to drop it")
    add("dus_traffic", cats.get("dus", 0.0),
        "dynamic-(update-)slice fusions moving scan-stacked residuals and "
        "accumulators — HBM bandwidth; levers: scan_unroll, "
        "remat_save_dtype, fused backward kernels",
        hbm_floor_ms=bw_floor("dus"))
    add("copy_traffic", cats.get("copy", 0.0),
        "copies / data formatting / async transfers",
        hbm_floor_ms=bw_floor("copy"))
    for cat in sorted(cats):
        if cat == "collective" or cat.startswith("collective:"):
            axis = cat.partition(":")[2] or "unattributed"
            add(cat, cats[cat], f"collective time on mesh axis '{axis}'")
    add("fused_norm", cats.get("fused_norm", 0.0),
        "fused residual+LayerNorm+cast Pallas passes (ops/fused_norm.py) — "
        "one HBM pass replacing the elementwise round-trips around each "
        "norm", hbm_floor_ms=bw_floor("fused_norm"))
    add("elementwise", cats.get("elementwise", 0.0),
        "non-matmul compute (norms, softmax pieces, optimizer math)",
        hbm_floor_ms=bw_floor("elementwise"))
    add("rng", cats.get("rng", 0.0), "dropout-mask generation")
    math_ms = cats.get("matmul", 0.0) + cats.get("flash", 0.0) - recompute_ms
    if ideal_ms is not None:
        add("matmul_inefficiency", math_ms - ideal_ms,
            "matmul+flash time above the calibrated roofline floor")
    add("host_gap", float(decomp.get("host_gap_ms_per_step") or 0.0),
        "device idle inside the step span (dispatch / input stalls)")

    contributors.sort(key=lambda c: -c["ms_per_step"])
    if gap_ms:
        for c in contributors:
            c["share_of_gap"] = round(c["ms_per_step"] / gap_ms, 4)
    accounted = round(sum(c["ms_per_step"] for c in contributors), 4)
    return {
        "flops_per_step": flops_per_step,
        "peak_flops": peak,
        "matmul_flops": matmul_peak,
        "hbm_bytes_per_s": hbm_bw,
        "measured_step_ms": round(step_ms, 4),
        "ideal_step_ms": None if ideal_ms is None else round(ideal_ms, 4),
        "gap_ms": None if gap_ms is None else round(gap_ms, 4),
        "mfu": None if mfu_measured is None else round(mfu_measured, 4),
        "accounted_ms": accounted,
        "contributors": contributors[:max(int(top_k), 1)],
    }


def analyze(source: Any, flops_per_step: Optional[float] = None,
            roofline: Optional[dict] = None, num_layers: Optional[int] = None,
            axis_sizes: Optional[dict] = None, top_k: int = 5) -> dict:
    """One-call pipeline: load → decompose → roofline-score.

    The full report dict: the ``decompose`` keys plus ``mfu_gap``. This is
    what ``tools/trace_report.py`` prints and what the engine emits into
    the perf metrics stream after every closed profiler window.
    """
    decomp = decompose(source, num_layers=num_layers, axis_sizes=axis_sizes)
    decomp["mfu_gap"] = mfu_gap(decomp, flops_per_step=flops_per_step,
                                roofline=roofline, top_k=top_k)
    return decomp


def summary(report: dict) -> dict:
    """Slim, record-friendly view of an ``analyze`` report (what rides in
    the metrics stream, bench JSON and the flight ring)."""
    phases = report.get("phases", {})
    gap = report.get("mfu_gap", {})
    out = {
        "step_ms": report.get("step_ms"),
        "host_gap_ms": report.get("host_gap_ms_per_step"),
        "mfu": gap.get("mfu"),
        "gap_ms": gap.get("gap_ms"),
        "top_contributors": [
            {"name": c["name"], "ms_per_step": c["ms_per_step"]}
            for c in gap.get("contributors", [])[:3]],
    }
    for label in ("fwd_scan", "bwd_scan"):
        ph = phases.get(label)
        if ph and ph.get("ms_per_layer") is not None:
            out[f"{label}_ms_per_layer"] = ph["ms_per_layer"]
    # backward flash kernel passes per layer: the fused-backward A/B's
    # mechanized evidence (1 fused vs 3 split; bench.py promotes it to
    # the flash_bwd_passes row tools/perf_gate.py exact-matches)
    bwd = phases.get("bwd_scan") or {}
    if bwd.get("flash_passes_per_layer") is not None:
        out["bwd_flash_passes_per_layer"] = bwd["flash_passes_per_layer"]
    # fused residual+norm flag (0/1 int — perf_gate's numeric schema
    # rejects bools): did any fused_norm pallas pass land on the device?
    cats = report.get("categories_ms_per_step") or {}
    out["norm_fused"] = 1 if cats.get("fused_norm") else 0
    return out
