"""Perf regression gate: fresh bench JSON vs committed baselines.

Usage::

    python tools/perf_gate.py fresh.json                       # auto-match
    python tools/perf_gate.py fresh.json --baseline BENCH_SELF.json:gpt
    python tools/perf_gate.py --schema-only                    # CPU CI mode
    python tools/perf_gate.py                                  # = schema-only

Compares the metrics ``bench.py`` emits against a committed
``BENCH_SELF.json`` entry with per-metric, noise-aware tolerance bands
(``GATE_METRICS``): direction-aware (tokens/s regress DOWN, step time
regresses UP), relative bands sized to the observed capture-to-capture
jitter (the committed ``gpt`` vs ``gpt_trace`` pair differs ~1%; the
default 5% band is 5× that), and absolute floors so sub-millisecond span
means aren't failed on scheduler noise. Prints a verdict table and exits
non-zero on any regression — the bench pipeline's analogue of
``tools/lint.py``.

``--schema-only`` (and the no-argument form) is the repo-gate mode for
hosts with no fresh chip numbers (CPU CI): it validates the baseline
file's shape and self-checks the gate logic — an identical copy must
PASS, a synthetic 10% tokens/s regression must FAIL — so the gate itself
is regression-tested on every run. Exit codes follow ``tools/lint.py``:
0 clean, 1 regression (or self-check failure), 2 usage error.

Updating baselines: a baseline is a chip run — never hand-edit a number
to make the gate pass (docs/performance.md "Gate thresholds").
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BASELINE = os.path.join(REPO, "BENCH_SELF.json")

#: metric → (direction, relative tolerance, absolute floor).
#: direction "higher" = larger is better (regression when fresh drops
#: below base×(1−tol)); "lower" = smaller is better; "exact" = ANY change
#: is a regression (structural counts like kernel passes — a half-pass
#: drift means the compiled program changed shape, not that it got
#: noisy). The absolute floor is in the metric's own unit and wins for
#: tiny baselines where a relative band is all jitter.
GATE_METRICS = {
    "value": ("higher", 0.05, 0.0),            # tokens/s (the headline)
    "mfu": ("higher", 0.05, 0.0),
    "step_time_s": ("lower", 0.05, 0.0),
    "fit_step_time_s": ("lower", 0.08, 0.0),
    "data_stall_frac": ("lower", 0.0, 0.05),   # abs band: baseline ~0
    "hbm_peak_bytes": ("lower", 0.10, 0.0),
    "hbm_model_error": ("lower", 0.0, 0.10),   # abs: it's already relative
    # fused-backward evidence (docs/bandwidth_levers.md): the backward
    # scan's per-layer time (same band as the decomposition row it
    # mirrors) and the backward flash kernel pass count — 1 fused vs 3
    # split, exact-matched. Both skip when absent (pre-PR-13 baselines).
    "perf_bwd_ms_per_layer": ("lower", 0.10, 0.05),
    "flash_bwd_passes": ("exact", 0.0, 0.0),
    # fused-norm + overlapped-update evidence (docs/bandwidth_levers.md):
    # the elementwise trace line the fused kernel deletes regresses UP
    # (its time re-appearing means the fusion stopped dispatching or the
    # optimizer chain grew new pointwise passes), and the two 0/1 path
    # flags exact-match — a silent flip to the fallback is a compiled-
    # program change, not noise. All skip when absent (pre-PR-20
    # baselines).
    "perf_elementwise_ms": ("lower", 0.10, 0.05),
    "norm_fused": ("exact", 0.0, 0.0),
    "update_overlapped": ("exact", 0.0, 0.0),
}
#: per-phase span means are noisier than the headline (host scheduling):
#: wide relative band + a 0.5 ms absolute floor
SPAN_TOL = ("lower", 0.25, 0.5)
#: decomposition per-layer times (present when the capture carried a
#: profiler trace — docs/performance.md)
DECOMP_METRICS = {
    "decomposition.bwd_scan_ms_per_layer": ("lower", 0.10, 0.05),
    "decomposition.fwd_scan_ms_per_layer": ("lower", 0.10, 0.05),
    "decomposition.gap_ms": ("lower", 0.15, 1.0),
}
#: fine-tune micro-bench rows (bench.py "finetune" phase, docs/finetune.md):
#: the adapter step regresses UP with the usual noise-aware band;
#: trainable_params_frac and the adapter payload bytes are STRUCTURAL —
#: the frac exact-matches (it is a deterministic ratio of the config, any
#: change means the mask or the targets moved) and the bytes carry a 4 KiB
#: absolute floor over npz/zip jitter. All skip when absent (baselines
#: predating the finetune subsystem).
FINETUNE_METRICS = {
    "finetune.adapter_step_time_s": ("lower", 0.25, 0.01),
    "finetune.trainable_params_frac": ("exact", 0.0, 0.0),
    "finetune.adapter_ckpt_bytes": ("lower", 0.0, 4096.0),
}
#: serving-bench SLOs (tools/serve.py --bench, docs/serving.md): decode
#: throughput regresses DOWN, tail latencies UP. Bands are wider than the
#: training ones (a Poisson stream adds arrival jitter on top of host
#: scheduling) with absolute floors so millisecond-scale quantiles aren't
#: failed on scheduler noise. Baselines without a serving entry skip —
#: same stance as the pre-PR-10 decomposition metrics.
SERVING_METRICS = {
    "serving.tokens_per_s": ("higher", 0.15, 0.0),
    "serving.ttft_p50_s": ("lower", 0.25, 0.005),
    "serving.ttft_p99_s": ("lower", 0.25, 0.010),
    "serving.itl_p50_s": ("lower", 0.25, 0.002),
    "serving.itl_p99_s": ("lower", 0.25, 0.005),
    "serving.refused": ("lower", 0.0, 0.5),  # abs: any new refusal fails
    # fleet-economics rows (PR 16): completions per chip regress DOWN,
    # page occupancy regressing DOWN means the batcher stopped packing the
    # KV pool (with an absolute floor over tiny-bench noise), and SLO
    # attainment carries a pure 2-point absolute band — a 0.99 → 0.96
    # drop is a breached objective, not jitter. All skip-if-absent.
    "serving.requests_per_chip": ("higher", 0.15, 0.0),
    "serving.page_occupancy": ("higher", 0.15, 0.05),
    "serving.slo_attainment": ("higher", 0.0, 0.02),
    # lazy-lifecycle rows (PR 18): MEAN occupancy over worked steps is
    # the production-occupancy headline — lazy admission exists to raise
    # it, so it regresses DOWN (absolute floor over tiny-bench noise);
    # preemption_rate (swap-outs per completion) regresses UP on a pure
    # absolute band — a modest rate is healthy back-pressure, but a jump
    # of 0.25 preemptions/request means admission got too greedy for the
    # pool and decode is thrashing
    "serving.page_occupancy_mean": ("higher", 0.15, 0.05),
    "serving.preemption_rate": ("lower", 0.0, 0.25),
    # fault-tolerance rows (PR 19, docs/serving.md "Fault tolerance"):
    # pure absolute bands — counts, not rates, on the fixed-size bench.
    # A handful of deadline sheds is admission doing its job under the
    # bimodal burst, but +2 over baseline means the projection math or
    # the shed path regressed; hedges only fire on genuine stragglers so
    # a +3 jump means the hedge timer got trigger-happy (each hedge
    # burns a duplicate decode); breaker opens on the in-process bench
    # (no real fleet) should stay at 0 — any opening means the counters
    # wired into the bench path are misfiring. All skip-if-absent.
    "serving.deadline_sheds": ("lower", 0.0, 2.0),
    "serving.hedges_total": ("lower", 0.0, 3.0),
    "serving.breaker_opens": ("lower", 0.0, 0.5),
}


def _get_path(d: dict, dotted: str):
    """Nested lookup by dotted path, None when any hop is absent."""
    node = d
    for part in dotted.split("."):
        if not isinstance(node, dict) or part not in node:
            return None
        node = node[part]
    return node


def _numeric(v):
    return v if isinstance(v, (int, float)) and not isinstance(v, bool) \
        else None


def compare(fresh: dict, base: dict,
            overrides: dict | None = None) -> list[dict]:
    """Row per gate metric present in BOTH dicts → verdict table rows.

    A metric missing from either side is reported as ``skip`` (pre-PR-10
    baselines carry no HBM/decomposition keys — absence is not a
    regression), never silently dropped from the table.
    """
    specs = dict(GATE_METRICS)
    specs.update(DECOMP_METRICS)
    specs.update(FINETUNE_METRICS)
    specs.update(SERVING_METRICS)
    for key in sorted(set(list((base.get("span_means_ms") or {}))
                          + list((fresh.get("span_means_ms") or {})))):
        specs[f"span_means_ms.{key}"] = SPAN_TOL
    specs.update(overrides or {})

    rows = []
    for metric, (direction, rel, floor) in specs.items():
        b, f = _numeric(_get_path(base, metric)), \
            _numeric(_get_path(fresh, metric))
        if b is None or f is None:
            rows.append({"metric": metric, "base": b, "fresh": f,
                         "verdict": "skip"})
            continue
        band = max(abs(b) * rel, floor)
        delta = f - b
        if direction == "exact":
            regressed = delta != 0
        else:
            regressed = (delta < -band) if direction == "higher" \
                else (delta > band)
        rows.append({
            "metric": metric, "base": b, "fresh": f,
            "delta": round(delta, 6),
            "delta_pct": round(delta / b * 100.0, 2) if b else None,
            "band": round(band, 6), "direction": direction,
            "verdict": "FAIL" if regressed else "pass",
        })
    return rows


def print_table(rows: list[dict]) -> None:
    """Render the verdict table (skips compressed to one line)."""
    hdr = f"{'metric':<38} {'baseline':>12} {'fresh':>12} {'Δ%':>8} " \
          f"{'verdict':>8}"
    print(hdr)
    print("-" * len(hdr))
    skipped = []
    for r in rows:
        if r["verdict"] == "skip":
            skipped.append(r["metric"])
            continue
        pct = r.get("delta_pct")
        print(f"{r['metric']:<38} {r['base']:>12,.4g} {r['fresh']:>12,.4g} "
              f"{(f'{pct:+.1f}' if pct is not None else '—'):>8} "
              f"{r['verdict']:>8}")
    if skipped:
        print(f"skipped (absent on one side): {', '.join(skipped)}")


def _load_entry(spec: str) -> dict:
    """``FILE[:KEY]`` → one bench-result dict (BENCH_*.json or raw)."""
    path, _, key = spec.partition(":")
    with open(path) as f:
        payload = json.load(f)
    results = payload.get("results", payload)
    if key:
        entry = results.get(key)
        if not isinstance(entry, dict) or "value" not in entry:
            raise KeyError(
                f"no result entry {key!r} with a 'value' in {path}")
        return entry
    return payload


def _load_fresh(path: str) -> dict:
    """A fresh bench JSON: a file whose LAST JSON line/object wins (the
    bench.py contract is exactly one JSON line on stdout)."""
    with open(path) as f:
        text = f.read().strip()
    try:
        return json.loads(text)
    except json.JSONDecodeError:
        for line in reversed(text.splitlines()):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    raise ValueError(f"{path} contains no JSON object")


def _match_keys(fresh: dict, baseline_path: str) -> list[str]:
    """Auto-match: ALL baseline results entries sharing fresh's 'metric'.

    Returns every hit so the caller can refuse ambiguity: BENCH_SELF
    holds several captures of the same bench config under one metric
    string (gpt / gpt_trace / the traced A/Bs), and silently gating a
    variant against the first — typically the oldest, slowest — entry
    would let a real regression hide inside the inter-entry spread.
    """
    with open(baseline_path) as f:
        payload = json.load(f)
    return [key for key, entry in (payload.get("results") or {}).items()
            if isinstance(entry, dict)
            and entry.get("metric") == fresh.get("metric")]


def self_check(baseline_entry: dict) -> list[str]:
    """The gate's own regression test (schema-only mode): identical copy
    PASSES, a synthetic −10% tokens/s copy FAILS. Returns problems."""
    problems = []
    rows = compare(dict(baseline_entry), baseline_entry)
    if any(r["verdict"] == "FAIL" for r in rows):
        problems.append("identical copy flagged as regression")
    if not any(r["verdict"] == "pass" for r in rows):
        problems.append("identical copy compared zero metrics")
    regressed = dict(baseline_entry)
    regressed["value"] = float(baseline_entry["value"]) * 0.9
    rows = compare(regressed, baseline_entry)
    if not any(r["metric"] == "value" and r["verdict"] == "FAIL"
               for r in rows):
        problems.append("synthetic 10% tokens/s regression NOT caught")
    # the fused-backward rows self-check on synthetic values even when the
    # committed baseline predates them (their real rows skip-if-absent):
    # a pass-count change must exact-match FAIL, a 20% backward-per-layer
    # slowdown must exceed its band, and identical copies must pass
    seeded = dict(baseline_entry)
    seeded["flash_bwd_passes"] = 1
    seeded["perf_bwd_ms_per_layer"] = 5.0
    rows = compare(dict(seeded), seeded)
    if any(r["verdict"] == "FAIL" for r in rows):
        problems.append("identical fused-backward rows flagged as regression")
    drifted = dict(seeded)
    drifted["flash_bwd_passes"] = 3
    drifted["perf_bwd_ms_per_layer"] = 6.0
    rows = compare(drifted, seeded)
    for metric in ("flash_bwd_passes", "perf_bwd_ms_per_layer"):
        if not any(r["metric"] == metric and r["verdict"] == "FAIL"
                   for r in rows):
            problems.append(f"synthetic {metric} regression NOT caught")
    # fused-norm / overlapped-update rows self-check on synthetic values
    # (their real rows skip-if-absent on pre-PR-20 baselines): identical
    # copies pass, ANY path-flag flip must exact-match FAIL, and an
    # elementwise-line regrowth past its 10% band must fail
    fn = dict(baseline_entry)
    fn["norm_fused"] = 1
    fn["update_overlapped"] = 1
    fn["perf_elementwise_ms"] = 4.0
    rows = compare(dict(fn), fn)
    if any(r["verdict"] == "FAIL" for r in rows):
        problems.append("identical fused-norm rows flagged as regression")
    drifted_fn = dict(fn)
    drifted_fn["norm_fused"] = 0
    drifted_fn["update_overlapped"] = 0
    drifted_fn["perf_elementwise_ms"] = 5.0
    rows = compare(drifted_fn, fn)
    for metric in ("norm_fused", "update_overlapped",
                   "perf_elementwise_ms"):
        if not any(r["metric"] == metric and r["verdict"] == "FAIL"
                   for r in rows):
            problems.append(f"synthetic {metric} regression NOT caught")
    # finetune rows self-check the same way (their real rows skip-if-absent
    # on pre-finetune baselines): identical copies pass, a 2x adapter-step
    # slowdown and ANY trainable-frac change must fail
    ft = dict(baseline_entry)
    ft["finetune"] = {"adapter_step_time_s": 0.1,
                      "trainable_params_frac": 0.07,
                      "adapter_ckpt_bytes": 36000.0}
    rows = compare(json.loads(json.dumps(ft)), ft)
    if any(r["verdict"] == "FAIL" for r in rows):
        problems.append("identical finetune rows flagged as regression")
    drifted_ft = json.loads(json.dumps(ft))
    drifted_ft["finetune"]["adapter_step_time_s"] = 0.2
    drifted_ft["finetune"]["trainable_params_frac"] = 0.08
    rows = compare(drifted_ft, ft)
    for metric in ("finetune.adapter_step_time_s",
                   "finetune.trainable_params_frac"):
        if not any(r["metric"] == metric and r["verdict"] == "FAIL"
                   for r in rows):
            problems.append(f"synthetic {metric} regression NOT caught")
    # fleet-economics serving rows self-check on synthetic values (their
    # real rows skip-if-absent on pre-fleet baselines): identical copies
    # pass, a 30% requests-per-chip drop and a 0.99 → 0.90 attainment
    # drop must both fail
    sv = dict(baseline_entry)
    sv["serving"] = {"requests_per_chip": 4.0, "page_occupancy": 0.6,
                     "slo_attainment": 0.99}
    rows = compare(json.loads(json.dumps(sv)), sv)
    if any(r["verdict"] == "FAIL" for r in rows):
        problems.append("identical fleet serving rows flagged as regression")
    drifted_sv = json.loads(json.dumps(sv))
    drifted_sv["serving"]["requests_per_chip"] = 2.8
    drifted_sv["serving"]["slo_attainment"] = 0.90
    rows = compare(drifted_sv, sv)
    for metric in ("serving.requests_per_chip", "serving.slo_attainment"):
        if not any(r["metric"] == metric and r["verdict"] == "FAIL"
                   for r in rows):
            problems.append(f"synthetic {metric} regression NOT caught")
    # lazy-lifecycle serving rows (their real rows skip-if-absent on
    # pre-lazy baselines): identical copies pass, a mean-occupancy
    # collapse (the batcher stopped packing) and a preemption-rate jump
    # past the 0.25/request band (admission thrashing) must both fail
    lz = dict(baseline_entry)
    lz["serving"] = {"page_occupancy_mean": 0.7, "preemption_rate": 0.1}
    rows = compare(json.loads(json.dumps(lz)), lz)
    if any(r["verdict"] == "FAIL" for r in rows):
        problems.append("identical lazy-lifecycle rows flagged as regression")
    drifted_lz = json.loads(json.dumps(lz))
    drifted_lz["serving"]["page_occupancy_mean"] = 0.45
    drifted_lz["serving"]["preemption_rate"] = 0.5
    rows = compare(drifted_lz, lz)
    for metric in ("serving.page_occupancy_mean",
                   "serving.preemption_rate"):
        if not any(r["metric"] == metric and r["verdict"] == "FAIL"
                   for r in rows):
            problems.append(f"synthetic {metric} regression NOT caught")
    # fault-tolerance serving rows (their real rows skip-if-absent on
    # pre-PR-19 baselines): identical copies pass; a shed-count jump past
    # the +2 band, a hedge burst past +3, and ANY breaker opening on the
    # in-process bench must all fail
    ft_sv = dict(baseline_entry)
    ft_sv["serving"] = {"deadline_sheds": 1.0, "hedges_total": 0.0,
                        "breaker_opens": 0.0}
    rows = compare(json.loads(json.dumps(ft_sv)), ft_sv)
    if any(r["verdict"] == "FAIL" for r in rows):
        problems.append(
            "identical fault-tolerance rows flagged as regression")
    drifted_fs = json.loads(json.dumps(ft_sv))
    drifted_fs["serving"]["deadline_sheds"] = 4.0
    drifted_fs["serving"]["hedges_total"] = 4.0
    drifted_fs["serving"]["breaker_opens"] = 1.0
    rows = compare(drifted_fs, ft_sv)
    for metric in ("serving.deadline_sheds", "serving.hedges_total",
                   "serving.breaker_opens"):
        if not any(r["metric"] == metric and r["verdict"] == "FAIL"
                   for r in rows):
            problems.append(f"synthetic {metric} regression NOT caught")
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="gate a fresh bench JSON against committed baselines")
    ap.add_argument("fresh", nargs="?",
                    help="fresh bench JSON file (bench.py output); omit "
                         "for schema-only mode")
    ap.add_argument("--baseline", default=None, metavar="FILE[:KEY]",
                    help=f"baseline entry (default {DEFAULT_BASELINE} with "
                         "the entry auto-matched by 'metric')")
    ap.add_argument("--schema-only", action="store_true",
                    help="validate baselines + self-check the gate logic "
                         "without fresh chip numbers (CPU CI mode)")
    ap.add_argument("--json", metavar="OUT", nargs="?", const="-",
                    default=None,
                    help="write the verdict rows as JSON to OUT "
                         "(bare --json streams to stdout)")
    args = ap.parse_args(argv)

    base_spec = args.baseline or DEFAULT_BASELINE
    if args.schema_only or not args.fresh:
        path = base_spec.partition(":")[0]
        if not os.path.exists(path):
            print(f"error: baseline {path} not found", file=sys.stderr)
            return 2
        try:
            entry = _load_entry(base_spec if ":" in base_spec
                                else f"{path}:gpt")
        except (KeyError, ValueError, json.JSONDecodeError) as e:
            print(f"error: bad baseline: {e}", file=sys.stderr)
            return 2
        problems = self_check(entry)
        if problems:
            print("perf_gate self-check FAILED:", file=sys.stderr)
            for p in problems:
                print(f"  {p}", file=sys.stderr)
            return 1
        print(f"perf_gate schema-only: baseline {path} OK, gate logic "
              f"self-check passed ({len(GATE_METRICS)} headline metrics)")
        return 0

    try:
        fresh = _load_fresh(args.fresh)
    except (OSError, ValueError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    try:
        if ":" in base_spec:
            base = _load_entry(base_spec)
        else:
            keys = _match_keys(fresh, base_spec)
            if not keys:
                print(f"error: no entry in {base_spec} matches metric "
                      f"{fresh.get('metric')!r} — pass --baseline FILE:KEY",
                      file=sys.stderr)
                return 2
            if len(keys) > 1:
                print(f"error: metric {fresh.get('metric')!r} matches "
                      f"{len(keys)} entries in {base_spec} "
                      f"({', '.join(keys)}) — pass --baseline FILE:KEY to "
                      f"pick the A/B you are gating against",
                      file=sys.stderr)
                return 2
            print(f"baseline: {base_spec}:{keys[0]}")
            base = _load_entry(f"{base_spec}:{keys[0]}")
    except (OSError, KeyError, ValueError, json.JSONDecodeError) as e:
        print(f"error: bad baseline: {e}", file=sys.stderr)
        return 2

    rows = compare(fresh, base)
    print_table(rows)
    if args.json:
        payload = json.dumps({"rows": rows}, indent=1)
        if args.json == "-":
            print(payload)
        else:
            with open(args.json, "w") as f:
                f.write(payload + "\n")
    failed = [r for r in rows if r["verdict"] == "FAIL"]
    if failed:
        print(f"\nREGRESSION: {len(failed)} metric(s) outside their "
              f"tolerance band", file=sys.stderr)
        return 1
    print("\nperf gate: pass")
    return 0


if __name__ == "__main__":
    sys.exit(main())
