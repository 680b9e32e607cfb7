"""From a profiler trace to numbers: the reduction every PR shares.

Copied in idea from ``fleetx_tpu/observability/perf.py`` (category
taxonomy, scan regions as ``while`` ops) and rebuilt on two inputs: the
``.xplane.pb`` that ``jax.profiler`` writes (read with
``jax.profiler.ProfileData``, nothing else) and the Chrome-trace JSON of
older captures. Both are normalised to planes -> lines -> events in
microseconds before anything is computed.

What a TPU trace looks like (read by hand on the v5e, PERF.md): one plane
``/device:TPU:<i>`` per chip with the lines ``Steps``, ``XLA Modules``
(one event per executed program, ``jit_<name>(<hash>)``) and ``XLA Ops``
(every HLO op; a layer scan is a ``while`` op that covers its body's ops; an op's name is its whole HLO
instruction, a Mosaic kernel's the ``name=`` of its ``pallas_call``);
the host is ``/host:CPU`` with one line per thread, where the benchmark's
``bench:<span>`` annotations sit on the same clock.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
COPY_CATEGORIES = ("data formatting", "copy", "copy-start", "copy-done")
SPAN_PREFIX = "bench:"
MIN_GAP_US = 20.0


# ---------------------------------------------------------------- loading
FUSION_KINDS = {"kOutput": "convolution fusion", "kLoop": "loop fusion",
                "kInput": "input fusion", "kCustom": "custom fusion"}


def split_hlo(text: str) -> Optional[tuple]:
    """A TPU trace names a device op by its whole HLO instruction,
    ``%<name> = <type> <opcode>(<operands>), <attributes>``. Returns
    ``(name, category)`` — the category is the opcode, or for a fusion its
    kind as older traces spelled it (``kOutput`` holds the matrix
    products) — or None for a name that is not an instruction."""
    m = re.match(r"%(\S+) = ", text)
    if not m:
        return None
    op = re.search(r" ([a-z][a-z0-9\-]*)\(", text)
    cat = op.group(1) if op else ""
    if cat == "fusion":
        kind = re.search(r"kind=(\w+)", text)
        cat = FUSION_KINDS.get(kind.group(1) if kind else "", "loop fusion")
    return m.group(1), cat


def normalise(event: dict) -> dict:
    """Give an event whose name is an HLO instruction the short name and
    the ``hlo_category`` / ``long_name`` arguments of the older format."""
    parts = split_hlo(event["name"])
    if parts is not None:
        event["args"] = dict(event.get("args") or {},
                             long_name=event["name"], hlo_category=parts[1])
        event["name"] = parts[0]
    return event


def load_xplane(path: str) -> list:
    """An ``.xplane.pb`` as planes -> lines -> events, in microseconds."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = [normalise({"name": e.name, "ts": e.start_ns / 1e3,
                                 "dur": e.duration_ns / 1e3,
                                 "args": dict(e.stats)})
                      for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def load_chrome(source: dict) -> list:
    """Chrome-trace JSON, parsed."""
    procs, threads, events = {}, {}, {}
    for e in source.get("traceEvents") or []:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            threads[(e["pid"], e.get("tid"))] = e["args"]["name"]
        elif e.get("ph") == "X":
            events.setdefault((e["pid"], e.get("tid")), []).append(
                {"name": e.get("name", ""), "ts": float(e["ts"]),
                 "dur": float(e.get("dur", 0.0)),
                 "args": e.get("args") or {}})
    planes = {}
    for (pid, tid), evs in events.items():
        plane = planes.setdefault(pid, {"name": procs.get(pid, str(pid)),
                                        "lines": []})
        plane["lines"].append({"name": threads.get((pid, tid), str(tid)),
                               "events": evs})
    return list(planes.values())


def load(path: str) -> list:
    """``.xplane.pb``, Chrome-trace JSON, or planes already normalised (the
    reduced fixtures under ``benchmarks/fixtures``)."""
    if path.endswith(".pb"):
        return load_xplane(path)
    with open(path, "rb") as f:
        data = f.read()
    if data[:2] == b"\x1f\x8b":
        data = gzip.decompress(data)
    parsed = json.loads(data.decode("utf-8", errors="replace"))
    if not isinstance(parsed, list):
        return load_chrome(parsed)
    for plane in parsed:
        for line in plane["lines"]:
            line["events"] = [normalise(e) for e in line["events"]]
    return parsed


def newest_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` that ``jax.profiler`` wrote under ``trace_dir``."""
    hits = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(hits, key=os.path.getmtime)


# ------------------------------------------------------------- classifying
def _shape_suffix(long_name: str) -> str:
    m = re.search(r"=\s*\(?([a-z0-9]+)\[([0-9,]*)\]", long_name or "")
    if not m:
        return ""
    return f"_{m.group(1)}_" + m.group(2).replace(",", "_") + "_"


def label_of(event: dict, kernel_names: tuple = ()) -> str:
    """``<category>:<name>`` for one device op: kernels by the ``name=``
    their ``pallas_call`` carries; copies, dynamic-(update-)slices,
    collectives, matmuls and the rest by HLO category, with the result's
    shape where the name alone says nothing."""
    args = event.get("args") or {}
    name = event["name"]
    long_name = str(args.get("long_name", ""))
    cat = str(args.get("hlo_category", "")).lower()
    base = re.sub(r"(\.\d+|\.clone|\.remat\d*)+$", "", name)
    if base in kernel_names:
        return f"kernel:{base}"
    low = name.lower()
    if any(c in low or c in cat for c in COLLECTIVES):
        return f"collective:{base}"
    if "dynamic-update-slice" in low or "dynamic-slice" in low or \
            cat == "dynamic-update-slice":
        return f"dus:{base}{_shape_suffix(long_name)}"
    if cat in COPY_CATEGORIES:
        return f"copy:{base}{_shape_suffix(long_name)}"
    if "convolution" in cat or cat == "custom fusion":
        return f"matmul:{base}{_shape_suffix(long_name)}"
    if cat == "custom-call":
        return f"custom:{base}"
    if cat == "rng-bit-generator":
        return f"rng:{base}"
    return f"fusion:{base}{_shape_suffix(long_name)}"


def _is_container(event: dict) -> bool:
    cat = str((event.get("args") or {}).get("hlo_category", "")).lower()
    return cat in CONTAINERS or \
        re.match(r"^(while|conditional|call)(\.\d+)?$", event["name"]) \
        is not None


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return sorted(line["events"], key=lambda e: e["ts"])
    return []


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


# ---------------------------------------------------------------- reducing
def host_spans(planes: list) -> list:
    """The benchmark's own annotations: ``(name, ts, end)`` in us."""
    out = []
    for plane in planes:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            out.extend((e["name"][len(SPAN_PREFIX):], e["ts"],
                        e["ts"] + e["dur"]) for e in line["events"]
                       if e["name"].startswith(SPAN_PREFIX))
    return sorted(out, key=lambda s: s[1])


def _attribute_gaps(gaps: list, spans: list) -> dict:
    """Each idle gap goes to the shortest span that covers its middle."""
    out: dict = {}
    for s, e in gaps:
        mid = 0.5 * (s + e)
        cover = [sp for sp in spans if sp[1] <= mid <= sp[2]]
        name = min(cover, key=lambda sp: sp[2] - sp[1])[0] if cover \
            else "unattributed"
        out[name] = out.get(name, 0.0) + (e - s)
    return out


def scan_regions(plane: dict, module_prefix: str) -> Optional[dict]:
    """Per executed module whose name starts with ``module_prefix``: the
    first ``while`` inside is the forward layer scan, the longest of the
    others the backward; the rest of the module is outside the scans.
    Mean milliseconds per execution, or None without such a module."""
    modules = [m for m in _line(plane, MODULES_LINE)
               if m["name"].startswith(module_prefix)]
    whiles = [e for e in _line(plane, OPS_LINE)
              if _is_container(e) and e["name"].startswith("while")]
    rows = []
    for m in modules:
        inside = [w for w in whiles
                  if m["ts"] <= w["ts"] < m["ts"] + m["dur"]]
        # nested whiles (a scan inside a scan) count once, by the outer
        outer = [w for w in inside if not any(
            o is not w and o["ts"] <= w["ts"] and
            w["ts"] + w["dur"] <= o["ts"] + o["dur"] for o in inside)]
        if len(outer) < 2:
            continue
        fwd = outer[0]
        bwd = max(outer[1:], key=lambda w: w["dur"])
        rows.append((m["dur"], fwd["dur"], bwd["dur"]))
    if not rows:
        return None
    n = len(rows)
    step, fwd, bwd = (sum(r[i] for r in rows) / n / 1e3 for i in range(3))
    return {"executions": n, "module_ms": step, "fwd_ms": fwd,
            "bwd_ms": bwd, "outside_scan_ms": step - fwd - bwd}


def reduce(planes: list, kernel_names: tuple = ()) -> dict:
    """Busy and idle time, time by op, executed modules, idle gaps by the
    host span they fall in — averaged over the device planes."""
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    spans = host_spans(planes)
    out: dict = {"n_devices": len(devices),
                 "spans": {}, "ops": {}, "op_counts": {}, "op_text": {},
                 "modules": {}, "top_ops": [], "idle_gaps": []}
    for name, s, e in spans:
        row = out["spans"].setdefault(name, [0, 0.0])
        row[0] += 1
        row[1] += (e - s) / 1e6
    if not devices:
        return out
    edges = [(e["ts"], e["ts"] + e["dur"]) for p in devices
             for e in _line(p, OPS_LINE)]
    edges += [(s, e) for _, s, e in spans]
    t0, t1 = min(s for s, _ in edges), max(e for _, e in edges)
    busy_total, gap_by_span = 0.0, {}
    for i, plane in enumerate(devices):
        leaves = [e for e in _line(plane, OPS_LINE) if not _is_container(e)]
        merged = _union([(e["ts"], e["ts"] + e["dur"]) for e in leaves])
        busy_total += sum(e - s for s, e in merged)
        for e in leaves:
            lab = label_of(e, kernel_names)
            out["ops"][lab] = out["ops"].get(lab, 0.0) + e["dur"] / 1e6
            out["op_counts"][lab] = out["op_counts"].get(lab, 0) + 1
            out["op_text"].setdefault(lab, str((e.get("args") or {}).get(
                "long_name", ""))[:2000])
        for m in _line(plane, MODULES_LINE):
            base = re.sub(r"\(.*\)$", "", m["name"])
            row = out["modules"].setdefault(base, [0, 0.0])
            row[0] += 1
            row[1] += m["dur"] / 1e6
        if i == 0:
            cuts = [t0] + [x for iv in merged for x in iv] + [t1]
            gaps = [(cuts[j], cuts[j + 1]) for j in range(0, len(cuts), 2)
                    if cuts[j + 1] - cuts[j] >= MIN_GAP_US]
            gap_by_span = _attribute_gaps(gaps, spans)
    n = len(devices)
    out["ops"] = {k: v / n for k, v in out["ops"].items()}
    out["op_counts"] = {k: v / n for k, v in out["op_counts"].items()}
    out["modules"] = {k: [c / n, s / n] for k, (c, s) in
                      out["modules"].items()}
    out["window_s"] = (t1 - t0) / 1e6
    out["busy_s"] = busy_total / n / 1e6
    out["idle_share"] = 1.0 - out["busy_s"] / out["window_s"]
    out["top_ops"] = [[k, v] for k, v in sorted(
        out["ops"].items(), key=lambda kv: -kv[1])[:10]]
    out["idle_gaps"] = [[k, v / 1e6] for k, v in sorted(
        gap_by_span.items(), key=lambda kv: -kv[1])[:10]]
    out["collective_s"] = sum(v for k, v in out["ops"].items()
                              if k.startswith("collective:"))
    out["_device0"] = devices[0]
    return out


def reduce_dir(trace_dir: str, kernel_names: tuple = ()) -> dict:
    return reduce(load(newest_xplane(trace_dir)), kernel_names)


def ops_matching(reduced: dict, prefix: str = "", text: str = "") -> float:
    """Seconds (per device) in ops whose label starts with ``prefix`` and
    whose HLO text holds ``text``."""
    return sum(v for k, v in reduced["ops"].items()
               if k.startswith(prefix) and text in reduced["op_text"][k])
