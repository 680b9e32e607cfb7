"""``BENCHMARK.json`` and the files it names.

The harness is driven by data: a cell is ``<config> + <traffic> + chips``,
and whatever belongs to one configuration, one traffic mix, one per-layer
metric, one kernel or one served model family sits in a file of its own
that is found by the name in the manifest (a family: by the recipe's
``Model.module``). A later PR adds files and entries and edits nothing,
for a serve cell as for a train cell.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = ("command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer")


class ManifestError(ValueError):
    """The manifest, or a file it names, breaks the benchmark's contract."""


def _need(cond: bool, msg: str) -> None:
    if not cond:
        raise ManifestError(msg)


def _line(text: Any, what: str) -> None:
    _need(isinstance(text, str) and 1 <= len(text) <= 200
          and "\n" not in text and "\t" not in text,
          f"{what} must be 1..200 characters on one line")


class Manifest:
    """A validated ``BENCHMARK.json`` rooted at ``root``."""

    def __init__(self, root: str = ROOT):
        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.data = json.load(f)
        d = self.data
        _need(set(d) == set(TOP_KEYS),
              f"BENCHMARK.json keys must be exactly {TOP_KEYS}")
        self.bench_dir = os.path.join(root, d["paths"][0])
        self.configs = {c["name"]: c for c in d["configs"]}
        self.cells = {w["name"]: w for w in d["workloads"]}
        self.end_to_end = {m["name"]: m for m in d["end_to_end"]}
        self.per_layer = {m["name"]: m for m in d["per_layer"]}
        self.validate()

    # ------------------------------------------------------------ validation
    def validate(self) -> None:
        """Hold the manifest to the benchmark's contract; raise on a breach."""
        d = self.data
        _need(isinstance(d["command"], list) and 1 <= len(d["command"]) <= 32,
              "command: a list of 1..32 strings")
        for w in d["command"]:
            _line(w, "a word of command")
        _need(1 <= len(d["paths"]) <= 16, "paths: 1..16 directories")
        _need(isinstance(d["run_seconds"], int) and
              1 <= d["run_seconds"] <= 51, "run_seconds: 1..51")
        for group, keys in (("configs", {"name", "source", "file",
                                         "reduced", "why"}),
                            ("workloads", {"name", "config", "traffic",
                                           "chips", "why"})):
            names = [e["name"] for e in d[group]]
            _need(1 <= len(names) <= 24 and len(set(names)) == len(names),
                  f"{group}: 1..24 entries with distinct names")
            for e in d[group]:
                _need(set(e) == keys, f"{group} entry {e.get('name')}: "
                                      f"keys must be {sorted(keys)}")
                _need(bool(NAME_RE.match(e["name"])), f"bad name {e['name']}")
                _line(e["why"], f"why of {e['name']}")
        for c in d["configs"]:
            _line(c["source"], f"source of {c['name']}")
            _need(any(c["file"].startswith(p.rstrip("/") + "/")
                      for p in d["paths"]), f"{c['file']} not under paths")
            _need(len(c["reduced"]) <= 16 and
                  all(NAME_RE.match(k) for k in c["reduced"]),
                  f"reduced of {c['name']}")
            _need(os.path.exists(os.path.join(self.root, c["file"])),
                  f"config file {c['file']} is missing")
        _need(len({c["file"] for c in d["configs"]}) == len(d["configs"]),
              "two configurations share a file")
        pairs = set()
        for w in d["workloads"]:
            _need(w["config"] in self.configs, f"cell {w['name']}: "
                                               f"unknown config")
            _need(bool(NAME_RE.match(w["traffic"])), "bad traffic name")
            _need(w["chips"] in (1, 4), f"cell {w['name']}: chips 1 or 4")
            _need((w["config"], w["traffic"]) not in pairs,
                  f"pair of {w['name']} appears twice")
            pairs.add((w["config"], w["traffic"]))
            self.traffic_path(w["traffic"])
        used = {w["config"] for w in d["workloads"]}
        _need(used == set(self.configs), "a configuration no cell uses")
        four = sum(w["chips"] == 4 for w in d["workloads"])
        _need(four <= max(1, len(d["workloads"]) // 4),
              "too many four-chip cells")
        names = [m["name"] for m in d["end_to_end"] + d["per_layer"]]
        _need(len(set(names)) == len(names), "two metrics share a name")
        _need(1 <= len(d["end_to_end"]) <= 16 and
              1 <= len(d["per_layer"]) <= 128, "metric counts")
        _need("setup_s" in self.end_to_end, "setup_s is required")
        for m in d["end_to_end"]:
            _need(set(m) - {"workloads"} == {"name", "unit", "better",
                                             "bound", "source"},
                  f"end_to_end {m.get('name')}: wrong keys")
            _need(m["source"] in ("host_clock", "device_trace"),
                  f"{m['name']}: an end-to-end source is host_clock or "
                  f"device_trace")
            _need(0.01 <= m["bound"] <= 0.1, f"{m['name']}: bound")
        for m in d["per_layer"]:
            _need(set(m) - {"workloads"} == {"name", "unit", "better",
                                             "source", "layer", "moves"},
                  f"per_layer {m.get('name')}: wrong keys")
            _need(m["source"] in SOURCES, f"{m['name']}: source")
            _line(m["layer"], f"layer of {m['name']}")
            _need(m["moves"] in self.end_to_end,
                  f"{m['name']} moves no end-to-end metric")
            for cell in self.cells_of(m):
                _need(cell in self.cells_of(self.end_to_end[m["moves"]]),
                      f"{m['name']} is read in {cell}, which does not "
                      f"report {m['moves']}")
            self.reader_path(m["name"])
        for m in d["end_to_end"] + d["per_layer"]:
            _need(bool(NAME_RE.match(m["name"])), f"bad name {m['name']}")
            _need(bool(UNIT_RE.match(m["unit"])), f"bad unit {m['unit']!r}")
            _need(m["better"] in ("lower", "higher"), f"{m['name']}: better")
            for cell in m.get("workloads", []):
                _need(cell in self.cells, f"{m['name']}: unknown cell {cell}")
        for cell in self.cells:
            e2e = [m for m in self.end_to_end.values()
                   if cell in self.cells_of(m)]
            _need(len(e2e) >= 2 and any(m["name"] == "setup_s" for m in e2e),
                  f"cell {cell} reports setup_s and one more")
            _need(any(cell in self.cells_of(m)
                      for m in self.per_layer.values()),
                  f"cell {cell} has no per-layer metric")

    # --------------------------------------------------------------- lookups
    def cells_of(self, metric: dict) -> list:
        """The cells a metric is reported in (all, without ``workloads``)."""
        return list(metric.get("workloads") or self.cells)

    def metrics_of(self, cell: str, group: str) -> list:
        """The ``end_to_end`` or ``per_layer`` metrics reported in ``cell``."""
        table = self.end_to_end if group == "end_to_end" else self.per_layer
        return [m for m in table.values() if cell in self.cells_of(m)]

    def _find(self, sub: str, name: str, exts: tuple) -> str:
        for ext in exts:
            path = os.path.join(self.bench_dir, sub, name + ext)
            if os.path.exists(path):
                return path
        raise ManifestError(f"no {sub}/{name}{'|'.join(exts)} under "
                            f"{self.bench_dir}")

    def traffic_path(self, name: str) -> str:
        return self._find("traffic", name, (".json",))

    def reader_path(self, name: str) -> str:
        return self._find("layer_metrics", name, (".py",))

    def kernel_path(self, name: str) -> str:
        return self._find("kernels", name, (".py",))

    def reference_path(self, name: str) -> str:
        return self._find("reference", name, (".py",))

    def family(self, module: str) -> Any:
        """The serving family file of a recipe's ``Model.module``."""
        return load_module(self._find("families", module, (".py",)))

    def kernel_trace_names(self) -> tuple:
        """Every kernel's names as a device trace shows them."""
        names = []
        kdir = os.path.join(self.bench_dir, "kernels")
        for fn in sorted(os.listdir(kdir)):
            if fn.endswith(".py"):
                names.extend(load_module(os.path.join(kdir, fn)).TRACE_NAMES)
        return tuple(names)

    def config(self, name: str) -> dict:
        with open(os.path.join(self.root, self.configs[name]["file"])) as f:
            return json.load(f)

    def traffic(self, name: str) -> dict:
        with open(self.traffic_path(name)) as f:
            return json.load(f)

    def peaks(self, device_kind: str) -> dict:
        """The chip's published peaks; a device not in the table is an
        error, never a default."""
        with open(os.path.join(self.bench_dir, "peaks.json")) as f:
            table = json.load(f)
        if device_kind not in table:
            raise ManifestError(f"no peaks for device kind {device_kind!r} "
                                f"in peaks.json")
        return table[device_kind]


def load_module(path: str) -> Any:
    """Import one file that was found by name (a reader, a kernel count,
    a reference)."""
    name = "bench_file_" + re.sub(r"\W", "_", os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
