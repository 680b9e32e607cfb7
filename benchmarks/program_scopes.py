"""The device's time in a run's trace, put under the program's own scopes.

The train step and the serving programs open ``jax.named_scope("fx.<name>")``
scopes whose names are the keys of
``fleetx_tpu.observability.trace.DEVICE_SCOPES`` (``embed``, ``attn.core``,
``moe.route``, ``optimizer`` …), and the program keeps, for every program
``utils.env.log_compile`` compiled, a table from the optimised program's
instructions to ``(scope, direction)`` — ``fwd``, ``bwd`` or ``remat`` —
that ``trace.compiled_programs()`` hands out by module name
(``jit_train_step``, ``jit_prefill``, ``jit_decode``). A device trace names
every op by that same instruction. This module joins the two, once per run
(cached in the reduced-trace dict the readers share, like
``program_spans``): for the first device, every LEAF of ``XLA Ops``
(containers out, as ``trace_reduce.reduce`` takes them) that starts inside
an execution of a module on ``XLA Modules`` goes, by its instruction name,
to ``(module, scope, direction)``; an instruction the table lacks, or one
without a scope, is ``unscoped``. One line a module goes to the run's log,
after the seconds the program took to print and parse its tables.

A program without ``compiled_programs`` (an older commit), one that
compiled nothing through ``log_compile``, a trace without a device plane
(a CPU rehearsal) or without any execution of a known module raise
nothing: the readers get ``None`` and the result line leaves their metrics
out.
"""

from __future__ import annotations

import bisect
import re
import time
from typing import Optional

from benchmarks import program_spans, trace_reduce

CACHE_KEY = "_program_scopes"
UNSCOPED = "unscoped"
TRAIN_MODULE = "jit_train_step"
SERVE_MODULES = ("jit_prefill", "jit_decode")


def program_tables() -> dict:
    """``compiled_programs()`` of the program under test: module ->
    ``{instruction: (scope, direction)}``; empty where the program has no
    such function."""
    try:
        from fleetx_tpu.observability.trace import compiled_programs
    except ImportError:
        return {}
    return {name: table for name, table in compiled_programs().items()
            if table}


def build(device0: dict, tables: dict) -> dict:
    """module -> ``{"calls", "module_us", "leaf_us", "by": {(scope,
    direction): us}, "stray": {instruction: us}}`` for the modules of
    ``tables`` that ran on ``device0``; ``("unscoped", "")`` holds what no
    scope covers, ``stray`` names it by instruction."""
    runs = [(m["ts"], m["ts"] + m["dur"],
             re.sub(r"\(.*\)$", "", m["name"]))
            for m in trace_reduce._line(device0, trace_reduce.MODULES_LINE)]
    runs = [r for r in runs if r[2] in tables]
    starts = [r[0] for r in runs]
    out: dict = {}
    for start, end, name in runs:
        row = out.setdefault(name, {"calls": 0, "module_us": 0.0,
                                    "leaf_us": 0.0, "by": {}, "stray": {}})
        row["calls"] += 1
        row["module_us"] += end - start
    for e in trace_reduce._line(device0, trace_reduce.OPS_LINE):
        if trace_reduce._is_container(e):
            continue
        at = bisect.bisect_right(starts, e["ts"]) - 1
        if at < 0 or e["ts"] >= runs[at][1]:
            continue                    # between two executions
        name = runs[at][2]
        row = out[name]
        scope, direction = tables[name].get(e["name"], ("", ""))
        key = (scope, direction) if scope else (UNSCOPED, "")
        row["by"][key] = row["by"].get(key, 0.0) + e["dur"]
        row["leaf_us"] += e["dur"]
        if not scope:
            row["stray"][e["name"]] = row["stray"].get(e["name"], 0.0) \
                + e["dur"]
    return out


def _log_lines(got: dict) -> list:
    """``device by scope:`` — one line a module, largest scope first, the
    directions of a scope that has more than ``fwd``; then what is
    unscoped, by instruction."""
    lines = []
    for name, row in sorted(got.items()):
        n = row["calls"]
        scopes: dict = {}
        for (scope, direction), us in row["by"].items():
            scopes.setdefault(scope, {})[direction] = us
        parts = []
        for scope, dirs in sorted(scopes.items(),
                                  key=lambda kv: -sum(kv[1].values())):
            if scope == UNSCOPED:
                continue
            text = f"{scope} {sum(dirs.values()) / n / 1e3:.2f}"
            if set(dirs) - {"fwd"}:
                text += " (" + " ".join(
                    f"{d} {us / n / 1e3:.2f}"
                    for d, us in sorted(dirs.items())) + ")"
            parts.append(text)
        stray_us = row["by"].get((UNSCOPED, ""), 0.0)
        parts.append(f"{UNSCOPED} {stray_us / n / 1e3:.2f}")
        lines.append(
            f"device by scope: {name} {row['module_us'] / n / 1e3:.2f} ms a "
            f"call x {n}, {row['leaf_us'] / n / 1e3:.2f} in leaves: "
            + ", ".join(parts))
        if row["stray"]:
            worst = sorted(row["stray"].items(), key=lambda kv: -kv[1])[:6]
            lines.append(f"unscoped in {name}: " + ", ".join(
                f"{op} {us / n / 1e3:.3f}" for op, us in worst)
                + f" ms a call ({len(row['stray'])} instructions)")
    return lines


def of_run(trace: dict, info: dict) -> Optional[dict]:
    """The run's device time by module, scope and direction, worked out
    once and kept in ``trace``; None where there is nothing to join."""
    if CACHE_KEY in trace:
        return trace[CACHE_KEY]
    trace[CACHE_KEY] = None
    device0 = trace.get("_device0")
    if device0 is None:
        return None
    t0 = time.monotonic()
    tables = program_tables()
    if not tables:
        return None
    print(f"device scope tables: {len(tables)} programs, "
          f"{sum(map(len, tables.values()))} instructions, made in "
          f"{time.monotonic() - t0:.3f} s", file=info["ctx"].err)
    got = build(device0, tables)
    if not got:
        return None
    trace[CACHE_KEY] = got
    for line in _log_lines(got):
        print(line, file=info["ctx"].err)
    return got


# ------------------------------------------------------------- the readers
def _covers(prefixes: Optional[tuple], scope: str) -> bool:
    return prefixes is None or any(
        scope == p or scope.startswith(p + ".") for p in prefixes)


def scope_us(got: Optional[dict], modules: tuple,
             scopes: Optional[tuple] = None, directions: Optional[tuple] = None,
             but: tuple = ()) -> Optional[float]:
    """Microseconds of the named modules' leaves whose scope is one of
    ``scopes`` (a name, or a prefix of dotted names: ``attn`` covers
    ``attn.core``; None: every scope) and not one of ``but``, in one of
    ``directions`` (None: all). ``unscoped`` time is in no scope. None
    where none of the modules ran."""
    if got is None or not any(m in got for m in modules):
        return None
    return sum(us for m in modules if m in got
               for (scope, direction), us in got[m]["by"].items()
               if scope != UNSCOPED and _covers(scopes, scope)
               and not _covers(but, scope)
               and (directions is None or direction in directions))


def ms_per_call(got: Optional[dict], module: str, scopes=None,
                directions=None, but: tuple = ()) -> Optional[float]:
    """``scope_us`` of one module over its executions, in ms."""
    us = scope_us(got, (module,), scopes, directions, but)
    return None if us is None else us / got[module]["calls"] / 1e3


def ms_per_tick(trace: dict, info: dict, scopes: tuple) -> Optional[float]:
    """``scope_us`` of both serving programs over the traced ``serve.tick``
    spans of the program (``program_spans``), in ms."""
    us = scope_us(of_run(trace, info), SERVE_MODULES, scopes)
    spans = program_spans.of_run(trace, info)
    ticks = len(spans["by_name"].get("serve.tick", ())) if spans else 0
    return None if us is None or not ticks else us / ticks / 1e3


def unscoped_pct(got: Optional[dict], modules: tuple) -> Optional[float]:
    """Leaf time of the named modules under no scope over their leaf
    time, in %."""
    if got is None:
        return None
    rows = [got[m] for m in modules if m in got]
    total = sum(r["leaf_us"] for r in rows)
    if not total:
        return None
    return 100.0 * sum(r["by"].get((UNSCOPED, ""), 0.0) for r in rows) / total
