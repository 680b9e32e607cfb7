"""The program's own spans in a run's trace, and the device's idle gaps put
down to them.

The two host loops of the program — ``ServingEngine.step`` and the step
loop of ``EagerEngine.fit`` — open ``jax.profiler.TraceAnnotation`` spans
whose names are the keys of
``fleetx_tpu.observability.trace.HOT_LOOP_SPANS`` (``serve.tick`` around
``serve.admit`` … ``serve.gauges``; ``data_fetch``, ``shard_batch``,
``train_step``, ``fit.fetch_metrics``, ``fit.log``). They sit on the host
plane of the profiler's trace, on the device's clock. This module reads
them once per run (through ``trace_reduce.load``, which copies every event
into plain dicts), caches the result in the reduced-trace dict the readers
share, and gives the readers spans by name, a span's time less what other
spans cover inside it, and the device's idle gaps (leaves of ``XLA Ops`` of
the first device, gaps of at least ``trace_reduce.MIN_GAP_US``) attributed
to the shortest program span that covers each gap's middle — ``outside``
where none does: between two ticks that is the benchmark's own loop.

A program without the table (an older commit), a trace without its spans,
or a trace without a device plane (a CPU rehearsal) raise nothing: the
readers get ``None`` and the result line leaves their metrics out.
"""

from __future__ import annotations

from typing import Optional

from benchmarks import stats, trace_reduce

OUTSIDE = "outside"
CACHE_KEY = "_program_spans"
WAIT_PARTS = ("queue_wait", "prefill_wait", "prefill_run")


def span_table() -> dict:
    """``HOT_LOOP_SPANS`` of the program under test: name -> ``(working |
    waiting, what it covers)``; empty where the program has none."""
    try:
        from fleetx_tpu.observability.trace import HOT_LOOP_SPANS
    except ImportError:
        return {}
    return dict(HOT_LOOP_SPANS)


def waiting_names(table: dict) -> set:
    """The spans in which the host waits on the device."""
    return {n for n, (kind, _) in table.items() if kind == "waiting"}


def fit_names(table: dict) -> set:
    """The spans of ``fit``'s step loop (the others are ``serve.*``)."""
    return {n for n in table if not n.startswith("serve.")}


def collect(planes: list, table: dict) -> list:
    """The host events named in ``table``: ``(name, ts, end, args)`` in us,
    by start; an enclosing span sorts before what it holds."""
    out = []
    for plane in planes:
        if trace_reduce.DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            out.extend((e["name"], e["ts"], e["ts"] + e["dur"],
                        e.get("args") or {})
                       for e in line["events"] if e["name"] in table)
    return sorted(out, key=lambda s: (s[1], -s[2]))


def by_name(spans: list) -> dict:
    """name -> its spans, in order."""
    out: dict = {}
    for sp in spans:
        out.setdefault(sp[0], []).append(sp)
    return out


def inside(spans: list, outer: tuple, names=None) -> list:
    """The spans that lie within ``outer`` (itself left out), optionally
    only those named in ``names``."""
    return [sp for sp in spans
            if sp is not outer and outer[1] <= sp[1] and sp[2] <= outer[2]
            and (names is None or sp[0] in names)]


def covered_us(intervals: list) -> float:
    """Microseconds in the union of ``(start, end)`` intervals."""
    total, edge = 0.0, None
    for s, e in sorted(intervals):
        if edge is None or s > edge:
            total, edge = total + (e - s), e
        elif e > edge:
            total, edge = total + (e - edge), e
    return total


def self_us(spans: list, span: tuple) -> float:
    """A span's time less what the spans inside it cover."""
    return (span[2] - span[1]) - covered_us(
        [(sp[1], sp[2]) for sp in inside(spans, span)])


def self_by_name(spans: list) -> dict:
    """name -> ``[spans, seconds]`` of self time: where the host's time in
    the program's loops went, each microsecond under one name."""
    out: dict = {}
    for sp in spans:
        row = out.setdefault(sp[0], [0, 0.0])
        row[0] += 1
        row[1] += self_us(spans, sp) / 1e6
    return out


def device_gaps(planes: list, device0: dict) -> list:
    """Idle gaps of the first device, ``(start, end)`` in us, as
    ``trace_reduce.reduce`` takes them: between the leaves of ``XLA Ops``,
    from the trace's first edge to its last (device ops of every chip and
    the benchmark's own ``bench:`` spans), gaps of at least ``MIN_GAP_US``."""
    devices = [p for p in planes if trace_reduce.DEVICE_PLANE.match(p["name"])]
    edges = [(e["ts"], e["ts"] + e["dur"]) for p in devices
             for e in trace_reduce._line(p, trace_reduce.OPS_LINE)]
    edges += [(s, e) for _, s, e in trace_reduce.host_spans(planes)]
    leaves = [e for e in trace_reduce._line(device0, trace_reduce.OPS_LINE)
              if not trace_reduce._is_container(e)]
    if not edges or not leaves:
        return []
    merged = trace_reduce._union([(e["ts"], e["ts"] + e["dur"])
                                  for e in leaves])
    cuts = [min(s for s, _ in edges)] + [x for iv in merged for x in iv] \
        + [max(e for _, e in edges)]
    return [(cuts[j], cuts[j + 1]) for j in range(0, len(cuts), 2)
            if cuts[j + 1] - cuts[j] >= trace_reduce.MIN_GAP_US]


def attribute(gaps: list, spans: list) -> dict:
    """name -> ``[gaps, seconds]``: each gap goes whole to the shortest
    span that covers its middle, or to ``outside``."""
    out: dict = {}
    for s, e in gaps:
        mid = 0.5 * (s + e)
        cover = [sp for sp in spans if sp[1] <= mid <= sp[2]]
        name = min(cover, key=lambda sp: sp[2] - sp[1])[0] if cover \
            else OUTSIDE
        row = out.setdefault(name, [0, 0.0])
        row[0] += 1
        row[1] += (e - s) / 1e6
    return out


def build(planes: list, device0: Optional[dict], table: dict) -> dict:
    """What the readers share, from planes already loaded."""
    spans = collect(planes, table)
    idle = None
    if device0 is not None:
        idle = attribute(device_gaps(planes, device0), spans)
    return {"table": table, "spans": spans, "by_name": by_name(spans),
            "idle": idle}


def of_run(trace: dict, info: dict) -> Optional[dict]:
    """The run's program spans, read once and kept in ``trace``; None where
    the program has no table or the trace none of its spans. The host's self
    time by phase (mean per span) and the device's idle table by phase go
    to the run's log, one line each."""
    if CACHE_KEY in trace:
        return trace[CACHE_KEY]
    trace[CACHE_KEY] = None
    table = span_table()
    ctx = info.get("ctx")
    if not table or ctx is None:
        return None
    try:
        planes = trace_reduce.load(trace_reduce.newest_xplane(ctx.trace_dir))
    except OSError:             # no trace was written
        return None
    got = build(planes, trace.get("_device0"), table)
    if not got["spans"]:
        return None
    trace[CACHE_KEY] = got
    rows = sorted(self_by_name(got["spans"]).items(), key=lambda kv: -kv[1][1])
    print("host by phase: " + ", ".join(
        f"{name} {1e3 * sec / n:.3f} ms x {n}" for name, (n, sec) in rows),
        file=ctx.err)
    if got["idle"] is not None:
        rows = sorted(got["idle"].items(), key=lambda kv: -kv[1][1])
        total = sum(sec for _, sec in got["idle"].values())
        table_line = ", ".join(f"{name} {1e3 * sec:.2f} ms in {n} gaps"
                               for name, (n, sec) in rows)
        print(f"idle by phase: {table_line}; attributed {total:.4f} s of "
              f"{trace['window_s'] - trace['busy_s']:.4f} s idle",
              file=ctx.err)
    return got


# ------------------------------------------------------------- the readers
def host_ms_per_unit(got: Optional[dict], unit: str) -> Optional[float]:
    """Median over the traced units of ``unit`` (a span that holds its
    phases: ``serve.tick``) of its time less the waiting spans inside it."""
    if got is None or not got["by_name"].get(unit):
        return None
    waits = waiting_names(got["table"])
    rows = [(u[2] - u[1]) - covered_us(
        [(sp[1], sp[2]) for sp in inside(got["spans"], u, waits)])
        for u in got["by_name"][unit]]
    return stats.percentile(rows, 50) / 1e3


def fit_host_ms(got: Optional[dict]) -> Optional[float]:
    """Per step, from one ``train_step`` span's start to the next, the sum of
    the working ``fit`` spans that start in it; median over the traced steps,
    in ms."""
    if got is None:
        return None
    starts = [sp[1] for sp in got["by_name"].get("train_step", [])]
    if len(starts) < 2:
        return None
    working = fit_names(got["table"]) - waiting_names(got["table"])
    mine = [sp for sp in got["spans"] if sp[0] in working]
    rows = [sum(sp[2] - sp[1] for sp in mine if a <= sp[1] < b)
            for a, b in zip(starts, starts[1:])]
    return stats.percentile(rows, 50) / 1e3


def idle_ms_per_unit(got: Optional[dict], unit: str) -> Optional[float]:
    """All the device's idle gaps of the trace — those inside the program's
    spans and those ``outside`` — over the traced units of ``unit``, in ms;
    None without a device plane."""
    if got is None or got["idle"] is None or not got["by_name"].get(unit):
        return None
    total = sum(sec for _, sec in got["idle"].values())
    return 1e3 * total / len(got["by_name"][unit])


def first_token_wait_ms(facts: dict, histogram: str) -> Optional[float]:
    """Mean of the newest ``n_ttft`` samples of a registry histogram that
    the engine fills once per first token: nothing is recorded after the
    window closes, so they are the window's first tokens. None where the
    program has no such histogram."""
    from fleetx_tpu.observability.metrics import get_registry

    n = int(facts.get("n_ttft") or 0)
    last = getattr(get_registry().histogram(histogram), "last", None)
    if not n or last is None:
        return None
    samples = last(n)
    return 1e3 * sum(samples) / n if len(samples) == n else None


def first_token_waits(facts: dict, info: dict) -> Optional[dict]:
    """The three parts of a first token's wait (``WAIT_PARTS``) in ms, read
    once and kept in ``facts``; None unless the program records all three.
    Their sum goes to the run's log beside the mean of the benchmark's own
    first-token latencies, which it has to equal."""
    if "_first_token_waits" not in facts:
        got = {p: first_token_wait_ms(facts, f"serving_{p}")
               for p in WAIT_PARTS}
        facts["_first_token_waits"] = got = \
            None if None in got.values() else got
        if got is not None:
            ttft = facts.get("ttft_s") or [0.0]
            print("first-token waits: " + " + ".join(
                f"{p} {v:.2f}" for p, v in got.items())
                + f" = {sum(got.values()):.2f} ms; mean ttft "
                  f"{1e3 * sum(ttft) / len(ttft):.2f} ms over "
                  f"{facts.get('n_ttft')} first tokens", file=info["ctx"].err)
    return facts["_first_token_waits"]
