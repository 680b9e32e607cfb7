"""What the per-layer readers share: the roofline arithmetic and a few
look-ups. A reader is ``read(spans, facts, trace, info) -> value | None``;
``spans`` are the benchmark's host spans, ``facts`` what the cell counted,
``trace`` the reduced device trace, ``info`` the run's context. A reader
that finds nothing to read returns None and the line leaves it out."""

from __future__ import annotations

from benchmarks import manifest as manifest_mod


def kernel(info: dict, name: str):
    return manifest_mod.load_module(
        info["ctx"].manifest.kernel_path(name))


def peaks(info: dict) -> dict:
    """The published peaks of the chip the run is on."""
    ctx = info["ctx"]
    return ctx.manifest.peaks(ctx.devices[0].device_kind)


def on_device(trace: dict) -> bool:
    return bool(trace) and trace.get("n_devices", 0) > 0


def kernel_seconds(trace: dict, names: tuple) -> dict:
    """name -> (calls, seconds) per device for the kernels present."""
    return {n: (trace["op_counts"][f"kernel:{n}"], trace["ops"][f"kernel:{n}"])
            for n in names if f"kernel:{n}" in trace.get("ops", {})}


def roofline_share(floor_inputs: list, seconds: float, pk: dict) -> float:
    """``floor_inputs``: (calls, {"flops", "bytes"}) pairs. The least time
    the chip could take, the larger of operations over peak FLOP/s and
    bytes over peak bytes/s for each call, over the kernel's time, in %."""
    floor = sum(calls * max(c["flops"] / pk["bf16_flops_per_s"],
                            c["bytes"] / pk["hbm_bytes_per_s"])
                for calls, c in floor_inputs)
    return 100.0 * floor / seconds


def train_regions(trace: dict):
    """Forward / backward / outside split of the train step program, worked
    out once for the three readers that share it."""
    if not on_device(trace):
        return None
    if "_regions" not in trace:
        from benchmarks import trace_reduce

        trace["_regions"] = trace_reduce.scan_regions(trace["_device0"],
                                                      "jit_train_step")
    return trace["_regions"]


def per_tick(seconds: float, trace: dict, span: str):
    """``seconds`` over the traced executions of host span ``span``, in ms."""
    n = trace.get("spans", {}).get(span, [0, 0.0])[0]
    return 1e3 * seconds / n if n else None
