"""One cell, once, in a new process:

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Build, warm the cell's own shapes, measure for ``--seconds``, check what
the timed path produced against the plain reference, print one JSON line
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, when
traced, ``breakdown``) and exit. It measures the chip and nothing else:
any platform but ``tpu``, or fewer chips than the cell asks for, is exit
code 2 and no result line.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import logging
import os
import re
import sys
import threading
import time

T_PROCESS_START = time.monotonic()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import manifest as manifest_mod  # noqa: E402
from benchmarks import spans as spans_mod  # noqa: E402

CELL_KINDS = {"train_steps": "train_cell", "closed_loop": "serve_cell",
              "open_loop": "serve_cell"}


class _CompileLines(logging.Handler):
    """Collects the program's ``compiled <what> in <s>s; Mosaic kernels:
    {...}`` lines (``utils/env.log_compile``)."""

    def __init__(self):
        super().__init__()
        self.seconds: dict = {}
        self.kernels: dict = {}

    def emit(self, record):
        """Keep a ``compiled ...`` line's seconds and kernels."""
        m = re.match(r"compiled (.+) in ([0-9.]+)s; Mosaic kernels: (.*)",
                     record.getMessage())
        if m:
            self.seconds[m.group(1)] = float(m.group(2))
            self.kernels[m.group(1)] = json.loads(m.group(3))


def _dump_stacks(err, seconds: float) -> None:
    import faulthandler

    print(f"stalled for {seconds:g} s:", file=err, flush=True)
    faulthandler.dump_traceback(file=err, all_threads=True)


class Context:
    """What one run hands to its cell code and to the readers."""

    def __init__(self, manifest, cell: dict, args, devices, err, t_start):
        self.manifest, self.cell, self.err = manifest, cell, err
        self.t_start = t_start
        self.seed, self.seconds = int(args.seed), float(args.seconds)
        self.trace = bool(int(args.trace))
        self.control = args.control or None
        self.control_numbers = None
        self.chips, self.devices = int(cell["chips"]), devices
        self.config = manifest.config(cell["config"])
        self.mix = manifest.traffic(cell["traffic"])
        self.trace_seconds = min(float(self.mix.get("trace_seconds", 5.0)),
                                 self.seconds)
        self.spans = spans_mod.Spans(annotate=self.trace)
        self.trace_dir = os.path.join(manifest.root, ".bench_trace",
                                      cell["name"])
        self.compile_lines = _CompileLines()
        self.t_build_done = self.t_open = None
        self.memory_peak_bytes = 0
        self.chip_start_s = 0.0
        self.marks: list = []
        self._watchdog: threading.Timer | None = None

    # -- the files of this cell's configuration
    def reference(self):
        return manifest_mod.load_module(
            self.manifest.reference_path(self.config["reference"]))

    def reference_optimizer(self):
        return manifest_mod.load_module(
            self.manifest.reference_path(self.config["train"]["optimizer"]
                                         ["reference"]))

    def _over_chips(self, *spec):
        """A sharding of the reference's own: ``spec`` over the cell's
        chips, as one axis ``chips``."""
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        return NamedSharding(Mesh(np.array(self.devices), ("chips",)),
                             PartitionSpec(*spec))

    def reference_shardings(self, spec: dict):
        """One chip: none. Several: each leaf split over the chips along
        its largest dimension that divides, so the reference's float32
        state fits once the program's is freed."""
        if self.chips == 1:
            return None
        out = {}
        for name, (shape, _) in spec.items():
            dims = [i for i, d in enumerate(shape) if d % self.chips == 0]
            part = [None] * len(shape)
            if dims:
                part[max(dims, key=lambda i: shape[i])] = "chips"
            out[name] = self._over_chips(*part)
        return out

    def place_batch(self, batch: dict) -> dict:
        """A batch for the reference: on one chip as it is, on several
        split by rows."""
        import jax
        import jax.numpy as jnp

        if self.chips == 1:
            return {k: jnp.asarray(v) for k, v in batch.items()}
        rows = self._over_chips("chips")
        return {k: jax.device_put(v, rows) for k, v in batch.items()}

    # -- edges of the run
    def mark(self, what: str):
        """Where set-up's seconds go, for the log."""
        self.marks.append((what, time.monotonic() - self.t_start))

    def build_done(self):
        """The program's objects stand, seeded weights in them."""
        self.t_build_done = time.monotonic()
        self.mark("build_done")

    def window_opens(self) -> float:
        """The measured window starts now (and, traced, the profiler)."""
        self.t_open = time.monotonic()
        self.mark("window_opens")
        if self.trace:
            import jax

            # host spans come from the benchmark's own annotations; the
            # Python call tracer would swamp the trace and the host
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        return self.t_open

    def stop_trace(self):
        """End the traced stretch of the window."""
        import jax

        jax.profiler.stop_trace()

    def window_closed(self):
        """Read the peak memory while it is still the program's own."""
        self.mark("window_closed")
        for dev in self.devices:
            st = dev.memory_stats() or {}
            self.memory_peak_bytes = max(
                self.memory_peak_bytes,
                int(st.get("peak_bytes_in_use", 0))
                + int(st.get("peak_bytes_reserved", 0)))

    def watch(self, seconds: float | None) -> None:
        """Arm (or, with None, disarm) a one-shot dump of every thread's
        stack to the log if the next ``seconds`` pass without a re-arm: a
        tick or a step that stalls says where. A Python thread makes the
        dump, so it holds the interpreter lock while it walks the stacks.
        ``faulthandler.dump_traceback_later`` walks them from a thread
        without it, and on the chip's host that ended the run with SIGSEGV
        whenever the main thread was running Python just then: with a warm
        compile cache, a serving cell's first tick (PERF.md, PR 27)."""
        if self._watchdog is not None:
            self._watchdog.cancel()
            self._watchdog = None
        if seconds is None:
            return
        try:
            self.err.fileno()
        except (AttributeError, OSError, ValueError):
            return      # a log without a file descriptor (the tests')
        self._watchdog = threading.Timer(seconds, _dump_stacks,
                                         (self.err, seconds))
        self._watchdog.daemon = True
        self._watchdog.start()

    def free_program(self):
        """Drop what the program left on the device before the reference runs."""
        import jax

        gc.collect()
        jax.clear_caches()


def _devices(cell: dict, platforms: tuple) -> tuple:
    """The cell's devices and the seconds the runtime took to bring the
    chip up (the first ``jax.devices()``)."""
    import jax

    t0 = time.monotonic()
    devs = jax.devices()
    chip_start_s = time.monotonic() - t0
    if devs[0].platform not in platforms:
        raise SystemExit(
            f"benchmarks/run.py measures the chip; JAX found platform "
            f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < int(cell["chips"]):
        raise SystemExit(f"cell {cell['name']} needs {cell['chips']} chips; "
                         f"JAX found {len(devs)}")
    return devs[:int(cell["chips"])], chip_start_s


def run_cell(args, root: str = manifest_mod.ROOT,
             platforms: tuple = ("tpu",), out=sys.stdout, err=sys.stderr,
             t_start: float | None = None) -> dict:
    """Run one cell and print its result line; returns the line's object.
    ``root`` and ``platforms`` exist for the harness's own tests, which
    rehearse on the CPU from a temporary manifest."""
    t_start = time.monotonic() if t_start is None else t_start
    manifest = manifest_mod.Manifest(root)
    if args.workload not in manifest.cells:
        raise SystemExit(f"no cell {args.workload!r} in BENCHMARK.json")
    cell = manifest.cells[args.workload]
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax_imported_s = time.monotonic() - t_start
    devices, chip_start_s = _devices(cell, platforms)
    ctx = Context(manifest, cell, args, devices, err, t_start)
    ctx.chip_start_s = chip_start_s
    ctx.marks.append(("jax_imported", jax_imported_s))
    ctx.mark("devices_found")
    from fleetx_tpu.utils.log import logger as program_logger

    program_logger.addHandler(ctx.compile_lines)
    kind = ctx.mix["kind"]
    cell_code = importlib.import_module(f"benchmarks.{CELL_KINDS[kind]}")
    try:
        result = cell_code.run(ctx)
    finally:
        program_logger.removeHandler(ctx.compile_lines)

    ctx.mark("checked")
    print("timeline: " + ", ".join(f"{w} {t:.1f}s" for w, t in ctx.marks),
          file=err)
    values = dict(result["values"])
    # process start to the window's start, less the runtime's own start-up
    # of the chip: 9-19 s that swing by +-4 s from run to run, that no PR
    # can move, and that would alone swing a 20 s set-up by a fifth
    values["setup_s"] = ctx.t_open - ctx.t_start - ctx.chip_start_s
    metrics = {}
    if ctx.trace:
        from benchmarks import trace_reduce

        reduced = trace_reduce.reduce_dir(ctx.trace_dir,
                                          manifest.kernel_trace_names())
        for m in manifest.metrics_of(cell["name"], "per_layer"):
            reader = manifest_mod.load_module(manifest.reader_path(m["name"]))
            value = reader.read(ctx.spans, result["facts"], reduced,
                                {"ctx": ctx, "cell": cell,
                                 "values": values})
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        for m in manifest.metrics_of(cell["name"], "end_to_end"):
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    for name, v in metrics.items():
        if (name.endswith("_roofline") or "mfu" in name) and v["value"] > 100:
            raise SystemExit(f"{name} reads {v['value']:.1f} % of a peak: "
                             f"operations or bytes are counted too high, or "
                             f"the time leaves out part of the work")
    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(ctx.memory_peak_bytes)}
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": metrics,
            "device": device, "check": result["numbers"]}
    if ctx.trace:
        if reduced["n_devices"]:     # a rehearsal's trace has no device
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["top_ops"][:10],
                             "idle_gaps": reduced["idle_gaps"][:10]}
    if ctx.control_numbers is not None:
        line["control"] = {"precision": ctx.control,
                           "check": ctx.control_numbers}
    print(json.dumps(line), file=out, flush=True)
    return line


def parse(argv=None):
    """The driver's arguments, and ``--control`` for the builder."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default="",
                    help="also read the control's numbers in this lower "
                         "precision (the benchmark's own runs never do)")
    return ap.parse_args(argv)


if __name__ == "__main__":
    run_cell(parse(), t_start=T_PROCESS_START)
