"""Traffic kind ``train_steps``: drive ``EagerEngine`` through ``fit``.

Set-up builds ONE engine (the recipe YAML plus the configuration's
overrides), gives it the seeded weights, and feeds one ``fit`` call: the
first steps are followed by the check, the next ones warm up, and the same
call then runs the measured window — the same compiled step, state and
feed throughout. The benchmark sees each step end through the module's
``training_step_end`` hook, which ``fit`` calls after it has fetched the
step's metrics from the device (so the device is drained at every edge).
"""

from __future__ import annotations

import itertools
import os
import time

import jax

from benchmarks import check, stats, traffic, weights
from benchmarks.manifest import ROOT


STALL_S = 2.0      # a step this long has its stacks dumped to the log


def _adam_mu(opt_state):
    """The first moments inside an optax chain's state."""
    found = [s for s in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(s, "mu")]
    assert len(found) == 1, "expected one Adam state in the optimizer chain"
    return found[0].mu


def build_engine(ctx):
    """Recipe + overrides -> (cfg, engine), through the entry point's own
    calls (``tools/train.py``)."""
    from fleetx_tpu.core.engine import EagerEngine
    from fleetx_tpu.models import build_module
    from fleetx_tpu.optims import build_lr_scheduler, build_optimizer
    from fleetx_tpu.parallel.mesh import build_mesh, set_mesh
    from fleetx_tpu.utils import config as config_mod
    from fleetx_tpu.utils.check import check_config

    ctx.mark("program_imported")
    part = ctx.config["train"]
    cfg = config_mod.get_config(os.path.join(ROOT, part["recipe"]),
                                list(part["overrides"]),
                                num_devices=ctx.chips)
    check_config(cfg)
    mesh = set_mesh(build_mesh(cfg.get("Distributed"), devices=ctx.devices))
    module = build_module(cfg)
    ctx.mark("module_built")
    opt_cfg = dict(cfg.get("Optimizer") or {})
    lr = build_lr_scheduler(opt_cfg.get("lr"))
    engine = EagerEngine(cfg, module, optimizer=build_optimizer(opt_cfg, lr),
                         lr_schedule=lr, mesh=mesh)
    return cfg, engine


class Driver:
    """The feed and the step-end hook of one ``fit`` call."""

    def __init__(self, ctx, engine, ref, spec, batches):
        self.ctx, self.engine, self.spec = ctx, engine, spec
        self.ref, self.batches = ref, batches
        mix = ctx.mix
        self.check_steps = int(mix["check_steps"])
        self.warm_steps = self.check_steps + int(mix["warmup_steps"])
        self.step_ends: list = []
        self.losses: list = []
        self.program: dict = {}
        self.t_open = None
        self.stop = False
        self.tracing = False

    def __iter__(self):
        while not self.stop:
            with self.ctx.spans.span("make_batch"):
                batch = next(self.batches)
            yield batch

    def on_step_end(self, log_dict: dict) -> None:
        """A step has ended and its metrics are on the host: read what the
        check needs after the first steps, open and close the window."""
        ctx, n = self.ctx, len(self.step_ends) + 1
        ctx.spans.end("train_step")
        self.losses.append(float(log_dict["loss"]))
        if n <= self.warm_steps:
            ctx.mark(f"step_{n}_ended")
        if n == 1:
            paths = ctx.config["param_paths"]
            mu = weights.program_paths(paths, _adam_mu(
                self.engine.state.opt_state))
            b1 = float(ctx.config["train"]["optimizer"]["beta1"])
            self.program["grad_norms"] = {
                k: v / (1.0 - b1)
                for k, v in check.leaf_norms(self.ref, mu).items()}
        if n == self.check_steps:
            self.program["losses"] = list(self.losses)
            self.program["delta_norms"] = self._delta_norms()
            ctx.mark("program_norms_read")
        now = time.monotonic()
        self.step_ends.append(now)
        if n >= self.warm_steps and not self.stop:
            ctx.watch(STALL_S)
        if n == self.warm_steps:
            self.t_open = ctx.window_opens()
            self.tracing = ctx.trace
        elif self.t_open is not None:
            if self.tracing and now - self.t_open >= ctx.trace_seconds:
                self.tracing = False
                ctx.stop_trace()
            if now - self.t_open >= ctx.seconds and not self.tracing:
                # fit trains until max_steps, re-iterating a loader that
                # runs dry: the window's end is a max_steps it has reached
                self.stop = True
                self.engine.max_steps = 0
                ctx.watch(None)
        ctx.spans.begin("train_step")

    def _delta_norms(self) -> dict:
        """Norm of each leaf's change since the seeded weights, which are
        made again inside the one program that takes the norms."""
        params = weights.program_paths(self.ctx.config["param_paths"],
                                       self.engine.state.params)
        spec, ref = self.spec, self.ref

        def norms(params, root):
            w0 = weights.build(spec, root)
            return check.traced_leaf_norms(
                ref, {k: params[k] - w0[k] for k in params})

        got = jax.jit(norms)(params, weights.root_key(self.ctx.seed))
        return {k: float(v) for k, v in jax.device_get(got).items()}


def run(ctx) -> dict:
    """Build, run one ``fit`` (checked steps, warm-up, window), free the
    program, follow the first steps with the reference."""
    from fleetx_tpu.utils import env as env_mod

    env_mod.init_compile_cache()
    mix, sizes = ctx.mix, ctx.config
    ref = ctx.reference()
    spec = ref.weight_spec(sizes)
    with ctx.spans.span("build"):
        cfg, engine = build_engine(ctx)
        batches = traffic.train_batches(mix, ctx.seed, sizes["vocab_size"],
                                        ctx.chips)
        kept = [next(batches) for _ in range(int(mix["check_steps"]))]
        ctx.mark("engine_built")
        engine.prepare(kept[0])
        ctx.mark("engine_prepared")
        template = weights.program_paths(ctx.config["param_paths"],
                                         engine.state.params)
        w = weights.make(spec, ctx.seed,
                         {k: v.sharding for k, v in template.items()})
        engine.state = engine.state.replace(params=weights.to_program_tree(
            w, ctx.config["param_paths"], engine.state.params))
        del w, template
    ctx.build_done()

    driver = Driver(ctx, engine, ref, spec, itertools.chain(kept, batches))
    module = engine.module
    original = module.training_step_end

    def hook(log_dict):
        original(log_dict)
        driver.on_step_end(log_dict)

    module.training_step_end = hook
    ctx.spans.begin("train_step")
    engine.fit(driver)
    ctx.spans.end("train_step")
    rows = int(mix["sequences_per_chip"]) * ctx.chips
    n_steps, t_last = stats.steps_in_window(driver.step_ends, driver.t_open,
                                            ctx.seconds)
    window_s = t_last - driver.t_open
    ctx.window_closed()
    tokens_per_step = rows * int(mix["seq_len"])

    # the program's state is freed before the reference makes its own
    engine.state = None
    del engine, driver.engine
    ctx.free_program()

    opt = ctx.config["train"]["optimizer"]
    w = weights.make(spec, ctx.seed, ctx.reference_shardings(spec))
    placed = [ctx.place_batch(b) for b in kept]
    # on several chips a block takes one row from each
    per_block = int(mix.get("reference_rows_per_block", 1)) \
        if ctx.chips == 1 else ctx.chips
    reference = check.train_reference(
        ref, ctx.reference_optimizer(), sizes, opt, w, placed,
        rows_per_block=per_block)
    numbers = check.train_numbers(driver.program, reference)
    correct = check.judge(numbers, ctx.config["check"]["train"], ctx.err)
    if ctx.control:
        ctl = check.train_reference(
            ref, ctx.reference_optimizer(), sizes, opt, w, placed,
            precision=ctx.control, rows_per_block=per_block)
        ctx.control_numbers = check.train_numbers(ctl, reference)
    return {
        "correct": correct, "attempted": n_steps, "failed": 0,
        "numbers": numbers,
        "values": {
            "train_tokens_per_s": n_steps * tokens_per_step / window_s
            if n_steps else 0.0,
        },
        "facts": {
            "n_steps": n_steps, "window_s": window_s,
            "tokens_per_step": tokens_per_step, "rows": rows,
            "seq_len": int(mix["seq_len"]),
            "step_ms": 1e3 * window_s / max(n_steps, 1),
            "median_step_s": stats.percentile(
                [b - a for a, b in zip(driver.step_ends, driver.step_ends[1:])
                 if a >= driver.t_open], 50),
        },
    }
