"""Traffic kinds ``closed_loop`` and ``open_loop``: drive ``ServingEngine``.

The benchmark owns the loop: it calls ``engine.step()`` in the run's own
process, keeps the handles ``submit`` returns, and after every tick reads
which requests grew a token and when (the request's own
``first_token_at`` / ``last_token_at``, stamped by the engine on the host
clock after ``device_get``). Nothing outlives the run.
"""

from __future__ import annotations

import os
import time

from benchmarks import check, stats, traffic, weights
from benchmarks.manifest import ROOT


STALL_S = 1.0      # a tick this long has its stacks dumped to the log


def build_engine(ctx, spec):
    """Recipe + overrides -> ``ServingEngine``, through the family file
    that the recipe's ``Model.module`` names (``benchmarks/families/``):
    its template says which shape and dtype the engine holds each leaf in,
    the seeded weights are made in those (no float32 copy of a tree that is
    served in bfloat16 ever stands on the device, and the engine finds no
    leaf to cast), and its second call builds the engine around them, with
    an eos id no token can equal."""
    from fleetx_tpu.utils import config as config_mod

    part = ctx.config["serve"]
    cfg = config_mod.get_config(os.path.join(ROOT, part["recipe"]),
                                list(part["overrides"]),
                                num_devices=ctx.chips)
    family = ctx.manifest.family((cfg.get("Model") or {})["module"])
    model_cfg, template = family.served_template(cfg)
    paths = ctx.config["param_paths"]
    made = weights.make(spec, ctx.seed, dtypes={
        name: leaf.dtype
        for name, leaf in weights.program_paths(paths, template).items()})
    params = weights.to_program_tree(made, paths, template)
    ctx.mark("weights_made")
    return family.serving_engine(cfg, model_cfg, params,
                                 eos_token_id=int(part["eos_token_id"]),
                                 seed=ctx.seed % (2 ** 31))


class Loop:
    """Clients, handles and the token log around ``engine.step()``."""

    def __init__(self, ctx, engine):
        self.ctx, self.engine = ctx, engine
        self.log = stats.TokenLog()
        self.live: dict = {}        # request id -> (handle, plan, seen)
        self.done: list = []        # (plan, handle) finished
        self.failed = 0
        self.submitted = 0
        self.sent_at: dict = {}

    def submit(self, plan, start_s=None) -> None:
        """Send one planned request; latency runs from ``start_s`` (the due
        time in an open loop) or from the submission."""
        with self.ctx.spans.span("client_send"):
            handle = self.engine.submit(plan.prompt, plan.max_new,
                                        request_id=f"b{plan.index}")
        self.submitted += 1
        if handle.state == "refused":
            self.failed += 1
            return
        self.sent_at[plan.index] = handle.submitted_at
        self.log.open(handle.id, handle.submitted_at
                      if start_s is None else start_s)
        self.live[handle.id] = [handle, plan, 0]

    def tick(self) -> list:
        """One scheduler iteration; returns the plans that finished."""
        with self.ctx.spans.span("engine_step"):
            self.engine.step()
        finished = []
        with self.ctx.spans.span("read_handles"):
            for rid in list(self.live):
                handle, plan, seen = self.live[rid]
                grown = len(handle.tokens) - seen
                if grown:
                    # a request whose prefill ends in a tick also decodes
                    # in it: two tokens, first_token_at and last_token_at
                    times = [handle.last_token_at]
                    if seen == 0:
                        times = [handle.first_token_at] + (
                            [handle.last_token_at] if grown > 1 else [])
                    assert len(times) == grown, (rid, grown, seen)
                    self.log.note(rid, times)
                    self.live[rid][2] = seen + grown
                if handle.state == "finished":
                    del self.live[rid]
                    if handle.error:
                        self.failed += 1
                    else:
                        self.done.append((plan, handle))
                    finished.append(plan)
        return finished


def _counters(engine) -> dict:
    return {"tokens_total":
            engine.metrics.counter("serving_tokens_total").value,
            "engine_steps": engine.steps}


def _check_served(ctx, ref, spec, samples: list, pad_to: int) -> tuple:
    """``(numbers, correct)`` of the sampled requests against the reference
    (and, asked for, the control's numbers into ``ctx``). No sample, or
    one the configuration's position table cannot hold, is judged not
    correct; whatever else the reference raises ends the run."""
    if not samples:
        print("check: no request finished inside the window: nothing to "
              "compare, NOT CORRECT", file=ctx.err)
        return {}, False
    sizes = ctx.config
    source = weights.Source(spec, ctx.seed)
    try:
        got = check.served_logit_gaps(ref, sizes, source, samples, pad_to)
    except check.TooLong as e:
        print(f"check: {e}  NOT CORRECT", file=ctx.err)
        return {}, False
    numbers = {"served_logit_widest_gap": got["widest_gap"]}
    correct = check.judge(numbers, sizes["check"]["serve"], ctx.err)
    longest = max(len(p) + len(s) for p, s in samples)
    print(f"check: {got['tokens_compared']} served tokens of "
          f"{len(samples)} requests compared (longest request {longest} "
          f"tokens, rows {got['width']} wide)", file=ctx.err)
    if ctx.control:
        ctl = check.served_logit_gaps(ref, sizes, source, samples, pad_to,
                                      chooser=ctx.control)
        ctx.control_numbers = {"served_logit_widest_gap": ctl["widest_gap"]}
    return numbers, correct


def run(ctx) -> dict:
    """Build, fill the slots, measure the window, check the served tokens."""
    from fleetx_tpu.utils import env as env_mod

    env_mod.init_compile_cache()
    mix, sizes = ctx.mix, ctx.config
    ref = ctx.reference()
    spec = ref.weight_spec(sizes)
    with ctx.spans.span("build"):
        engine = build_engine(ctx, spec)
    ctx.build_done()
    loop = Loop(ctx, engine)
    vocab = int(sizes["vocab_size"])
    closed = mix["kind"] == "closed_loop"

    # ---- set-up: fill the slots; the window opens in steady state
    with ctx.spans.span("fill"):
        if closed:
            gen = traffic.ClosedLoop(mix, ctx.seed, vocab)
            first = gen.first(engine.serving.prefill_chunk)
            for plan in first:
                loop.submit(plan)
            waiting_first = {f"b{p.index}" for p in first}
            while waiting_first:
                ctx.watch(STALL_S)
                for plan in loop.tick():
                    loop.submit(gen.next_for(plan.client))
                waiting_first = {r for r in waiting_first
                                 if r in loop.live and loop.live[r][2] == 0}
        else:
            plan = traffic.open_loop_plan(mix, ctx.seed, vocab,
                                          ctx.seconds)
            warm = traffic.PlannedRequest(-1, -1, plan[0].prompt[:], 2, 2)
            loop.submit(warm)       # compiles both programs
            while loop.live:
                loop.tick()
    at_open = _counters(engine)
    before = loop.submitted - len(loop.live)   # answered during set-up
    occupancy, lateness, context_tokens, tick_s = [], [], [], []
    t_open = ctx.window_opens()
    tracing = ctx.trace

    # ---- the measured window
    nxt = 0
    while True:
        now = time.monotonic()
        if tracing and now - t_open >= ctx.trace_seconds:
            tracing = False
            ctx.stop_trace()
        if now - t_open >= ctx.seconds and not tracing:
            break
        if not closed:
            while nxt < len(plan) and t_open + plan[nxt].due_s <= now:
                loop.submit(plan[nxt], start_s=t_open + plan[nxt].due_s)
                lateness.append(loop.sent_at.get(plan[nxt].index, now)
                                - (t_open + plan[nxt].due_s))
                nxt += 1
            if not loop.live and nxt < len(plan):
                with ctx.spans.span("idle_wait"):
                    time.sleep(max(min(t_open + plan[nxt].due_s - now,
                                       0.005), 0.0))
                continue
        ctx.watch(STALL_S)
        for done in loop.tick():
            if closed:
                loop.submit(gen.next_for(done.client))
        tick_s.append(time.monotonic() - now)
        running = [(p, seen) for h, p, seen in loop.live.values()
                   if h.state == "running"]
        occupancy.append(len(running))
        context_tokens.append(sum(len(p.prompt) + seen
                                  for p, seen in running))
    ctx.watch(None)
    t_close = time.monotonic()
    at_close = _counters(engine)
    ctx.window_closed()

    finished = [(p.prompt, list(h.tokens)) for p, h in loop.done
                if h.finished_at and t_open < h.finished_at <= t_close]
    preempts = sum(h.preemptions for _, h in loop.done)
    slots = engine.serving.max_batch
    pool_pages = engine.serving.num_pages
    page_size = engine.serving.page_size
    paged_kernel = bool(engine.paged_kernel_active)

    # ---- free the program, then the reference makes its own weights
    del engine, loop.engine
    ctx.free_program()
    chk = mix["check"]
    samples = check.sample_served(finished, ctx.seed, int(chk["requests"]))
    numbers, correct = _check_served(ctx, ref, spec, samples,
                                     int(chk["pad_to"]))

    window_s = t_close - t_open
    if tick_s:      # a stalled host shows here, not in a percentile
        print(f"ticks: {len(tick_s)} in {window_s:.2f}s, median "
              f"{1e3 * stats.percentile(tick_s, 50):.1f} ms, longest "
              f"{1e3 * max(tick_s):.1f} ms", file=ctx.err)
    gaps = loop.log.gaps_in(t_open, t_close)
    ttft = loop.log.first_token_latencies_in(t_open, t_close)
    delta = {k: at_close[k] - at_open[k] for k in at_open}
    values = {
        "serve_out_tokens_per_s": stats.window_rate(
            at_open["tokens_total"], at_close["tokens_total"],
            t_open, t_close),
        "itl_p95_ms": 1e3 * (stats.percentile(gaps, 95) or 0.0),
        "ttft_mean_ms": 1e3 * (stats.mean(ttft) or 0.0),
    }
    return {
        "correct": correct,
        "attempted": loop.submitted - before, "failed": loop.failed,
        "numbers": numbers, "values": values,
        "facts": {
            "window_s": window_s, "n_gaps": len(gaps), "n_ttft": len(ttft),
            "ttft_s": ttft, "counters": delta,
            "occupancy": occupancy, "slots": slots, "preempts": preempts,
            "finished": len(finished), "finished_all": len(loop.done),
            "lateness_s": lateness, "context_tokens": context_tokens,
            "pool_pages": pool_pages, "page_size": page_size,
            "paged_kernel": paged_kernel,
        },
    }
