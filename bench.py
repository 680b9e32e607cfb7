"""Benchmark: GPT-345M pretraining throughput on the attached accelerator.

Baseline (BASELINE.md): the reference's only published single-card number —
GPT-345M, fp16 O2, seq_len 1024, local_bs 8 → ~16,200 tokens/s on 1x V100-32G
(``/root/reference/docs/quick_start.md:112-116``). ``vs_baseline`` is the
ratio of our measured tokens/s to that bar.

Prints ONE JSON line on success:
    {"metric": ..., "value": N, "unit": "tokens/s", "vs_baseline": N, ...}

It measures the chip and nothing else: the measurement runs in one child
process (a chip belongs to one process; this parent never imports JAX), the
child refuses any platform but ``tpu``, and a phase that fails fails the
run — the exit code is the child's, and no result is printed from a CPU.
The compile cache is wherever ``utils/env.init_compile_cache`` puts it.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

BASELINE_TOKENS_PER_S = 16200.0
# overridable so one child can measure a variant (seq-2048 amortisation,
# bs16 + parallel vocab head); the default is the reference bench config.
DEFAULT_BATCH, DEFAULT_SEQ = 8, 1024
BATCH = int(os.environ.get("FLEETX_BENCH_BS", DEFAULT_BATCH))
SEQ = int(os.environ.get("FLEETX_BENCH_SEQ", DEFAULT_SEQ))
VOCAB_CHUNK = int(os.environ.get("FLEETX_BENCH_VOCAB_CHUNK", 0))
# ZeRO sharding stage for the bench mesh (docs/zero_sharding.md): 2 turns
# on grad reduce-scatter + sharded update over an all-fsdp mesh; 0 keeps
# the plain data-parallel step. Single-device runs exercise the code path
# with fsdp=1 (constraints become no-ops).
ZERO_STAGE = int(os.environ.get("FLEETX_BENCH_ZERO_STAGE", 0))
# overlapped sharded update (docs/bandwidth_levers.md): with stage >= 2,
# params live on the grad shards and the allgather moves into the loss
# where it overlaps the next forward. Only meaningful with ZERO_STAGE >= 2.
OVERLAP_UPDATE = os.environ.get(
    "FLEETX_BENCH_OVERLAP_UPDATE", "").lower() in ("1", "true")
HIDDEN, LAYERS, VOCAB = 1024, 24, 50304


def _check_flash_numerics() -> str:
    """Compiled Pallas flash attention vs naive attention, on this backend;
    drift past the bf16 tolerance fails the run."""
    import jax
    import jax.numpy as jnp
    from fleetx_tpu.ops import flash_attention as fa

    rng = np.random.RandomState(0)
    shape = (2, 512, 8, 64)
    q = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    k = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    v = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    out = jax.jit(lambda q, k, v: fa.flash_attention(q, k, v, causal=True))(q, k, v)
    ref = jax.jit(lambda q, k, v: fa.reference_attention(
        q.astype(jnp.float32), k.astype(jnp.float32),
        v.astype(jnp.float32), causal=True))(q, k, v)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
    if not err < 2e-2:
        raise AssertionError(f"flash attention drifts from the reference "
                             f"on this chip: max |err| {err:.1e}")
    return f"flash-ok(err={err:.1e})"


def _bench_impl() -> dict:
    """The actual measurement; assumes the backend initializes."""
    import jax

    from fleetx_tpu.utils.env import init_compile_cache

    init_compile_cache()
    dev = jax.devices()[0]
    platform = dev.platform
    if platform != "tpu":
        raise SystemExit(f"bench.py measures the chip; JAX found platform "
                         f"{platform!r} ({dev.device_kind})")
    flash_status = _check_flash_numerics()
    layers, bsz, seq = LAYERS, BATCH, SEQ
    warmup, n_steps = 3, 10

    from fleetx_tpu.core.engine import EagerEngine
    from fleetx_tpu.core.module import GPTModule
    from fleetx_tpu.optims.lr_scheduler import build_lr_scheduler
    from fleetx_tpu.optims.optimizer import build_optimizer

    # recompute: the 16G-HBM v5e cannot hold bs8xseq1024 activations
    # (the 32G V100 baseline config relies on fp16 O2 + more memory); remat
    # is the reference's own recipe for this (pretrain_gpt_1.3B_dp8.yaml).
    # "dots" keeps matmul outputs (fastest that fits).
    granularity = os.environ.get("FLEETX_BENCH_RECOMPUTE", "dots")
    model_kwargs = {}
    if VOCAB_CHUNK:
        model_kwargs["vocab_chunk"] = VOCAB_CHUNK
    if os.environ.get("FLEETX_BENCH_SCAN_UNROLL"):
        model_kwargs["scan_unroll"] = int(os.environ["FLEETX_BENCH_SCAN_UNROLL"])
    # bf16 remat residuals (docs/bandwidth_levers.md): halves the backward's
    # scan-stacked residual DUS bytes when the saved values are wider
    remat_save_dtype = os.environ.get("FLEETX_BENCH_REMAT_SAVE_DTYPE")
    if remat_save_dtype:
        model_kwargs["remat_save_dtype"] = remat_save_dtype
    # fused single-pass flash backward A/B (docs/bandwidth_levers.md):
    # force either side; unset keeps the model default (on where the
    # kernel predicate admits the shape)
    fused_bwd_env = os.environ.get("FLEETX_BENCH_FUSED_BWD")
    if fused_bwd_env is not None:
        model_kwargs["flash_fused_bwd"] = \
            fused_bwd_env.lower() not in ("0", "false", "")
    # fused residual+LayerNorm A/B (docs/bandwidth_levers.md): force either
    # side; unset keeps the model default (on where the kernel predicate
    # admits the shape)
    fused_norm_env = os.environ.get("FLEETX_BENCH_FUSED_NORM")
    if fused_norm_env is not None:
        model_kwargs["fused_residual_norm"] = \
            fused_norm_env.lower() not in ("0", "false", "")
    cfg = {
        "Model": dict(vocab_size=VOCAB, hidden_size=HIDDEN, num_layers=layers,
                      num_attention_heads=16, ffn_hidden_size=4096,
                      max_position_embeddings=seq, use_recompute=True,
                      recompute_granularity=granularity, **model_kwargs),
        "Engine": {"max_steps": 10_000, "logging_freq": 100},
        # hardware-accelerated PRNG for dropout masks (measured ~8% step-time
        # saving vs threefry on v5e; same statistics, different stream)
        "Global": {"seed": 0, "prng_impl": "rbg"},
        # telemetry for the input-pipeline phase below: span histograms +
        # the data-stall integral, no Chrome trace (FLEETX_BENCH_TRACE
        # already covers the XLA-level capture)
        "Observability": {"enable": True, "trace": {"enable": False},
                          "output_dir": "./output/bench_telemetry"},
        # resilience runtime ON for the fit phase so guard/watchdog overhead
        # is auditable from the bench JSON (docs/resilience.md). The in-step
        # skip is disabled so the HEADLINE number measures the unmodified
        # train step; guard + watchdog are host-side only. The SDC sentinel
        # (FLEETX_BENCH_SDC_EVERY, default 0 = off — the loop is then
        # byte-identical) reports its cost as the separate sdc_sentinel
        # span below, never inside the headline step time.
        "Resilience": {"enable": True, "auto_resume": False,
                       "guard": {"skip_nonfinite_update": False},
                       "watchdog": {"enable": True, "min_timeout_s": 300.0,
                                    "action": "log"},
                       "integrity": {"sentinel_every": int(os.environ.get(
                           "FLEETX_BENCH_SDC_EVERY", "0")),
                           "sentinel_action": "log"}},
    }
    if ZERO_STAGE:
        cfg["Distributed"] = {
            "dp_degree": 1, "fsdp_degree": jax.device_count(),
            "sharding": {"sharding_stage": ZERO_STAGE,
                         "sharding_degree": jax.device_count(),
                         "overlap_update": OVERLAP_UPDATE}}
    module = GPTModule(cfg)
    lr = build_lr_scheduler({"max_lr": 3e-4, "warmup_steps": 100,
                             "decay_steps": 1000})
    opt = build_optimizer({"name": "AdamW"}, lr)
    engine = EagerEngine(cfg, module, optimizer=opt, lr_schedule=lr)

    rng = np.random.RandomState(0)
    tokens = rng.randint(0, VOCAB, size=(bsz, seq + 1)).astype(np.int32)
    batch = {
        "tokens": tokens[:, :-1],
        "position_ids": np.broadcast_to(
            np.arange(seq, dtype=np.int32), (bsz, seq)).copy(),
        "labels": tokens[:, 1:],
        "loss_mask": np.ones((bsz, seq), np.float32),
    }

    engine.prepare(batch)
    from fleetx_tpu.core.engine.eager_engine import _param_count
    n_params = _param_count(engine.state.params)
    sharded = engine.shard_batch(batch)
    with engine._ctx():
        for _ in range(warmup):
            engine.state, metrics = engine._train_step(engine.state, sharded)
        jax.block_until_ready(metrics["loss"])

        # optional profiler capture (FLEETX_BENCH_TRACE names the directory)
        trace_dir = os.environ.get("FLEETX_BENCH_TRACE")
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
        t0 = time.perf_counter()
        for _ in range(n_steps):
            engine.state, metrics = engine._train_step(engine.state, sharded)
        loss = float(jax.block_until_ready(metrics["loss"]))
        dt = (time.perf_counter() - t0) / n_steps
        if trace_dir:
            jax.profiler.stop_trace()

    tokens_per_s = bsz * seq / dt

    # ---- input-pipeline phase (docs/bandwidth_levers.md): drive the SAME
    # compiled step through engine.fit so the data path (host fetch +
    # per-leaf device_put sharding) is measured too, with the device-side
    # prefetch iterator gated by FLEETX_BENCH_PREFETCH (queue depth; 0 =
    # the serial fetch→shard→step loop). data_stall_frac and the span
    # means land in the JSON so the double-buffering A/B is auditable
    # from the bench output alone.
    prefetch_depth = int(os.environ.get("FLEETX_BENCH_PREFETCH", "2"))
    engine.prefetch_to_device = prefetch_depth
    engine.logging_freq = n_steps
    host_batches = [dict(batch) for _ in range(n_steps)]
    stall0 = engine.obs.stall_seconds_total()
    t0 = time.perf_counter()
    engine.fit(iter(host_batches))
    fit_wall = time.perf_counter() - t0
    stall_frac = ((engine.obs.stall_seconds_total() - stall0)
                  / max(fit_wall, 1e-9))
    # isolated update-phase timing (docs/zero_sharding.md): norm + clip +
    # optimizer + apply through the SAME closure train_step uses, recorded
    # as the optimizer_update span the loop below picks up
    engine.measure_update_phase()
    span_means_ms = {}
    for phase in ("data_fetch", "shard_batch", "shard_batch_async",
                  "optimizer_update", "sdc_sentinel"):
        summ = engine.obs.registry.histogram(phase).summary()
        if summ.get("count"):
            span_means_ms[phase] = round(summ["mean"] * 1000.0, 3)

    name = "gpt345m"
    variant = (bsz != DEFAULT_BATCH or seq != DEFAULT_SEQ
               or bool(VOCAB_CHUNK))
    if variant:
        name += f"_bs{bsz}_seq{seq}" + (f"_vc{VOCAB_CHUNK}" if VOCAB_CHUNK else "")
    result = {
        "metric": f"{name}_train_tokens_per_s_{platform}",
        "value": round(tokens_per_s, 1),
        "unit": "tokens/s",
        # the baseline bar is defined ONLY for the bs8/seq1024 345M recipe —
        # variant sweeps are recorded but not comparable
        "vs_baseline": (round(tokens_per_s / BASELINE_TOKENS_PER_S, 3)
                        if not variant else 0.0),
        "step_time_s": round(dt, 4),
        "batch_size": bsz,
        "loss": round(loss, 3),
        "flash": flash_status,
        "platform": platform,
        "device_kind": dev.device_kind,
        "device_count": jax.device_count(),
        # input-pipeline evidence: fraction of the fit phase's wall time the
        # consumer loop was host-blocked on data (fetch + on-path sharding),
        # plus per-phase span means; with prefetch on, shard_batch_async
        # replaces shard_batch and the stall integral excludes it
        "data_stall_frac": round(stall_frac, 4),
        "span_means_ms": span_means_ms,
        "prefetch_depth": prefetch_depth,
        "fit_step_time_s": round(fit_wall / n_steps, 4),
        # ZeRO-2 evidence (docs/zero_sharding.md): bytes of grad leaves the
        # stage-2 constraint distributes over fsdp (0 below stage 2 or on a
        # 1-device mesh), next to the stage the mesh ran
        "zero_stage": engine.sharding_stage,
        "grad_bytes_sharded": int(
            engine.obs.registry.gauge("grad_bytes_sharded").value or 0),
        # gang observability evidence (docs/observability.md "Multi-host"):
        # mean milliseconds spent waiting inside coordination agreements
        # (0.0 on single-process runs — the LocalCoordinator issues none),
        # this rank's rolling arrival skew, and whether the crash flight
        # recorder was armed — so BENCH_*.json trajectories capture
        # coordination overhead from this PR on
        "barrier_wait_ms": round(
            engine.obs.registry.histogram("barrier_wait_ms")
            .summary().get("mean") or 0.0, 3),
        "rank_skew": round(
            float(engine.obs.registry.gauge("rank_skew").value or 0.0), 6),
        "flight_recorder": engine.obs.flight is not None,
        # resilience counters (docs/resilience.md): all-zero on a healthy
        # run; fit_step_time_s vs step_time_s bounds the guard/watchdog
        # overhead since both run the same compiled step
        "resilience": {
            k: int(engine.obs.registry.counter(k).value)
            for k in ("nonfinite_skips", "nonfinite_windows",
                      "rollbacks_total", "ckpt_retries_total",
                      "preemption_exits", "watchdog_stalls",
                      "ckpt_gc_total",
                      # state-integrity evidence (docs/resilience.md
                      # "Integrity"): sentinel checks/mismatches and
                      # checkpoint digest verification outcomes — all-zero
                      # mismatches on healthy hardware
                      "sdc_checks_total", "sdc_replay_mismatches",
                      "sdc_fingerprint_mismatches", "sdc_quarantines",
                      "ckpt_verify_total", "ckpt_verify_failed",
                      "ckpt_verify_fallbacks", "ckpt_commit_aborts",
                      "download_checksum_mismatches")},
    }
    if remat_save_dtype:
        result["remat_save_dtype"] = remat_save_dtype
    # which backward the flash kernel compiled: the config knob AND the
    # kernel predicate for this config's attention shape — a shape the
    # predicate rejects reports False even with the knob on, so the
    # gpt_fusedbwd A/B and the flash_bwd_passes row can never contradict
    import jax.numpy as jnp

    from fleetx_tpu.ops import flash_attention as fa
    from fleetx_tpu.ops import fused_norm as fnorm

    mc = module.model_cfg
    q_abs = jax.ShapeDtypeStruct(
        (bsz, seq, mc.num_attention_heads, mc.head_dim), jnp.bfloat16)
    result["flash_fused_bwd"] = bool(
        mc.flash_fused_bwd and fa.supported(q_abs, q_abs)
        and fa.fused_backward_supported(q_abs, q_abs))
    # which norm path compiled (docs/bandwidth_levers.md): the config knob
    # AND the fused_norm kernel predicate for this config's activation
    # shape — 0/1 ints (perf_gate's numeric schema rejects bools), so the
    # gpt_fusednorm A/B and the perf_elementwise_ms band stay consistent
    x_abs = jax.ShapeDtypeStruct((bsz, seq, mc.hidden_size), mc.dtype)
    result["norm_fused"] = int(bool(
        mc.fused_residual_norm and fnorm.fused_norm_supported(x_abs, x_abs)))
    # overlapped sharded update evidence: what the ENGINE resolved — the
    # gather shardings exist only when the knob survived the stage>=2 /
    # fsdp>1 gates (the engine demotes it with a warning otherwise, never
    # silently), i.e. exactly when the step really gathers inside the loss
    result["update_overlapped"] = int(
        getattr(engine, "_param_gather_shardings", None) is not None)

    # HBM attribution (docs/performance.md): measured peak vs auto_layout's
    # prediction for this exact config
    hbm = engine.mem.snapshot()
    result["hbm_stats"] = "ok" if hbm.get("available") else "unavailable"
    result["hbm_peak_bytes"] = hbm.get("peak_bytes")
    result["hbm_model_error"] = hbm.get("model_error")

    # trace decomposition (docs/performance.md): when FLEETX_BENCH_TRACE
    # armed a profiler capture, score it so the result carries the MFU-gap
    # report next to the tokens/s it explains
    if trace_dir:
        from fleetx_tpu.observability import perf as perf_mod
        from fleetx_tpu.utils.hardware import gpt_flops_per_token, roofline

        flops = gpt_flops_per_token(layers, HIDDEN, seq,
                                    num_params=n_params) * bsz * seq
        rep = perf_mod.analyze(trace_dir, flops_per_step=flops,
                               roofline=roofline(dev.device_kind))
        result["decomposition"] = perf_mod.summary(rep)
        # headline rows for tools/perf_gate.py: backward flash kernel
        # passes per layer (1 fused vs 3 split — exact-match gated) and
        # the backward scan's per-layer time under the gauge name the
        # engine's perf stream uses
        passes = result["decomposition"].get("bwd_flash_passes_per_layer")
        if passes is not None:
            result["flash_bwd_passes"] = passes
        bwd_ms = result["decomposition"].get("bwd_scan_ms_per_layer")
        if bwd_ms is not None:
            result["perf_bwd_ms_per_layer"] = bwd_ms
        # the elementwise line the fused-norm kernel deletes (its time
        # moves to the fused_norm category) — band-gated lower-is-better
        # by tools/perf_gate.py
        elem_ms = (rep.get("categories_ms_per_step") or {}).get("elementwise")
        if elem_ms is not None:
            result["perf_elementwise_ms"] = elem_ms

    # fine-tune micro-bench (docs/finetune.md): adapter step time +
    # trainable fraction + artifact bytes, gated by perf_gate's
    # FINETUNE_METRICS. FLEETX_BENCH_FINETUNE=0 skips the phase (it
    # compiles a second, small program).
    if os.environ.get("FLEETX_BENCH_FINETUNE", "1") not in ("0", "false"):
        result["finetune"] = _finetune_bench()

    from fleetx_tpu.utils.hardware import gpt_flops_per_token, peak_flops

    # the default mesh data-parallelizes over every local device — MFU is
    # per-chip, so divide by the device count
    flops = gpt_flops_per_token(layers, HIDDEN, seq,
                                num_params=n_params) * bsz * seq
    result["mfu"] = round(
        flops / dt / (peak_flops(dev) * jax.device_count()), 4)
    return result


def _finetune_bench() -> dict:
    """LoRA fine-tune micro-bench (docs/finetune.md): a small fixed-shape
    GPT with injected adapters under the masked optimizer — deliberately
    NOT the headline config, so the phase costs seconds on any backend.
    Emits the three gated keys (tools/perf_gate.py FINETUNE_METRICS):
    the adapter train-step time, the trainable-fraction gauge (exact-
    matched — it is a deterministic ratio of this config) and the
    adapter-only artifact's payload bytes, plus the bytes-vs-base ratio
    the <5% acceptance bound reads."""
    import shutil
    import tempfile

    import jax

    from fleetx_tpu.core.engine import EagerEngine
    from fleetx_tpu.finetune import checkpoint as ft_ckpt
    from fleetx_tpu.finetune import lora
    from fleetx_tpu.finetune.module import LoRAGPTModule
    from fleetx_tpu.optims.lr_scheduler import build_lr_scheduler
    from fleetx_tpu.optims.optimizer import build_optimizer

    bsz = max(2 * jax.device_count(), 4)
    seq, rank, alpha = 128, 8, 16.0
    cfg = {
        "Model": dict(vocab_size=8192, hidden_size=256, num_layers=4,
                      num_attention_heads=8, max_position_embeddings=seq,
                      use_flash_attention=False,
                      module="LoRAGPTModule"),
        "FineTune": {"lora": {"rank": rank, "alpha": alpha}},
        "Engine": {"max_steps": 10_000, "logging_freq": 100},
        "Global": {"seed": 0},
    }
    module = LoRAGPTModule(cfg)
    lr = build_lr_scheduler({"max_lr": 1e-4, "warmup_steps": 10,
                             "decay_steps": 100})
    opt = lora.lora_optimizer(build_optimizer({"name": "AdamW"}, lr))
    engine = EagerEngine(cfg, module, optimizer=opt, lr_schedule=lr)
    rng = np.random.RandomState(1)
    tokens = rng.randint(0, 8192, size=(bsz, seq + 1)).astype(np.int32)
    batch = {"tokens": tokens[:, :-1],
             "position_ids": np.broadcast_to(
                 np.arange(seq, dtype=np.int32), (bsz, seq)).copy(),
             "labels": tokens[:, 1:],
             "loss_mask": np.ones((bsz, seq), np.float32)}
    engine.prepare(batch)
    sharded = engine.shard_batch(batch)
    with engine._ctx():
        for _ in range(2):  # compile + warm
            engine.state, metrics = engine._train_step(engine.state,
                                                       sharded)
        jax.block_until_ready(metrics["loss"])
        n_steps = 5
        t0 = time.perf_counter()
        for _ in range(n_steps):
            engine.state, metrics = engine._train_step(engine.state,
                                                       sharded)
        jax.block_until_ready(metrics["loss"])
        dt = (time.perf_counter() - t0) / n_steps
    frac = lora.trainable_params_frac(engine.state.params)
    tmp = tempfile.mkdtemp(prefix="fleetx_ft_bench_")
    try:
        path = ft_ckpt.save_adapter(tmp, 0, engine.state.params,
                                    base_dir=None, rank=rank, alpha=alpha)
        adapter_nbytes = ft_ckpt.adapter_bytes(path)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    # actual bytes of the BASE tree only (adapters excluded, real dtype
    # widths) — the denominator the <5% acceptance bound compares against
    base_tree, _ = lora.split_adapters(engine.state.params)
    base_bytes = sum(int(leaf.nbytes)
                     for leaf in jax.tree.leaves(base_tree))
    return {
        "adapter_step_time_s": round(dt, 5),
        "trainable_params_frac": round(frac, 6),
        "adapter_ckpt_bytes": int(adapter_nbytes),
        "adapter_bytes_vs_base": round(adapter_nbytes
                                       / max(base_bytes, 1), 5),
        "batch_size": bsz,
        "lora_rank": rank,
    }


def main() -> int:
    if os.environ.get("FLEETX_BENCH_CHILD"):
        print(json.dumps(_bench_impl()))
        return 0
    # one child holds the chip; this parent never imports JAX
    return subprocess.run(
        [sys.executable, os.path.abspath(__file__)],
        env=dict(os.environ, FLEETX_BENCH_CHILD="1")).returncode


if __name__ == "__main__":
    sys.exit(main())
