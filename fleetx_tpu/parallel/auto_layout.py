"""Automatic mesh-layout planning — the TPU-native half of the reference's
auto-parallel stack.

The reference's semi-auto path (``ppfleetx/models/language_model/gpt/auto/
auto_utils.py:24-108`` + ``utils/config.py:418-444``) builds a ProcessMesh
from USER-supplied degrees and lets the framework place collectives; the
placement half is GSPMD here (``AutoEngine`` docstring). This module supplies
the other half the reference leaves to the user: choosing the degrees.

``suggest_layout`` picks ``(dp, fsdp, mp, pp, seq)`` for a model + device
count from a first-order memory model and TPU cost preferences:

- the memory model (``estimate_memory_terms``) is stage-aware
  (docs/zero_sharding.md): ZeRO stage 1 shards the Adam moments over
  ``fsdp``; stage 2 additionally shards the f32 gradients / accumulation
  carry (``parallel/sharding.zero_grad_specs`` — the engine constrains the
  grad pytree in-step, so the grad bytes divide by ``fsdp`` too); stage 3
  shards the weights as well.  The planner starts at stage 2 and escalates
  to 3 when the replicated weight bytes alone blow the budget;
- activations shard over mp/pp/seq but NOT fsdp, so when the activation
  term alone exceeds the budget the planner grows mp/pp before fsdp could
  burn the device budget without helping;
- axis preference order is fsdp (ZeRO — cheapest collectives, rides the
  same all-reduce dp already pays) → mp (tensor — adds per-layer
  collectives, capped at 8 and by head divisibility) → pp (adds the
  pipeline ramp). Models ≥ ~50B params invert to mp-then-pp (the
  megatron-style recipe: tensor inside a chip group, pipeline across),
  matching the reference's own 175B mp8×pp16 layout;
- long-context configs (``max_position_embeddings`` ≥ 4096) reserve a
  ``seq`` factor for ring attention when devices remain;
- whatever is left becomes dp.
"""

from __future__ import annotations

from fleetx_tpu.parallel.rules import stage_shards
from fleetx_tpu.utils.log import logger

_MOMENT_BYTES_PER_PARAM = 8.0  # 2 × f32 Adam moments — fsdp shards at stage ≥ 1
_GRAD_BYTES_PER_PARAM = 4.0    # f32 grads / accum carry — fsdp shards at stage ≥ 2
_WEIGHT_BYTES_PER_PARAM = 6.0  # f32 params + bf16 compute copy — stage 3 only
# activations are modelled explicitly (estimate_memory_terms), so the
# planning budget only reserves compiler workspace / fragmentation slack
_HBM_BUDGET_FRACTION = 0.9


def estimate_params(model: dict) -> int:
    """First-order GPT-family parameter count from a ``Model:`` section."""
    h = int(model.get("hidden_size") or 1024)
    layers = int(model.get("num_layers") or 24)
    ffn = int(model.get("ffn_hidden_size") or 4 * h)
    vocab = int(model.get("vocab_size") or 50304)
    seq = int(model.get("max_position_embeddings") or 1024)
    per_layer = 4 * h * h + 2 * h * ffn + 9 * h  # qkv+out + mlp + norms/bias
    return layers * per_layer + vocab * h + seq * h


# Activation bytes per (token · hidden · layer), by recompute granularity.
# A first-order model that errs on the safe side. On the 15.75 GB v5-lite
# chip GPT-345M seq 1024 with "dots" remat at 8 sequences holds
# `hbm_peak_gb` 14.17 (ledger, PR 30: peak in use + peak reserved, an upper
# bound); other batch sizes: not measured on the chip (ROADMAP S11).
# "none" follows the Megatron selective-recompute accounting (~34 bytes
# per token·hidden per layer plus the s² attention scores); "full" keeps
# only layer-boundary activations plus one layer's working set.
_ACT_BYTES = {"none": 34.0, "core_attn": 16.0, "full_attn": 14.0,
              "dots": 14.0, "full": 4.0}


def estimate_memory_terms(model: dict, micro_batch: int = 1,
                          recompute: str | None = "dots") -> dict:
    """Unsharded per-term HBM bytes of one training step.

    ``moments`` — the 2 f32 Adam moments (what ZeRO 1+ shards and what
    offload streams to host); ``grads`` — the f32 gradient buffer /
    accumulation carry (what stage 2 additionally shards over ``fsdp`` —
    halved when ``Model.grad_accum_dtype`` is bfloat16); ``weights`` —
    f32 params + the bf16 compute copy (sharded only by mp/pp, and by
    fsdp at stage 3); ``act`` — activations at the recompute granularity
    plus the LM-head logits block (full ``[b, s, V]`` f32 + gradient
    unless ``Model.vocab_chunk`` caps it at chunked blocks).
    """
    n_params = float(estimate_params(model))
    h = int(model.get("hidden_size") or 1024)
    layers = int(model.get("num_layers") or 24)
    seq = int(model.get("max_position_embeddings") or 1024)
    vocab = int(model.get("vocab_size") or 50304)
    k = _ACT_BYTES.get(recompute or "none", _ACT_BYTES["none"])
    act = k * micro_batch * seq * h * layers
    if (recompute or "none") == "none":
        act += 2.0 * micro_batch * seq * seq * layers * \
            int(model.get("num_attention_heads") or 16)
    head_cols = int(model.get("vocab_chunk") or 0) or vocab
    act += 8.0 * micro_batch * seq * min(head_cols, vocab)  # logits f32 + grad
    grad_bytes = _GRAD_BYTES_PER_PARAM
    if str(model.get("grad_accum_dtype") or "") == "bfloat16":
        grad_bytes /= 2.0  # bf16 accumulation carry (docs/zero_sharding.md)
    return {"moments": _MOMENT_BYTES_PER_PARAM * n_params,
            "grads": grad_bytes * n_params,
            "weights": _WEIGHT_BYTES_PER_PARAM * n_params,
            "act": act}


def estimate_step_hbm_bytes(model: dict, micro_batch: int = 1,
                            recompute: str | None = "dots") -> float:
    """Single-device HBM high-water estimate (sum of the memory terms)."""
    return sum(estimate_memory_terms(model, micro_batch, recompute).values())


def _per_device_bytes(terms: dict, fsdp: int, mp: int, pp: int, seq: int,
                      stage: int, overlap: bool = False) -> float:
    """Shard the memory terms by what each ZeRO stage actually shards.

    The stage→term table is the registry's (``parallel/rules.py``
    ``ZERO_STAGE_TERMS``/``stage_shards``) — the same data that gates the
    engine's ``zero_sharding``/``zero_grad_specs`` calls, so the memory
    model and the runtime cannot disagree about what a stage distributes.

    ``overlap`` is the engine's ``sharding.overlap_update``: params LIVE on
    the grad shards between steps and the step gathers a full transient
    copy inside the loss, so the weights peak grows by the resident
    ``1/fsdp`` shard riding alongside the gathered copy — overlap buys step
    time (the allgather hides under the forward), not memory.
    """
    mpp = max(mp * pp, 1)
    state = sum(
        terms[term] / (mpp * (fsdp if stage_shards(term, stage) else 1))
        for term in ("moments", "grads", "weights"))
    if overlap and stage >= 2 and fsdp > 1 \
            and not stage_shards("weights", stage):
        state += terms["weights"] / (mpp * fsdp)
    return state + terms["act"] / (mpp * max(seq, 1))


def predicted_step_bytes(model: dict, degrees: dict | None = None,
                         micro_batch: int = 1,
                         recompute: str | None = "dots") -> float:
    """Per-device HBM high-water PREDICTION for an active config.

    The public face of ``_per_device_bytes`` for the observability layer
    (``observability/memory.py``): the measured peak from
    ``device.memory_stats()`` is scored against this number as
    ``hbm_model_error``, closing the loop on the model that decides
    offload and stage escalation (``suggest_layout`` / ``offload_is_needed``
    plan with exactly these bytes). ``degrees`` is a ``Distributed``-style
    dict (``fsdp_degree``/``mp_degree``/``pp_degree``/``seq_degree`` +
    optional ``sharding`` sub-dict); absent axes default to 1.
    """
    deg = dict(degrees or {})
    sh = deg.get("sharding") or {}
    fsdp = int(deg.get("fsdp_degree") or sh.get("sharding_degree") or 1)
    stage = int(sh.get("sharding_stage") or (2 if fsdp > 1 else 0))
    terms = estimate_memory_terms(model, micro_batch, recompute)
    return _per_device_bytes(
        terms, fsdp, int(deg.get("mp_degree") or 1),
        int(deg.get("pp_degree") or 1), int(deg.get("seq_degree") or 1),
        stage, overlap=bool(sh.get("overlap_update")))


def advice_inputs(config: dict,
                  data_world: int | None = None) -> tuple[dict, int, str | None]:
    """(model dict, micro batch, recompute granularity) for the memory
    model, from a raw config — the shared fallback chain used by both the
    planner call site (``utils/config.get_config``) and the engine's
    offload advisory, so the two cannot drift.

    Fallback order for the batch: explicit micro → explicit local →
    ``global_batch_size / data_world`` (configs may set only the global
    batch and let the local derive after planning —
    ``utils/config.process_global_configs``; without this rung the
    activation term would be 1/batch of reality) → 1.
    """
    g = config.get("Global") or {}
    mb = g.get("micro_batch_size") or g.get("local_batch_size")
    if not mb and g.get("global_batch_size") and data_world:
        mb = max(int(g["global_batch_size"]) // max(int(data_world), 1), 1)
    mdl = dict(config.get("Model") or {})
    gran = (mdl.get("recompute_granularity") or "full") \
        if mdl.get("use_recompute") else "none"
    return mdl, int(mb or 1), gran


def offload_is_needed(model: dict, degrees: dict, micro_batch: int = 1,
                      recompute: str | None = "dots",
                      hbm_gb: float = 16.0) -> bool:
    """Should Adam-state offload be on for this config? True only when the
    per-device step estimate exceeds HBM — offload is a fit-enabler, not an
    optimisation: the f32 moments stream over PCIe every step (its cost:
    not measured on the chip, ROADMAP S10), so
    a config that fits without it should keep it off. The engine warns on
    that mismatch (``eager_engine.py``). Applies the planner's workspace
    slack (``_HBM_BUDGET_FRACTION``) so the advice and the plan agree on
    what "fits" means. Shares ``predicted_step_bytes`` with the HBM
    monitor's ``hbm_model_error`` so the offload decision and the
    measured-peak scoring can never use two drifting byte models."""
    per_dev = predicted_step_bytes(model, degrees, micro_batch, recompute)
    return per_dev > hbm_gb * (1 << 30) * _HBM_BUDGET_FRACTION


def suggest_layout(model: dict, n_devices: int, hbm_gb: float = 16.0,
                   micro_batch: int = 1,
                   recompute: str | None = "dots") -> dict:
    """→ ``Distributed``-section degrees whose product is ``n_devices``.

    Deterministic and purely static — suitable for config-time planning on
    any host (no devices touched). ``micro_batch``/``recompute`` feed the
    activation half of the memory model (VERDICT r4 weak #6: state-only
    ``fits()`` could pass layouts that OOM at the recipe's real batch).
    """
    n_params = estimate_params(model)
    heads = int(model.get("num_attention_heads") or 16)
    layers = int(model.get("num_layers") or 24)
    seq_len = int(model.get("max_position_embeddings") or 1024)
    budget = hbm_gb * (1 << 30) * _HBM_BUDGET_FRACTION
    terms = estimate_memory_terms(model, micro_batch, recompute)
    # megatron-style for huge models, ZeRO-first otherwise
    order = (("mp", "pp", "fsdp") if n_params >= 50e9
             else ("fsdp", "mp", "pp"))

    def plan(stage: int) -> dict:
        deg = {"fsdp": 1, "mp": 1, "pp": 1, "seq": 1}

        def product() -> int:
            return deg["fsdp"] * deg["mp"] * deg["pp"] * deg["seq"]

        def fits() -> bool:
            return _per_device_bytes(terms, deg["fsdp"], deg["mp"],
                                     deg["pp"], deg["seq"], stage) <= budget

        def can_double(axis: str) -> bool:
            # divisibility, not just capacity: on e.g. 24 devices fsdp must
            # stop at 8 (leaving dp=3), not run to 16 and fail the divmod
            if n_devices % (product() * 2):
                return False
            if axis == "mp":
                return deg["mp"] < 8 and heads % (deg["mp"] * 2) == 0
            if axis == "pp":
                return layers % (deg["pp"] * 2) == 0
            if axis == "fsdp":
                return deg["fsdp"] < 16
            return True

        # activations shard over mp/pp (not fsdp): when they alone blow
        # the budget, tensor/pipeline must grow first or the fsdp loop
        # below would burn the whole device budget without helping
        for axis in ("mp", "pp"):
            while terms["act"] / (deg["mp"] * deg["pp"]) > budget and \
                    can_double(axis):
                deg[axis] *= 2
        for axis in order:
            while not fits() and can_double(axis):
                deg[axis] *= 2

        if seq_len >= 4096:
            while deg["seq"] < 4 and n_devices % (product() * 2) == 0 and \
                    seq_len % (256 * deg["seq"] * 2) == 0:
                deg["seq"] *= 2
        deg["_fits"] = fits()
        deg["_stage"] = stage
        return deg

    deg = plan(2)
    if not deg["_fits"]:
        # stage 2 shards moments + grads but keeps the f32 params/bf16
        # copy replicated (parallel/sharding.zero_grad_specs); escalate to
        # full param sharding and re-plan before giving up
        deg3 = plan(3)
        if deg3["_fits"] or deg3["fsdp"] > 1:
            deg = deg3
    fit, stage = deg.pop("_fits"), deg.pop("_stage")

    dp, rem = divmod(n_devices, deg["fsdp"] * deg["mp"] * deg["pp"] * deg["seq"])
    if rem:
        raise ValueError(
            f"auto layout {deg} does not divide {n_devices} devices")
    out = {
        "dp_degree": dp,
        "fsdp_degree": deg["fsdp"],
        "mp_degree": deg["mp"],
        "pp_degree": deg["pp"],
        "seq_degree": deg["seq"],
    }
    if deg["fsdp"] > 1:
        out["sharding"] = {"sharding_stage": stage,
                           "sharding_degree": deg["fsdp"]}
    if not fit:
        per_dev = _per_device_bytes(terms, deg["fsdp"], deg["mp"],
                                    deg["pp"], deg["seq"], stage)
        logger.warning(
            "auto layout: %.1fGB state+activations per device exceeds the "
            "%.1fGB budget even at %s — expect recompute/offload to be "
            "required", per_dev / (1 << 30), budget / (1 << 30), out)
    logger.info("auto layout for %.2fB params on %d devices: %s",
                n_params / 1e9, n_devices, out)
    return out
