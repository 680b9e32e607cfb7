"""YAML config system with ``_base_`` inheritance and dotted CLI overrides.

Re-designs the reference config layer (``ppfleetx/utils/config.py:120-482``):
same user-facing semantics — ``_base_:`` file inheritance with
``_inherited_: false`` opt-out per sub-dict, ``-o Key.Sub=val`` dotted
overrides, and derivation of the dp degree and of the
global/local/micro-batch-size relations — but the distributed section now
describes a named TPU mesh ``(pipe, data, fsdp, seq, tensor)`` instead of NCCL
hybrid process groups.
"""

from __future__ import annotations

import argparse
import ast
import copy
import os
from typing import Any

import yaml

from fleetx_tpu.utils.log import logger

__all__ = [
    "AttrDict",
    "parse_config",
    "override_config",
    "get_config",
    "parse_args",
    "process_dist_config",
    "process_global_configs",
    "process_observability_config",
    "process_resilience_config",
    "print_config",
]


class AttrDict(dict):
    """Recursive attribute-access dict (reference ``config.py:120-144``)."""

    def __getattr__(self, key: str) -> Any:
        try:
            return self[key]
        except KeyError as e:  # pragma: no cover - mirrors dict semantics
            raise AttributeError(key) from e

    def __setattr__(self, key: str, value: Any) -> None:
        self[key] = value

    def __deepcopy__(self, memo: dict) -> "AttrDict":
        return AttrDict({copy.deepcopy(k, memo): copy.deepcopy(v, memo) for k, v in self.items()})

    def setdefault_tree(self, path: str, value: Any) -> Any:
        """setdefault through a dotted path, creating AttrDicts on the way."""
        node = self
        keys = path.split(".")
        for k in keys[:-1]:
            if k not in node or not isinstance(node[k], dict):
                node[k] = AttrDict()
            node = node[k]
        return node.setdefault(keys[-1], value)


def create_attr_dict(d: dict) -> AttrDict:
    """Recursively wrap nested dicts as AttrDict in place."""
    out = AttrDict()
    for k, v in d.items():
        out[k] = create_attr_dict(v) if isinstance(v, dict) else v
    return out


def _merge(base: dict, child: dict) -> dict:
    """Deep-merge ``child`` over ``base``.

    A child sub-dict containing ``_inherited_: false`` replaces the base
    sub-dict wholesale instead of merging (reference ``config.py:163-202``).
    """
    out = copy.deepcopy(base)
    for k, v in child.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            if v.get("_inherited_") is False:
                v = {kk: vv for kk, vv in v.items() if kk != "_inherited_"}
                out[k] = copy.deepcopy(v)
            else:
                out[k] = _merge(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


def parse_config(cfg_file: str) -> AttrDict:
    """Load a YAML config, resolving ``_base_`` inheritance recursively."""
    with open(cfg_file, "r") as f:
        raw = yaml.safe_load(f) or {}
    base_file = raw.pop("_base_", None)
    if base_file is not None:
        base_path = os.path.join(os.path.dirname(cfg_file), base_file)
        base = parse_config(base_path)
        raw = _merge(base, raw)
    return create_attr_dict(raw)


def _literal(v: str) -> Any:
    try:
        return ast.literal_eval(v)
    except (ValueError, SyntaxError):
        return v


def override_config(config: AttrDict, options: list[str] | None = None) -> AttrDict:
    """Apply ``Key.Sub=value`` dotted overrides (reference ``config.py:248-310``)."""
    if not options:
        return config
    for opt in options:
        assert "=" in opt, f"option '{opt}' must be of form Key.Sub=value"
        key, value = opt.split("=", 1)
        node: Any = config
        parts = key.split(".")
        for p in parts[:-1]:
            if p not in node:
                node[p] = AttrDict()
            node = node[p]
        node[parts[-1]] = _literal(value)
    return config


# ---------------------------------------------------------------------------
# Post-processing: distributed degrees and batch-size derivation
# ---------------------------------------------------------------------------

# one axis-name source for config validation, lint and runtime alike.
# NOTE: this import routes through fleetx_tpu.parallel/__init__ and thus
# pulls jax — no cost change here (fleetx_tpu.utils already imports jax
# via env.py), and lint never imports this module (it AST-parses
# parallel/rules.py instead)
from fleetx_tpu.parallel.rules import MESH_AXES  # noqa: E402


def process_dist_config(config: AttrDict, num_devices: int | None = None) -> AttrDict:
    """Validate/derive mesh degrees from the device count.

    Mirrors the degree math of the reference (``config.py:30-65``): any degree
    left unset (None/absent) is derived so the product equals the number of
    devices, with ``data`` the free axis by default.
    """
    if num_devices is None:
        import jax

        num_devices = jax.device_count()
    dist = config.setdefault("Distributed", AttrDict())
    degrees = {
        "pp_degree": int(dist.get("pp_degree") or 1),
        "fsdp_degree": int(dist.get("fsdp_degree") or dist.get("sharding", {}).get("sharding_degree") or 1),
        "seq_degree": int(dist.get("seq_degree") or 1),
        "mp_degree": int(dist.get("mp_degree") or 1),
    }
    fixed = degrees["pp_degree"] * degrees["fsdp_degree"] * degrees["seq_degree"] * degrees["mp_degree"]
    dp = dist.get("dp_degree")
    if dp in (None, -1):
        assert num_devices % fixed == 0, (
            f"device count {num_devices} not divisible by pp*fsdp*seq*mp={fixed}")
        dp = num_devices // fixed
    dp = int(dp)
    assert dp * fixed == num_devices, (
        f"dp({dp}) * pp*fsdp*seq*mp({fixed}) != device count ({num_devices})")
    dist.dp_degree = dp
    for k, v in degrees.items():
        dist[k] = v
    sharding = dist.setdefault("sharding", AttrDict())
    sharding.setdefault("sharding_degree", degrees["fsdp_degree"])
    sharding.setdefault("sharding_stage", 1 if degrees["fsdp_degree"] > 1 else 0)
    sharding.setdefault("sharding_offload", False)
    sharding.setdefault("overlap_update", False)
    return config


def process_global_configs(config: AttrDict) -> AttrDict:
    """Resolve global/local/micro batch relations (reference ``config.py:68-117``).

    data-parallel world = dp_degree * fsdp_degree (the reference treats
    dp x sharding as the data axis, ``utils/env.py:76-96``)::

        global = local * dp_world ;  accumulate_steps = local // micro
    """
    glb = config.setdefault("Global", AttrDict())
    dist = config.get("Distributed", AttrDict())
    dp_world = int(dist.get("dp_degree", 1)) * int(dist.get("fsdp_degree", 1))

    gbs = glb.get("global_batch_size")
    lbs = glb.get("local_batch_size")
    mbs = glb.get("micro_batch_size")

    if gbs is None and lbs is None:
        raise ValueError("global_batch_size or local_batch_size must be set")
    if lbs is None:
        assert gbs % dp_world == 0, (
            f"global_batch_size {gbs} not divisible by dp world {dp_world}")
        lbs = gbs // dp_world
    if gbs is None:
        gbs = lbs * dp_world
    if mbs is None:
        mbs = lbs
    assert lbs % mbs == 0, f"local_batch_size {lbs} % micro_batch_size {mbs} != 0"
    assert gbs == lbs * dp_world, (
        f"global_batch_size {gbs} != local_batch_size {lbs} * dp world {dp_world}")

    glb.global_batch_size = int(gbs)
    glb.local_batch_size = int(lbs)
    glb.micro_batch_size = int(mbs)
    glb.setdefault("seed", 1024)
    glb.setdefault("device", "tpu")

    eng = config.setdefault("Engine", AttrDict())
    if eng.get("accumulate_steps") in (None, 0):
        eng.accumulate_steps = glb.local_batch_size // glb.micro_batch_size
    return config


def process_engine_config(config: AttrDict) -> AttrDict:
    """Fill Engine defaults (reference process_engine_config)."""
    eng = config.setdefault("Engine", AttrDict())
    eng.setdefault("run_mode", "step")
    eng.setdefault("num_train_epochs", 1)
    eng.setdefault("max_steps", 500000)
    eng.setdefault("logging_freq", 10)
    eng.setdefault("eval_freq", None)
    eng.setdefault("eval_iters", 10)
    # device-side input double buffering (docs/bandwidth_levers.md): depth of
    # the prefetch-to-device queue; 0 keeps the serial fetch→shard→step loop
    eng.setdefault("prefetch_to_device", 0)
    mp = eng.setdefault("mix_precision", AttrDict())
    mp.setdefault("enable", True)
    mp.setdefault("dtype", "bfloat16")
    mp.setdefault("param_dtype", "float32")
    mp.setdefault("scale_loss", None)  # fp16-style loss scaling; off for bf16
    sl = eng.setdefault("save_load", AttrDict())
    sl.setdefault("save_steps", None)
    sl.setdefault("save_epoch", 1)
    sl.setdefault("output_dir", "./output")
    sl.setdefault("ckpt_dir", None)
    return config


def process_observability_config(config: AttrDict) -> AttrDict:
    """Ensure the ``Observability`` block exists (docs/observability.md).

    Only ``enable`` and ``gang`` (both opt-in, default False — telemetry
    never surprises a recipe, and gang mode changes sink file naming) are
    materialised here so ``print_config`` shows the switches; the
    per-knob defaults live in ONE place, ``observability.Observability``,
    which engines also reach without ``get_config``.

    The flight-recorder capacity gets eager validation: a zero/negative
    ring would silently record nothing, discovered only at the crash the
    recorder exists for.
    """
    obs = config.setdefault("Observability", AttrDict())
    obs.setdefault("enable", False)
    obs.setdefault("gang", False)
    flight = obs.get("flight") or {}
    capacity = flight.get("capacity")
    if capacity is not None and int(capacity) <= 0:
        raise ValueError(
            f"Observability.flight.capacity must be > 0, got {capacity!r}")
    return config


def process_resilience_config(config: AttrDict) -> AttrDict:
    """Ensure the ``Resilience`` block exists (docs/resilience.md).

    Same stance as ``process_observability_config``: only ``enable``
    (opt-in, default False — fault handling never changes a recipe's
    behaviour silently) is materialised so ``print_config`` shows the
    switch; per-knob defaults live in ONE place,
    ``resilience.Resilience`` and its component classes, which engines
    also reach without ``get_config``.

    The multi-host knobs get eager validation here: a bad agreement
    deadline or gang cadence would otherwise only surface as a hung or
    divergent gang minutes into a pod run, the most expensive possible
    place to learn about a YAML typo.
    """
    res = config.setdefault("Resilience", AttrDict())
    res.setdefault("enable", False)

    def _positive(block: str, key: str, value) -> None:
        if value is not None and float(value) <= 0:
            raise ValueError(
                f"Resilience.{block}.{key} must be > 0, got {value!r}")

    coord = res.get("coordination") or {}
    _positive("coordination", "timeout_s", coord.get("timeout_s"))
    _positive("coordination", "poll_s", coord.get("poll_s"))
    pre = res.get("preemption") or {}
    _positive("preemption", "sync_every", pre.get("sync_every"))
    wd = res.get("watchdog") or {}
    _positive("watchdog", "gang_timeout_s", wd.get("gang_timeout_s"))
    gang_steps = wd.get("gang_sync_steps")
    if gang_steps is not None and int(gang_steps) < 0:
        raise ValueError(
            f"Resilience.watchdog.gang_sync_steps must be >= 0 "
            f"(0 disables the gang barrier), got {gang_steps!r}")
    # state-integrity knobs (docs/resilience.md "Integrity"): a typo'd
    # sentinel action would otherwise only surface when the sentinel
    # first TRIPS — the worst possible moment to discover the config
    # cannot say what to do about a corrupt replica
    integ = res.get("integrity") or {}
    sentinel = integ.get("sentinel_every")
    if sentinel is not None and int(sentinel) < 0:
        raise ValueError(
            f"Resilience.integrity.sentinel_every must be >= 0 "
            f"(0 disables the SDC sentinel), got {sentinel!r}")
    action = integ.get("sentinel_action")
    if action is not None and action not in ("log", "quarantine", "abort"):
        raise ValueError(
            f"Resilience.integrity.sentinel_action must be log | "
            f"quarantine | abort, got {action!r}")
    verify = integ.get("verify_checkpoints")
    if verify is not None and not isinstance(verify, bool):
        raise ValueError(
            f"Resilience.integrity.verify_checkpoints must be a bool, "
            f"got {verify!r}")
    return config


def process_serving_config(config: AttrDict) -> AttrDict:
    """Eagerly validate the ``Serving`` block (docs/serving.md).

    Same stance as the observability/resilience processors: defaults live
    in ONE place (``serving.engine.ServingConfig``); this only validates
    what a typo would otherwise surface at the worst moment — the SLO
    block fails at launch instead of when the first attainment window
    closes, and zero-capacity trace rings would silently record nothing.
    """
    serving = config.get("Serving")
    if not serving:
        return config
    # import inside: keeps this module's import surface flat (slo.py pulls
    # the metrics registry, not needed by pure config consumers)
    from fleetx_tpu.observability.slo import validate_slo_block

    validate_slo_block(serving.get("slo"))
    for key in ("trace_requests", "trace_events"):
        v = serving.get(key)
        if v is not None and int(v) <= 0:
            raise ValueError(f"Serving.{key} must be > 0, got {v!r}")
    mq = serving.get("max_queue")
    if mq is not None and int(mq) < 0:
        raise ValueError(
            f"Serving.max_queue must be >= 0 (0 = unbounded admission "
            f"queue), got {mq!r}")
    # the router block validates through the SAME dataclass the router
    # boots from (serving/router.py — stdlib-only, cheap import): a
    # typo'd breaker knob fails at config load, not when the fleet
    # first degrades and the breaker math actually runs
    router = serving.get("router")
    if router is not None:
        if not isinstance(router, dict):
            raise ValueError(
                f"Serving.router must be a mapping of router knobs, "
                f"got {router!r}")
        from fleetx_tpu.serving.router import RouterConfig

        try:
            RouterConfig.from_dict(dict(router))
        except (AssertionError, TypeError, ValueError) as e:
            raise ValueError(f"Serving.router invalid: {e}") from e
    return config


def get_config(fname: str, overrides: list[str] | None = None, show: bool = False,
               num_devices: int | None = None, auto_layout: bool = False) -> AttrDict:
    """Load + override + post-process a config (reference ``config.py:313-345``).

    ``auto_layout`` (or ``Distributed.auto_layout: true`` in the YAML) runs
    the mesh-degree planner (``parallel/auto_layout.suggest_layout``) BEFORE
    the batch/degree derivations, so local/micro batch math follows the
    chosen layout — the reference ``get_auto_config`` analogue
    (``config.py:447-464``) with the planning half actually automated.
    """
    assert os.path.exists(fname), f"config file {fname} not found"
    config = parse_config(fname)
    override_config(config, overrides)
    dist = config.get("Distributed") or {}
    al = dist.get("auto_layout")
    if auto_layout or al:
        from fleetx_tpu.parallel.auto_layout import suggest_layout

        # YAML can size the planner's budget: auto_layout: {hbm_gb: 32}
        hbm_gb = float(al.get("hbm_gb", 16.0)) if isinstance(al, dict) \
            else 16.0
        if num_devices is None:
            import jax

            num_devices = jax.device_count()
        explicit = {k for k in ("dp_degree", "mp_degree", "pp_degree",
                                "fsdp_degree", "seq_degree")
                    if int(dist.get(k) or 0) > 1}
        if int((dist.get("sharding") or {}).get("sharding_degree") or 0) > 1:
            explicit.add("sharding.sharding_degree")
        if explicit:
            logger.info("auto_layout: explicit degrees %s kept", explicit)
        else:
            # feed the activation half of the memory model what the raw
            # config already knows (micro batch derives later, so fall back
            # through the batch keys conservatively)
            from fleetx_tpu.parallel.auto_layout import advice_inputs

            # pre-planning the mesh is unknown: assume all-dp for the
            # global→micro batch rung (the planner's act-first growth
            # corrects the layout if the per-device batch blows the budget)
            mdl, mb, gran = advice_inputs(config, data_world=num_devices)
            layout = suggest_layout(mdl, num_devices, hbm_gb=hbm_gb,
                                    micro_batch=mb, recompute=gran)
            config.setdefault("Distributed", AttrDict())
            for k, v in layout.items():
                # merge (don't replace) the sharding sub-dict: the recipe
                # may carry orthogonal keys like sharding_offload
                if k == "sharding" and isinstance(
                        config["Distributed"].get("sharding"), dict):
                    config["Distributed"]["sharding"].update(v)
                else:
                    config["Distributed"][k] = v
        config["Distributed"].pop("auto_layout", None)
    process_dist_config(config, num_devices=num_devices)
    process_global_configs(config)
    process_engine_config(config)
    process_observability_config(config)
    process_resilience_config(config)
    process_serving_config(config)
    if show:
        print_config(config)
    return config


def print_config(config: dict, indent: int = 0) -> None:
    """Pretty-print the resolved config tree (reference ``config.py:205-232``)."""
    for k, v in sorted(config.items()):
        if isinstance(v, dict):
            logger.info("%s%s :", " " * indent, k)
            print_config(v, indent + 4)
        else:
            logger.info("%s%s : %s", " " * indent, k, v)


def parse_args(description: str = "fleetx_tpu") -> argparse.Namespace:
    """`-c config.yaml -o A.B=v` CLI surface (reference ``config.py:467-482``)."""
    parser = argparse.ArgumentParser(description=description)
    parser.add_argument("-c", "--config", required=True, help="path to YAML config")
    parser.add_argument("-o", "--override", action="append", default=[],
                        help="dotted config overrides, e.g. -o Engine.max_steps=10")
    return parser.parse_args()
