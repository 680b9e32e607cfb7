"""Static sharding audit over the YAML config zoo (docs/static_analysis.md).

For every config in the zoo this derives the model's ABSTRACT parameter
tree with ``jax.eval_shape`` — shape-level only, no FLOPs, no devices, so
the whole audit runs on CPU CI in seconds — and verifies it against the
partition-rule registry (``parallel/rules.py``):

- every non-scalar leaf matched by exactly one rule (unmatched leaves and
  ambiguous overlaps are findings),
- no dead rules (a rule no audited config of its family ever matches),
- every sharded dim divisible by its mesh degree for THAT config's
  declared layout,
- no fully-replicated leaf above the size threshold outside families that
  declare replication (the forgotten-spec hazard),
- the serving KV pool's layout (pages over ``fsdp``, heads over
  ``tensor``) for configs carrying a ``Serving:`` section.

The drift this catches used to surface at jit bind time on real hardware;
``tools/shardcheck.py`` is the CLI and lint rules FX011/FX012
(``fleetx_tpu/lint/rules/sharding.py``) report the same audit through the
reporter stack (text/JSON/SARIF, fingerprint baseline, result cache keyed
on the registry + config + model fingerprints).

Kernel-choice knobs (flash/ring attention) are neutralised for the shape
trace: they select attention *implementations* with no parameters of
their own, and the ring path binds a mesh axis that does not exist on a
1-device CPU trace. Parameter shapes are unaffected — pipeline topology,
MoE, QAT and vocab-chunk knobs are kept faithful.
"""

from __future__ import annotations

import hashlib
import os
from typing import Any, Iterable, Optional

from fleetx_tpu.parallel import rules as rules_lib

#: directories holding the YAML config zoo, relative to the repo root
#: (mirrors lint's CONFIG_DIRS — kept literal so this module stays
#: importable without the lint package)
CONFIG_DIRS = ("fleetx_tpu/configs", "projects")

def zoo_configs(root: str) -> list[str]:
    """Every YAML file under the config zoo dirs (posix relpaths)."""
    out = []
    for d in CONFIG_DIRS:
        base = os.path.join(root, d)
        for dirpath, _, names in sorted(os.walk(base)):
            for name in sorted(names):
                if name.endswith((".yaml", ".yml")):
                    rel = os.path.relpath(os.path.join(dirpath, name), root)
                    out.append(rel.replace(os.sep, "/"))
    return out


# ----------------------------------------------------------- config loading

def _load_config(root: str, rel: str) -> dict:
    from fleetx_tpu.utils.config import parse_config

    return parse_config(os.path.join(root, rel))


def _layout_of(cfg: dict) -> tuple[rules_lib.SpecLayout, dict]:
    """(SpecLayout, mesh degrees) from a RAW config's Distributed section —
    no device-count validation (the audit is static; dp is irrelevant to
    parameter sharding). Stage defaults follow ``process_dist_config``:
    fsdp>1 without an explicit stage means stage 1."""
    dist = dict(cfg.get("Distributed") or {})
    sharding = dict(dist.get("sharding") or {})
    fsdp = int(dist.get("fsdp_degree") or sharding.get("sharding_degree")
               or 1)
    stage = int(sharding.get("sharding_stage") or (1 if fsdp > 1 else 0))
    layout = rules_lib.SpecLayout(
        stage=stage, sequence_parallel=bool(dist.get("sequence_parallel")))
    degrees = {
        "pipe": int(dist.get("pp_degree") or 1),
        "fsdp": fsdp,
        "seq": int(dist.get("seq_degree") or 1),
        "tensor": int(dist.get("mp_degree") or 1),
    }
    return layout, degrees


def _sanitized_model(cfg: dict) -> dict:
    """Copy of the config with kernel-choice knobs neutralised for the
    shape trace (see module docstring — parameter shapes unaffected)."""
    out = dict(cfg)
    model = dict(out.get("Model") or {})
    model["use_flash_attention"] = False
    model["use_ring_attention"] = False
    out["Model"] = model
    return out


def _sample_batch(module: Any, family: str) -> dict:
    """Synthetic 1-row host batch shaped for ``init_variables`` — only the
    SHAPES matter (everything runs under ``jax.eval_shape``)."""
    import numpy as np

    if family in ("gpt", "gpt_moe", "gpt_lora"):
        s = int(module.model_cfg.max_position_embeddings)
        tok = np.zeros((1, s), np.int32)
        return {"tokens": tok, "position_ids": tok.copy()}
    if family in ("mla_moe", "swa_moe", "gdn_mla", "conv_moe", "samba_y",
                  "ssm_mqa"):
        tok = np.zeros((1, int(module.tokens_per_sample)), np.int32)
        return {"tokens": tok, "position_ids": tok.copy()}
    if family == "ernie":
        s = int(module.model_cfg.max_position_embeddings)
        ids = np.zeros((1, s), np.int32)
        return {"input_ids": ids, "token_type_ids": ids.copy()}
    if family == "vision":
        sz = int(module.vit_cfg.image_size)
        return {"images": np.zeros((1, sz, sz, 3), np.float32)}
    if family == "imagen":
        ucfg = module.model.unet_cfg
        sz = int(module.model_dict.get("image_size", 64))
        batch = {"images": np.zeros((1, sz, sz, int(ucfg.channels)),
                                    np.float32),
                 "text_embeds": np.zeros((1, 8, int(ucfg.text_embed_dim)),
                                         np.float32),
                 "text_mask": np.ones((1, 8), bool)}
        if ucfg.lowres_cond:
            batch["lowres_images"] = np.zeros(
                (1, sz, sz, int(ucfg.channels)), np.float32)
        return batch
    raise KeyError(f"no sample-batch recipe for family {family!r}")


def _abstract_leaves(cfg: dict) -> tuple[str, list, Any]:
    """(family, named abstract param leaves, module) for one raw config —
    builds the real task module ONCE and ``eval_shape``s its
    ``init_variables`` (the module rides along so the serving-pool audit
    never pays a second model construction)."""
    import jax

    from fleetx_tpu.models import build_module

    module = build_module(_sanitized_model(cfg))
    family = rules_lib.family_of(module)
    if family is None:
        raise KeyError(
            f"module {type(module).__name__} declares no spec_family — "
            f"register it in PARTITION_RULES and set the attribute")
    batch = _sample_batch(module, family)
    abstract = jax.eval_shape(
        lambda rng: module.init_variables(rng, batch),
        jax.random.PRNGKey(0))
    from flax.core import meta

    return family, rules_lib.tree_leaf_names(meta.unbox(abstract)), module


def _kv_pool_leaves(cfg: dict, module: Any) -> Optional[list]:
    """Named abstract (K, V) pool leaves when the config serves — audited
    as family ``serving_kv`` (pages over fsdp, heads over tensor).
    ``module`` is the one ``_abstract_leaves`` already built."""
    serving = dict(cfg.get("Serving") or {})
    if not serving:
        return None
    import jax

    from fleetx_tpu.serving.paged_cache import init_pool

    num_pages = int(serving.get("num_pages") or 256)
    page_size = int(serving.get("page_size") or 16)
    if hasattr(module, "kv_pool_shape"):    # not every layer keeps every token
        k = v = jax.ShapeDtypeStruct(
            module.kv_pool_shape(num_pages, page_size), module.model_cfg.dtype)
    else:
        k, v = jax.eval_shape(
            lambda: init_pool(module.model_cfg, num_pages, page_size))
    return [("kv_pool/k", k), ("kv_pool/v", v)]


# ------------------------------------------------------------------- audit

def audit_config(root: str, rel: str,
                 _tree_cache: Optional[dict] = None) -> dict:
    """Audit one config; returns ``{"config", "family", "issues",
    "used_rules"}`` (issues carry the config relpath). A config that
    cannot be traced is itself a finding (``audit-error``) — the zoo must
    stay auditable, not silently shrink."""
    issues: list[dict] = []
    used: dict[str, set] = {}
    family = None
    try:
        cfg = _load_config(root, rel)
        layout, degrees = _layout_of(cfg)
        sig = None
        if _tree_cache is not None:
            sig = hashlib.sha1(repr(
                (sorted((cfg.get("Model") or {}).items(),
                        key=lambda kv: kv[0]),
                 (cfg.get("Distributed") or {}).get("pp_degree"),
                 (cfg.get("Distributed") or {}).get("virtual_pp_degree"),
                 )).encode("utf-8")).hexdigest()
        if sig is not None and sig in _tree_cache:
            family, leaves, module = _tree_cache[sig]
        else:
            family, leaves, module = _abstract_leaves(cfg)
            if sig is not None:
                _tree_cache[sig] = (family, leaves, module)
        fam_issues, fam_used = rules_lib.audit_leaves(
            family, leaves, layout, degrees)
        issues.extend(fam_issues)
        used.setdefault(family, set()).update(fam_used)
        pool = _kv_pool_leaves(cfg, module)
        if pool is not None:
            pool_issues, pool_used = rules_lib.audit_leaves(
                "serving_kv", pool, layout, degrees)
            issues.extend(pool_issues)
            used.setdefault("serving_kv", set()).update(pool_used)
    except Exception as e:  # noqa: BLE001 — a broken config IS the finding
        issues.append({"kind": "audit-error", "family": family or "?",
                       "leaf": "", "message":
                       f"config could not be audited: "
                       f"{type(e).__name__}: {e}"})
    for issue in issues:
        issue["config"] = rel
    return {"config": rel, "family": family, "issues": issues,
            "used_rules": used}


def audit_zoo(root: str, only: Optional[Iterable[str]] = None) -> dict:
    """Audit the whole zoo (or ``only`` — ``tools/shardcheck.py``'s
    positional configs, threaded through the FX011/FX012 filter in
    ``lint/rules/sharding.py``).

    Returns ``{"issues", "dead_rules", "configs", "families"}``. Dead
    rules (and unexercised families) are reported only on UNFILTERED runs
    — a partial zoo cannot prove a rule dead. ``dead_rules`` entries are
    ``{"family", "index", "pattern"}`` so callers can anchor findings to
    the pattern's line in ``parallel/rules.py``.
    """
    only = tuple(only) if only else None
    configs = zoo_configs(root)
    if only:
        wanted = {c.replace(os.sep, "/") for c in only}
        configs = [c for c in configs
                   if c in wanted or os.path.basename(c) in wanted]
    issues: list[dict] = []
    used: dict[str, set] = {}
    audited_families: set[str] = set()
    tree_cache: dict = {}
    for rel in configs:
        report = audit_config(root, rel, _tree_cache=tree_cache)
        issues.extend(report["issues"])
        for fam, idxs in report["used_rules"].items():
            used.setdefault(fam, set()).update(idxs)
            audited_families.add(fam)
    dead: list[dict] = []
    if not only:
        for family, table in sorted(rules_lib.PARTITION_RULES.items()):
            if family not in audited_families:
                dead.append({"family": family, "index": -1, "pattern": "",
                             "message":
                             f"family {family!r} is registered but no zoo "
                             f"config exercises it — its rules cannot be "
                             f"audited for deadness or coverage"})
                continue
            for i, (pattern, _) in enumerate(table):
                if i not in used.get(family, set()):
                    dead.append({"family": family, "index": i,
                                 "pattern": pattern, "message":
                                 f"rule {pattern!r} of family {family!r} "
                                 f"matches no parameter of any audited "
                                 f"config — dead rules hide typos and rot"})
    return {"issues": issues, "dead_rules": dead, "configs": len(configs),
            "families": {f: sorted(u) for f, u in used.items()}}
