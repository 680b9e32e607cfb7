"""Unified partition-rule registry — sharding specs as data, one table per
model family.

Before this module, the sharding of a parameter tree was decided in five
places that could silently drift: the engine resolved flax logical
annotations through ``make_axis_rules``, ``zero_grad_specs`` re-derived
fsdp placement per leaf, both checkpoint codecs trusted whatever abstract
tree they were handed, the auto-layout memory model hard-coded which ZeRO
stage shards which term, and the serving KV pool hand-wired its own
``PartitionSpec``. A bad spec surfaced only at jit bind time on real
hardware. Here the whole mapping is *data*:

- ``PARTITION_RULES``: per model family (``gpt``, ``gpt_moe``,
  ``gpt_lora``, ``vision``, ``ernie``, ``imagen``, ``mla_moe``, ``swa_moe``, ``gdn_mla``, ``conv_moe``, ``samba_y``, ``ssm_mqa``, plus the serving KV
  pool as ``serving_kv``), an
  ORDERED tuple of ``(regex, logical-axes template)`` rules matched against
  slash-joined parameter-tree paths, first match wins — the
  ``match_partition_rules`` pattern of "Scalable Training of Language
  Models using JAX pjit and TPUv4" (PAPERS.md) scaled to every family;
- ``SpecLayout``: the canonical logical→mesh table (one source for the
  runtime, the flax activation constraints, FX004 lint and the shardcheck
  auditor alike), parameterised only by the ZeRO stage and
  sequence-parallel flag;
- resolution helpers (``registry_specs`` / ``named_shardings``) every
  consumer calls: ``eager_engine.prepare``, ``zero_grad_specs`` (via
  :func:`with_fsdp_axis`), both checkpoint codecs (``load_params`` +
  the registry fingerprint stamped into checkpoint metas),
  ``auto_layout`` (:func:`stage_shards`) and ``serving.paged_cache``
  (:func:`kv_pool_spec`);
- audit helpers (:func:`audit_leaves`) the static shardcheck pass
  (``tools/shardcheck.py`` + lint rules FX011-FX013) runs over every
  YAML-zoo config's ``jax.eval_shape``-derived abstract tree — unmatched
  leaves, ambiguous overlaps, dead rules, indivisible sharded dims and
  oversized replicated leaves are findings on CPU CI, not jit-bind-time
  surprises on a pod.

Specs are canonical: no trailing ``None`` entries, scalars (and size-1
leaves) always replicate. The module imports neither jax nor flax at the
top level — the tables are pure data, so ``tools/lint.py`` can read
``MESH_AXES``/``LOGICAL_AXES`` by AST parse (it never imports this
module; importing it through the ``fleetx_tpu.parallel`` package DOES
pull jax via ``mesh.py``). jax types appear only inside the resolution
functions that already run under jax.

Stacked layers: scanned transformer stacks prepend up to three leading
"stack" dims (``layers``; ``pipe_stage, layers`` under pipeline
parallelism; ``pipe_repeat, pipe_stage, layers`` with virtual stages).
Rules describe the TRAILING feature axes once; leaves whose path matches
the family's ``STACK_MARKERS`` regex get the missing leading axes padded
from ``STACK_AXES`` — one rule covers the unstacked, scanned, pp and vpp
layouts of the same parameter (they are the same parameter).
"""

from __future__ import annotations

import dataclasses
import hashlib
import re
from typing import Any, Iterable, Optional

__all__ = [
    "MESH_AXES", "LOGICAL_AXES", "STACK_AXES", "PARTITION_RULES",
    "STACK_MARKERS", "REPLICATED", "SpecLayout", "match_partition_rules",
    "registry_specs", "named_shardings", "tree_leaf_names", "spec_for",
    "canonicalize", "first_free_divisible_dim", "with_fsdp_axis",
    "stage_shards", "kv_pool_spec", "batch_spec", "activation_spec",
    "audit_leaves",
    "registry_fingerprint", "family_fingerprint", "families", "family_of",
]

#: the mesh axis vocabulary — THE declaration (``parallel/mesh.py`` builds
#: its Mesh from this tuple and FX004 lint parses it from this file)
MESH_AXES = ("pipe", "data", "fsdp", "seq", "tensor")

#: the logical axis vocabulary rule templates may use (FX013 lint parses
#: this literal to recognise hand-wired rule tables outside this module)
LOGICAL_AXES = (
    "batch", "vocab", "mlp", "heads", "kv", "layers", "pipe_stage",
    "pipe_repeat", "act_stage", "norm", "embed", "act_seq", "act_embed",
    "act_heads", "act_kv", "act_vocab", "expert", "act_expert",
    "kv_pages", "page_slot",
)

#: leading stack axes of scanned layer stacks, outermost first; a stacked
#: leaf with k extra leading dims takes the LAST k entries
STACK_AXES = ("pipe_repeat", "pipe_stage", "layers")

#: sentinel template: replicated at any rank (families with no
#: tensor-parallel rules yet — document, don't guess)
REPLICATED = "replicated"


@dataclasses.dataclass(frozen=True)
class SpecLayout:
    """Canonical logical→mesh mapping for one run's parallelism layout.

    The two knobs mirror what ``make_axis_rules`` historically read from
    the ``Distributed`` config section: the ZeRO ``stage`` decides whether
    ``embed`` (the parameter hidden dim) shards over ``fsdp`` (stage 3),
    and ``sequence_parallel`` additionally spreads ``act_seq`` over the
    ``tensor`` axis (Megatron-SP).
    """

    stage: int = 0
    sequence_parallel: bool = False

    @classmethod
    def from_dist_config(cls, dist_config: dict | None) -> "SpecLayout":
        """Layout from a ``Distributed:`` config section (the historical
        ``make_axis_rules`` input contract)."""
        cfg = dist_config or {}
        stage = int((cfg.get("sharding") or {}).get("sharding_stage") or 0)
        return cls(stage=stage,
                   sequence_parallel=bool(cfg.get("sequence_parallel")))

    def axis_rules(self) -> tuple[tuple[str, Any], ...]:
        """The ONE logical→mesh table (consumed verbatim by
        ``flax.linen.logical_axis_rules`` for activation constraints and by
        :func:`spec_for` for parameter resolution):

        - tensor parallelism: ``vocab/mlp/heads/expert → tensor``
          (Megatron column/row splits; expert parallelism rides the same
          high-bandwidth axis)
        - ZeRO stage 3: additionally ``embed → fsdp`` (param sharding)
        - Megatron-SP: activations ``act_seq → (seq, tensor)``
        - context parallelism: ``act_seq → seq`` (ring attention)
        - serving KV pool: ``kv_pages → fsdp`` (capacity scales with the
          ZeRO axis), heads ride the ``heads → tensor`` rule
        """
        act_seq: Any = ("seq", "tensor") if self.sequence_parallel else ("seq",)
        return (
            ("batch", ("data", "fsdp")),
            ("vocab", "tensor"),
            ("mlp", "tensor"),
            ("heads", "tensor"),
            ("kv", None),
            ("layers", None),
            ("pipe_stage", "pipe"),
            ("pipe_repeat", None),
            ("act_stage", "pipe"),
            ("norm", None),
            ("embed", "fsdp" if self.stage >= 3 else None),
            ("act_seq", act_seq),
            ("act_embed", None),
            ("act_heads", "tensor"),
            ("act_kv", None),
            ("act_vocab", "tensor"),
            ("expert", "tensor"),
            ("act_expert", "tensor"),
            ("kv_pages", "fsdp"),
            ("page_slot", None),
        )

    def mesh_entry(self, logical: Optional[str]) -> Any:
        """Mesh axis (or axes tuple, or None) for one logical name."""
        if logical is None:
            return None
        table = dict(self.axis_rules())
        if logical not in table:
            raise KeyError(
                f"unknown logical axis {logical!r} — declared vocabulary is "
                f"LOGICAL_AXES in parallel/rules.py")
        return table[logical]

    def to_mesh(self, template: Iterable[Optional[str]]) -> tuple:
        """Logical template → canonical mesh-axes tuple (no trailing None).

        A mesh axis may appear only once per spec. When two logical axes
        of one leaf resolve to the same mesh axis (MoE: ``expert`` and
        ``mlp`` both map to ``tensor``), the logical axis EARLIER in the
        rule table keeps it and the later one replicates — exactly
        ``flax.linen.logical_to_mesh_axes``' resolution, pinned by the
        per-family parity gate in tests/test_zz_shardcheck.py.
        """
        template = tuple(template)
        order = {name: i for i, (name, _) in enumerate(self.axis_rules())}
        entries = [self.mesh_entry(a) for a in template]
        resolved: list = [None] * len(entries)
        used: set = set()
        by_priority = sorted(
            range(len(entries)),
            key=lambda i: (order.get(template[i], len(order)), i))
        for i in by_priority:
            entry = entries[i]
            axes = tuple(a for a in (
                entry if isinstance(entry, (tuple, list)) else (entry,))
                if a is not None)
            if axes and not used.intersection(axes):
                used.update(axes)
                resolved[i] = entry
        return canonicalize(resolved)


# --------------------------------------------------------------- rule tables
#
# Templates name the TRAILING feature axes of each parameter; stacked-layer
# leading dims are padded from STACK_AXES (see module docstring). Patterns
# are re.search'd against slash-joined leaf paths that may carry tree
# prefixes — "params/..." in the engine's TrainState, "opt_state/.../mu/..."
# for the Adam moments (which thereby inherit their param's rule) — so
# anchor leaf names with (^|/), never a bare ^. The
# tables are exhaustive per family — shardcheck's coverage gate
# (tests/test_zz_shardcheck.py) asserts every family's real param tree is
# matched by exactly one rule, and the per-family parity test asserts the
# resolved specs equal the flax logical annotations the model code carries,
# so neither side can drift.

_GPT_ATTN_RULES = (
    (r"attn/qkv_kernel$", ("embed", None, "heads", "kv")),
    (r"attn/qkv_bias$", (None, "heads", "kv")),
    (r"attn/out_kernel$", ("heads", "kv", "embed")),
    (r"attn/out_bias$", ("embed",)),
)

_GPT_DENSE_MLP_RULES = (
    (r"mlp/wi_kernel$", ("embed", "mlp")),
    (r"mlp/wi_bias$", ("mlp",)),
    (r"mlp/wo_kernel$", ("mlp", "embed")),
    (r"mlp/wo_bias$", ("embed",)),
)

_GPT_MOE_MLP_RULES = (
    (r"mlp/router_kernel$", ("embed", None)),
    (r"mlp/wi_kernel$", ("expert", "embed", "mlp")),
    (r"mlp/wi_bias$", ("expert", "mlp")),
    (r"mlp/wo_kernel$", ("expert", "mlp", "embed")),
    (r"mlp/wo_bias$", ("expert", None)),
)

_GPT_COMMON_RULES = (
    (r"embeddings/word_embeddings$", ("vocab", "embed")),
    (r"embeddings/position_embeddings$", (None, "embed")),
    (r"(ln1|ln2|ln_f)/(scale|bias)$", ("norm",)),
)

# LoRA adapter leaves (fleetx_tpu/finetune/lora.py): each registry-named
# target kernel gains `<kernel>_lora_a` / `<kernel>_lora_b` siblings with
# delta = B@A folded in at merge. A maps the target's input features to
# the rank and replicates (the rank dim is tiny and indivisible by
# design); B maps the rank to the target's output features and inherits
# the base leaf's OUTPUT-side placement — heads/mlp for the
# column-parallel qkv/wi, embed for the row-parallel out/wo, whose tensor
# axis lives on the INPUT side and therefore on no adapter leaf. The
# injection code derives its flax boxing metadata FROM these templates
# (lora.adapter_axis_names), so the table is the single source of truth
# the parity gate in tests/test_zz_shardcheck.py pins.
_GPT_LORA_RULES = (
    (r"attn/qkv_kernel_lora_a$", (None, None)),
    (r"attn/qkv_kernel_lora_b$", (None, None, "heads", "kv")),
    (r"attn/out_kernel_lora_a$", (None, None, None)),
    (r"attn/out_kernel_lora_b$", (None, "embed")),
    (r"mlp/wi_kernel_lora_a$", (None, None)),
    (r"mlp/wi_kernel_lora_b$", (None, "mlp")),
    (r"mlp/wo_kernel_lora_a$", (None, None)),
    (r"mlp/wo_kernel_lora_b$", (None, "embed")),
)

#: family → ordered (regex, template) rules; first match wins
PARTITION_RULES: dict[str, tuple] = {
    "gpt": _GPT_ATTN_RULES + _GPT_DENSE_MLP_RULES + _GPT_COMMON_RULES,
    # the MoE stack REPLACES the dense MLP — the dense wi/wo rules are
    # deliberately absent so dead-rule accounting stays exact per family
    "gpt_moe": _GPT_ATTN_RULES + _GPT_MOE_MLP_RULES + _GPT_COMMON_RULES,
    # parameter-efficient fine-tuning (docs/finetune.md): the dense GPT
    # tree plus the low-rank adapter leaves — one family so the engine,
    # both checkpoint codecs, ZeRO specs and shardcheck resolve a LoRA
    # state with no hand-wiring
    "gpt_lora": _GPT_LORA_RULES + _GPT_ATTN_RULES + _GPT_DENSE_MLP_RULES
    + _GPT_COMMON_RULES,
    "vision": _GPT_ATTN_RULES + _GPT_DENSE_MLP_RULES + (
        (r"(ln1|ln2|ln_f)/(scale|bias)$", ("norm",)),
        (r"(^|/)cls_token$", (None, None, "embed")),
        (r"(^|/)pos_embed$", (None, None, "embed")),
        (r"(^|/)patch_kernel$", (None, None, None, "embed")),
        (r"(^|/)patch_bias$", ("embed",)),
        # the classifier head is a vocab-style projection (classes shard
        # over tensor exactly like logits)
        (r"(^|/)head_kernel$", ("embed", "vocab")),
        (r"(^|/)head_bias$", ("vocab",)),
    ),
    "ernie": _GPT_ATTN_RULES + (
        # ernie's FFN leaves sit directly under layers/ (no mlp/ scope)
        (r"layers/wi_kernel$", ("embed", "mlp")),
        (r"layers/wi_bias$", ("mlp",)),
        (r"layers/wo_kernel$", ("mlp", "embed")),
        (r"layers/wo_bias$", ("embed",)),
        (r"(ln1|ln2|embed_ln|mlm_ln)/(scale|bias)$", ("norm",)),
        (r"word_embeddings$", ("vocab", "embed")),
        (r"(position|token_type)_embeddings$", (None, "embed")),
        (r"pooler_kernel$", ("embed", None)),
        (r"pooler_bias$", ("embed",)),
        (r"(^|/)mlm_transform_kernel$", ("embed", None)),
        (r"(^|/)mlm_transform_bias$", ("embed",)),
        (r"(^|/)mlm_bias$", ("vocab",)),
        (r"(^|/)nsp_kernel$", ("embed", None)),
        (r"(^|/)nsp_bias$", (None,)),
    ),
    # the diffusion stages are data-parallel only today (no tensor rules
    # yet) — every leaf replicates BY DECLARATION, not by omission
    "imagen": (
        (r".", REPLICATED),
    ),
    # latent-attention sparse-expert decoders (models/mla_moe): low-rank
    # query and key-value projections whose rank dims replicate, heads
    # over the Megatron axis, and an expert layer that holds a share of
    # the experts (the held experts over ``expert``; the router and the
    # selection bias span ALL experts and replicate)
    "mla_moe": (
        (r"attn/(q_a|kv_a)$", ("embed", None)),
        (r"attn/(q_b|kv_b)$", (None, "heads", "kv")),
        (r"attn/out$", ("heads", "kv", "embed")),
        (r"attn/(q_norm|kv_norm)$", ("norm",)),
        (r"(mlp/|moe/shared_)(gate|up)$", ("embed", "mlp")),
        (r"(mlp/|moe/shared_)down$", ("mlp", "embed")),
        (r"moe/router$", ("embed", None)),
        (r"moe/selection_bias$", (None,)),
        (r"moe/experts_(gate|up)$", ("expert", "embed", None)),
        (r"moe/experts_down$", ("expert", None, "embed")),
        (r"(embed/tokens|head/kernel)$", ("vocab", "embed")),
        (r"mtp/proj$", (None, "embed")),
        (r"(^|/)\w*norm/scale$", ("norm",)),
    ),
    # the windowed-attention sparse-expert decoder (models/swa_moe;
    # served): queries, keys and the output projection a [head_dim, hidden]
    # matrix a head, heads over the Megatron axis; values and the per-head
    # gates with the heads in their minor dimension; the held experts over
    # ``expert``, the router (ALL experts) replicated
    "swa_moe": (
        (r"attn/(q|k|out)$", ("heads", "kv", "embed")),
        (r"attn/(v|gate)$", ("embed", "heads")),
        (r"(mlp/|moe/shared_)(gate|up)$", ("embed", "mlp")),
        (r"(mlp/|moe/shared_)down$", ("mlp", "embed")),
        (r"moe/router$", ("embed", None)),
        (r"moe/experts_(gate|up)$", ("expert", "embed", None)),
        (r"moe/experts_down$", ("expert", None, "embed")),
        (r"embed/tokens$", ("vocab", "embed")),
        (r"head/kernel$", ("embed", "vocab")),
        (r"(^|/)\w*norm/scale$", ("norm",)),
    ),
    # the linear-attention / latent-attention sparse-expert decoder
    # (models/gdn_mla; served on one chip): the wide products over the
    # Megatron axis by their output (or, for the output products, input)
    # channels, the latents' small products and every vector replicated;
    # the held experts over ``expert``, the router (ALL experts) replicated
    "gdn_mla": (
        (r"mixer/(qkv|z|gate)$", ("embed", "heads")),
        (r"mixer/(ab|q_a|kv_a)$", ("embed", None)),
        (r"mixer/conv$", (None, "heads")),
        (r"mixer/(A_log|dt_bias|o_norm|q_norm|kv_norm)$", (None,)),
        (r"(linear_dense|linear_moe)/mixer/out$", ("heads", "embed")),
        (r"mixer/(q_bn|q_br|k_b|v_b)$", (None, "heads", "kv")),
        (r"(latent_dense|latent_moe)/mixer/out$", ("heads", "kv", "embed")),
        (r"(mlp/|moe/shared_)(gate|up)$", ("embed", "mlp")),
        (r"(mlp/|moe/shared_)down$", ("mlp", "embed")),
        (r"moe/router$", ("embed", None)),
        (r"moe/selection_bias$", (None,)),
        (r"moe/experts_(gate|up)$", ("expert", "embed", None)),
        (r"moe/experts_down$", ("expert", None, "embed")),
        (r"embed/tokens$", ("vocab", "embed")),
        (r"head/kernel$", ("embed", "vocab")),
        (r"(^|/)\w*norm/w$", ("norm",)),
    ),
    # the short-convolution / grouped-query sparse-expert decoder
    # (models/conv_moe; served on one chip): the convolution's two products
    # and the attention products over the Megatron axis, the taps with
    # their channels, the head-wide norms replicated; the experts over
    # ``expert``, the router and its selection bias replicated; no head
    # leaf (tied to the embedding)
    "conv_moe": (
        (r"conv/in$", ("embed", "heads")),
        (r"conv/taps$", (None, "heads")),
        (r"conv/out$", ("heads", "embed")),
        (r"attn/(q|k|out)$", ("heads", "kv", "embed")),
        (r"attn/v$", ("embed", "heads")),
        (r"attn/(q_norm|k_norm)$", (None,)),
        (r"mlp/(gate|up)$", ("embed", "mlp")),
        (r"mlp/down$", ("mlp", "embed")),
        (r"moe/router$", ("embed", None)),
        (r"moe/expert_bias$", (None,)),
        (r"moe/experts_(gate|up)$", ("expert", "embed", None)),
        (r"moe/experts_down$", ("expert", None, "embed")),
        (r"embed/tokens$", ("vocab", "embed")),
        (r"(^|/)\w*norm/scale$", ("norm",)),
    ),
    # the decoder-hybrid-decoder family (models/samba_y; served on one
    # chip): the scan's channels, the memory units' and the attention's
    # heads over the Megatron axis (a scan channel needs no neighbour: its
    # taps, step, decay and skip go with it), the small step and B / C
    # projections with their channel side, the lambda vectors and the
    # sub-norm replicated; no head leaf (tied to the embedding)
    "samba_y": (
        (r"ssm/in$", ("embed", "heads")),
        (r"ssm/(taps|dt|A_log)$", (None, "heads")),
        (r"ssm/(conv_bias|dt_bias|D)$", ("heads",)),
        (r"ssm/x$", ("heads", None)),
        (r"(ssm|gmu)/out$", ("heads", "embed")),
        (r"gmu/in$", ("embed", "heads")),
        (r"attn/qkv$", ("embed", "heads")),
        (r"attn/qkv_bias$", ("heads",)),
        (r"attn/out$", ("heads", "embed")),
        (r"attn/(out_bias|lambda_[qk][12]|subln)$", (None,)),
        (r"mlp/gate_up$", ("embed", "mlp")),
        (r"mlp/down$", ("mlp", "embed")),
        (r"embed/tokens$", ("vocab", "embed")),
        (r"(^|/)\w*norm\d?/(scale|bias)$", ("norm",)),
    ),
    # the scan / multi-query family (models/ssm_mqa; served on one chip):
    # the scan's channels and the attention's QUERY heads over the Megatron
    # axis; the small step and B / C projection with its channel side and
    # the three inner norms over its outputs replicated; the one key-value
    # head's columns ride the fused qkv product's head axis (a mesh that
    # divides 20 query heads would replicate that head: not written)
    "ssm_mqa": (
        (r"ssm/in$", ("embed", "heads")),
        (r"ssm/(taps|dt|A_log)$", (None, "heads")),
        (r"ssm/(conv_bias|dt_bias|D)$", ("heads",)),
        (r"ssm/x$", ("heads", None)),
        (r"ssm/(dt|b|c)_norm$", (None,)),
        (r"ssm/out$", ("heads", "embed")),
        (r"attn/qkv$", ("embed", "heads")),
        (r"attn/out$", ("heads", "embed")),
        (r"mlp/(gate|up)$", ("embed", "mlp")),
        (r"mlp/down$", ("mlp", "embed")),
        (r"embed/tokens$", ("vocab", "embed")),
        (r"(^|/)\w*norm\d?/scale$", ("norm",)),
    ),
    # the serving KV page pool (serving/paged_cache.py): pages over the
    # ZeRO axis (capacity scales with fsdp), heads over the Megatron axis
    # (heads and head_dim share the pool's minor dim, heads major)
    "serving_kv": (
        (r"kv_pool/(k|v)$",
         ("layers", "kv_pages", "page_slot", "heads")),
    ),
}

#: family → regex marking scanned-stack leaves (whose missing leading dims
#: pad from STACK_AXES); families without scanned stacks omit the entry
STACK_MARKERS: dict[str, str] = {
    "gpt": r"(^|/)layers/",
    "gpt_moe": r"(^|/)layers/",
    "gpt_lora": r"(^|/)layers/",
    "vision": r"(^|/)blocks/",
    "ernie": r"(^|/)layers/",
    "mla_moe": r"(^|/)(dense_layers|moe_layers|mtp/layers)/",
    "swa_moe": r"(^|/)(full|window)_(dense|moe)/",
    "gdn_mla": r"(^|/)(linear|latent)_(dense|moe)/",
    "conv_moe": r"(^|/)(conv|full)_(dense|moe)/",
    "samba_y": r"(^|/)(scan|window|full|gmu|cross)/",
    "ssm_mqa": r"(^|/)(scan|full)/",
}

#: families whose fully-replicated leaves are accepted at ANY size by the
#: forgotten-spec audit (imagen declares replication; everything else
#: above the size threshold is a hazard finding)
REPLICATED_OK = frozenset({"imagen"})

#: bytes above which a fully-replicated leaf is a "forgotten spec" finding
#: (the classic case: an embedding table nobody wrote a rule for). Sized
#: above the zoo's largest INTENDED replication — the 8k-context config's
#: 64 MiB position table (embed shards only at ZeRO stage 3) — while a
#: forgotten 50k-vocab embedding (hundreds of MiB) still trips it.
DEFAULT_REPLICATED_BYTES = 128 << 20


def families() -> tuple[str, ...]:
    """Registered model families, sorted."""
    return tuple(sorted(PARTITION_RULES))


def family_of(module: Any) -> Optional[str]:
    """The registry family a task module declares (``spec_family``
    attribute/property on ``BasicModule`` subclasses), or None for unknown
    modules — consumers then fall back to the flax logical metadata with a
    warning instead of mis-sharding silently."""
    fam = getattr(module, "spec_family", None)
    if fam is not None and fam not in PARTITION_RULES:
        raise KeyError(f"module {type(module).__name__} declares unknown "
                       f"spec family {fam!r}; registered: {families()}")
    return fam


# --------------------------------------------------------------- resolution

def _matches(family: str, name: str) -> list[tuple[int, str, Any]]:
    """Every ``(index, pattern, template)`` of ``family`` matching ``name``."""
    try:
        rules = PARTITION_RULES[family]
    except KeyError:
        raise KeyError(f"unknown spec family {family!r}; registered: "
                       f"{families()}") from None
    return [(i, pat, tpl) for i, (pat, tpl) in enumerate(rules)
            if re.search(pat, name)]


def _is_scalar(shape: tuple) -> bool:
    size = 1
    for d in shape:
        size *= int(d)
    return len(shape) == 0 or size == 1


def _stack_padded(family: str, name: str, template: Any,
                  ndim: int) -> tuple:
    """Template → full-rank logical tuple, padding stacked leading dims."""
    if template == REPLICATED:
        return (None,) * ndim
    tpl = tuple(template)
    if len(tpl) == ndim:
        return tpl
    marker = STACK_MARKERS.get(family)
    extra = ndim - len(tpl)
    if marker and re.search(marker, name) and 0 < extra <= len(STACK_AXES):
        return STACK_AXES[-extra:] + tpl
    raise ValueError(
        f"partition rule for {name!r} (family {family!r}) has "
        f"{len(tpl)} axes but the leaf has rank {ndim} and no stack "
        f"marker applies")


def spec_for(family: str, name: str, shape: tuple,
             layout: Optional[SpecLayout] = None) -> tuple:
    """Canonical mesh-axes tuple for one named leaf (first match wins;
    scalars and size-1 leaves always replicate; unmatched raises)."""
    layout = layout or SpecLayout()
    if _is_scalar(tuple(shape)):
        return ()
    matched = _matches(family, name)
    if not matched:
        raise KeyError(
            f"no partition rule in family {family!r} matches leaf {name!r} "
            f"— add a rule to PARTITION_RULES in parallel/rules.py")
    _, _, template = matched[0]
    logical = _stack_padded(family, name, template, len(shape))
    return layout.to_mesh(logical)


def canonicalize(entries: Iterable[Any]) -> tuple:
    """Drop trailing Nones — the canonical spec form every consumer and
    test compares in (``P('tensor')`` and ``P('tensor', None)`` place
    identically; only one spelling is allowed to exist)."""
    out = list(entries)
    while out and out[-1] is None:
        out.pop()
    return tuple(out)


def _keystr(key: Any) -> str:
    """One pytree path component → a stable string (no jax.keystr quirks)."""
    for attr in ("key", "name", "idx"):
        if hasattr(key, attr):
            return str(getattr(key, attr))
    return re.sub(r"\W+", "", str(key))


def tree_leaf_names(tree: Any) -> list[tuple[str, Any]]:
    """Slash-joined path names for every leaf of an (unboxed) pytree —
    the named-pytree surface the regex rules match against."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [("/".join(_keystr(k) for k in kp), leaf) for kp, leaf in flat]


def _unboxed(tree: Any) -> Any:
    """Strip flax ``nn.Partitioned`` boxes when flax is importable; the
    registry resolves by NAME, the logical metadata is a cross-checked
    annotation (tests/test_zz_shardcheck.py parity gate)."""
    try:
        from flax.core import meta
    except ImportError:  # pragma: no cover - flax is a hard dep in practice
        return tree
    return meta.unbox(tree)


def match_partition_rules(family: str, tree: Any,
                          layout: Optional[SpecLayout] = None) -> Any:
    """Pytree of canonical ``PartitionSpec`` for ``tree`` (SNIPPETS [2]
    shape: regex over named leaves, first match wins, scalars replicate,
    unmatched leaves raise naming the leaf)."""
    import jax
    from jax.sharding import PartitionSpec as P

    layout = layout or SpecLayout()
    tree = _unboxed(tree)

    def resolve(kp, leaf):
        name = "/".join(_keystr(k) for k in kp)
        shape = tuple(getattr(leaf, "shape", ()))
        return P(*spec_for(family, name, shape, layout))

    return jax.tree_util.tree_map_with_path(resolve, tree)


def registry_specs(family: str, tree: Any,
                   layout: Optional[SpecLayout] = None) -> Any:
    """Alias of :func:`match_partition_rules` under its consumer-facing
    name — the single resolution entrypoint the engine, the checkpoint
    codecs and the auditor share."""
    return match_partition_rules(family, tree, layout)


def named_shardings(tree: Any, mesh: Any, family: str,
                    layout: Optional[SpecLayout] = None) -> Any:
    """``registry_specs`` materialised as ``NamedSharding`` on ``mesh``
    (what ``jax.jit`` out_shardings and ``device_put`` consume)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    specs = registry_specs(family, tree, layout)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# ------------------------------------------------ ZeRO helpers (stage 1-3)

def first_free_divisible_dim(shape: Iterable[int], spec: Iterable[Any],
                             size: int) -> Optional[int]:
    """First still-replicated dim divisible by (and at least) ``size`` —
    the shared placement policy of ``zero_sharding``/``zero_grad_specs``
    (``parallel/sharding.py``), kept here so the runtime helpers and the
    static auditor agree on where a ZeRO axis may land."""
    spec = list(spec)
    for dim, d in enumerate(shape):
        entry = spec[dim] if dim < len(spec) else None
        if entry is None and int(d) % size == 0 and int(d) >= size:
            return dim
    return None


def with_fsdp_axis(shape: tuple, spec: Iterable[Any], size: int,
                   axis: str = "fsdp") -> tuple:
    """Augment a canonical spec with the ZeRO axis: the existing entries
    (tensor-parallel / stage-3 dims) are kept and ``axis`` lands on the
    first free divisible dim — unless the param's own spec already uses
    it. One placement for Adam moments (``zero_sharding``) and gradients
    (``zero_grad_specs``), so the sharded update reads both from the same
    shard. Returns the canonical (no-trailing-None) tuple.
    """
    entries = list(spec)
    entries += [None] * (len(shape) - len(entries))
    used = set()
    for entry in entries:
        for a in (entry if isinstance(entry, (tuple, list)) else (entry,)):
            if a is not None:
                used.add(a)
    if size > 1 and axis not in used:
        dim = first_free_divisible_dim(shape, entries, size)
        if dim is not None:
            entries[dim] = axis
    return canonicalize(entries)


#: which memory term each ZeRO stage starts sharding over fsdp — consumed
#: by ``parallel/auto_layout._per_device_bytes`` AND by the engine's
#: stage gating, so the memory model and the runtime cannot disagree
ZERO_STAGE_TERMS = {"moments": 1, "grads": 2, "weights": 3}


def stage_shards(term: str, stage: int) -> bool:
    """True when ZeRO ``stage`` shards ``term`` over the fsdp axis."""
    return stage >= ZERO_STAGE_TERMS[term]


# ------------------------------------------------------- derived one-liners

def kv_pool_spec(layout: Optional[SpecLayout] = None, *,
                 num_kv_heads: Optional[int] = None,
                 tensor_degree: Optional[int] = None):
    """The serving KV pool's placement, resolved through the registry
    (family ``serving_kv``): pages over ``fsdp``, heads over ``tensor``.

    The heads the pool's minor dimension holds are the KEY-VALUE heads;
    told how many there are and how wide the ``tensor`` axis is, it
    refuses a split that would cut a head in two: a head's ``head_dim``
    values are one contiguous run of a token's row, the decode kernel
    reads whole heads, and ``tensor`` devices beyond the key-value heads
    would each need a head another device holds."""
    from jax.sharding import PartitionSpec as P

    if num_kv_heads is not None and tensor_degree is not None and \
            num_kv_heads % tensor_degree:
        raise ValueError(
            f"the KV pool's heads axis holds {num_kv_heads} key-value "
            f"heads; a tensor axis of {tensor_degree} does not divide "
            f"them (a head is not split, and a device cannot hold a "
            f"fraction of one): keep tensor_degree a divisor of the "
            f"key-value heads or replicate the pool's heads")
    return P(*spec_for("serving_kv", "kv_pool/k", (1, 2, 2, 2),
                       layout or SpecLayout()))


def batch_spec():
    """Global-batch placement: the ``batch`` logical axis' mesh entry
    (``(data, fsdp)`` — dp × sharding is the data world)."""
    from jax.sharding import PartitionSpec as P

    return P(*canonicalize((SpecLayout().mesh_entry("batch"),)))


def activation_spec(*logical: Optional[str]):
    """Mesh placement of an activation annotated with ``logical`` axis
    names under the default layout — what the Pallas kernels' ``shard_map``
    wrappers (``ops/``) name as their in/out specs."""
    from jax.sharding import PartitionSpec as P

    return P(*SpecLayout().to_mesh(logical))


def registry_fingerprint() -> str:
    """Content hash of the rule tables + axis vocabulary — stamped into
    checkpoint metas (both codecs) and folded into the shardcheck result
    cache key, so a registry edit invalidates cached audits and a restore
    under different rules is visible in the meta."""
    payload = repr((MESH_AXES, LOGICAL_AXES, STACK_AXES,
                    sorted(PARTITION_RULES.items()),
                    sorted(STACK_MARKERS.items()),
                    sorted(REPLICATED_OK), sorted(ZERO_STAGE_TERMS.items())))
    return hashlib.sha1(payload.encode("utf-8")).hexdigest()[:16]


def family_fingerprint(family: str) -> str:
    """Content hash of ONE family's rule table + the shared axis
    vocabulary — the adapter-artifact provenance stamp
    (``finetune/checkpoint.py``). Narrower than
    :func:`registry_fingerprint` on purpose: an adapter's naming and
    placement contract is its own family's table, so an unrelated
    family's edit must not refuse every published adapter."""
    if family not in PARTITION_RULES:
        raise KeyError(f"unknown spec family {family!r}; registered: "
                       f"{families()}")
    payload = repr((MESH_AXES, LOGICAL_AXES, STACK_AXES, family,
                    PARTITION_RULES[family], STACK_MARKERS.get(family)))
    return hashlib.sha1(payload.encode("utf-8")).hexdigest()[:16]


# ------------------------------------------------------------------- audit

def _degree(degrees: dict, entry: Any) -> int:
    """Combined mesh degree of one spec entry (axis or axes tuple)."""
    total = 1
    for a in (entry if isinstance(entry, (tuple, list)) else (entry,)):
        if a is not None:
            total *= max(int(degrees.get(a, 1)), 1)
    return total


def audit_leaves(family: str, leaves: list[tuple[str, Any]],
                 layout: Optional[SpecLayout] = None,
                 degrees: Optional[dict] = None,
                 replicated_bytes: int = DEFAULT_REPLICATED_BYTES,
                 ) -> tuple[list[dict], set[int]]:
    """Statically audit one named abstract tree against its family table.

    Returns ``(issues, matched_rule_indexes)``. Issue kinds:

    - ``unmatched``: a non-scalar leaf no rule matches (the drifted-model
      hazard — today this would surface at jit bind time);
    - ``ambiguous``: a leaf matched by two rules that resolve to DIFFERENT
      specs (first-match-wins hides the conflict; overlapping rules with
      identical specs are benign);
    - ``rank-mismatch`` / ``unknown-axis``: a rule template that cannot
      apply to the leaf it matches (registry typos);
    - ``indivisible``: a sharded dim not divisible by the product of its
      mesh degrees for this config's layout;
    - ``replicated-large``: a fully-replicated leaf above
      ``replicated_bytes`` in a family not in ``REPLICATED_OK`` (the
      forgotten-spec hazard).

    ``matched_rule_indexes`` feeds the per-family dead-rule accounting in
    ``parallel/shardcheck.py``.
    """
    layout = layout or SpecLayout()
    degrees = degrees or {}
    issues: list[dict] = []
    used: set[int] = set()

    def issue(kind: str, name: str, message: str) -> None:
        issues.append({"kind": kind, "family": family, "leaf": name,
                       "message": message})

    for name, leaf in leaves:
        shape = tuple(int(d) for d in getattr(leaf, "shape", ()))
        if _is_scalar(shape):
            continue
        matched = _matches(family, name)
        if not matched:
            issue("unmatched", name,
                  f"leaf {name!r} {shape} matches no rule in family "
                  f"{family!r} — it would replicate silently; add a rule "
                  f"to PARTITION_RULES (parallel/rules.py)")
            continue
        used.add(matched[0][0])
        try:
            logical = _stack_padded(family, name, matched[0][2], len(shape))
            spec = layout.to_mesh(logical)
        except (ValueError, KeyError) as e:
            kind = "rank-mismatch" if isinstance(e, ValueError) \
                else "unknown-axis"
            issue(kind, name, f"rule {matched[0][1]!r}: {e}")
            continue
        if len(matched) > 1:
            others = []
            for idx, pat, tpl in matched[1:]:
                try:
                    other = layout.to_mesh(
                        _stack_padded(family, name, tpl, len(shape)))
                except (ValueError, KeyError):
                    other = ("<unresolvable>",)
                if other != spec:
                    others.append((pat, other))
            if others:
                issue("ambiguous", name,
                      f"leaf {name!r} matched by {matched[0][1]!r} -> "
                      f"{spec} but also by "
                      f"{', '.join(f'{p!r} -> {s}' for p, s in others)} — "
                      f"first-match-wins is hiding a conflicting rule")
        for dim, entry in enumerate(spec):
            deg = _degree(degrees, entry)
            if deg > 1 and shape[dim] % deg:
                issue("indivisible", name,
                      f"leaf {name!r} dim {dim} ({shape[dim]}) is sharded "
                      f"over {entry!r} (degree {deg}) but is not divisible "
                      f"by it for this layout")
        if not canonicalize(spec) and family not in REPLICATED_OK:
            nbytes = 1
            for d in shape:
                nbytes *= d
            nbytes *= getattr(getattr(leaf, "dtype", None), "itemsize", 4)
            if nbytes >= replicated_bytes:
                issue("replicated-large", name,
                      f"leaf {name!r} ({nbytes >> 20} MiB) resolves to a "
                      f"fully replicated spec — every device pays its full "
                      f"bytes; if that is intended, add the family to "
                      f"REPLICATED_OK, otherwise a rule is missing")
    return issues, used
