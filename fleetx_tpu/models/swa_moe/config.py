"""Configuration of the windowed-attention sparse-expert decoder family.

Two published models are members (``docs/swa_moe.md``): Laguna-S-2.1 and
SmallThinker-21BA3B-Instruct. The keys are those of the first's
``config.json`` (``layer_types``, ``num_attention_heads_per_layer``,
``mlp_only_layers``, ``rope_parameters``, ``num_experts`` ...); a recipe of
the second derives them from its own published keys and says which beside
them. The layer pattern is DATA: which layers attend over a window, how
many query heads each layer has, which layers have a dense MLP, whether a
layer type rotates at all. So is everything else the members differ in:
the gate, where the router reads and how it scores, the experts'
activation, the shared expert, the routed scaling. A recipe states each of
those keys (``MEMBER_KEYS``): ``config_from_dict`` lends no model's number
to another. Three keys describe what no published file can: the chip's
share of the expert layer (``experts_held`` and ``first_expert_held``: the
router still scores all ``num_experts``) and of the vocabulary
(``vocab_size`` is the number of ids held here; traffic, logits and
sampling are over them).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp

FULL, WINDOW = "full_attention", "sliding_attention"

GATINGS = ("per-head", "none")
ROUTER_INPUTS = ("post_attention", "pre_attention")
ROUTER_SCORINGS = ("softmax_topk", "topk_softmax")
ACTIVATIONS = ("silu", "relu")

#: the keys on which the family's members differ: a recipe states every one
#: (``config_from_dict``); the dataclass's own defaults are for toy tests
MEMBER_KEYS = ("moe_routed_scaling_factor", "shared_expert_intermediate_size",
               "mlp_only_layers", "sliding_window", "num_key_value_heads",
               "gating", "router_input", "router_scoring", "hidden_act",
               "rope_parameters")

#: the dataclass's default ``rope_parameters`` (Laguna-S-2.1's published
#: groups): what a toy test that names none builds on, never a recipe
_ROPE = {
    FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
           "original_max_position_embeddings": 8192, "beta_slow": 1,
           "beta_fast": 32, "attention_factor": 1.4852030263919618,
           "partial_rotary_factor": 0.5},
    WINDOW: {"rope_type": "default", "rope_theta": 10000,
             "partial_rotary_factor": 1},
}


@dataclasses.dataclass(eq=False)
class SWAMoEConfig:
    """Architecture and execution settings (YAML ``Model:`` section)."""

    # ``Model.module``: what finds the task module (``models/__init__.py``)
    # and the serving family (``serving/registry.py``)
    module: str = "SWAMoEModule"
    vocab_size: int = 100352             # ids held here (the chip's slice)
    hidden_size: int = 3072
    intermediate_size: int = 12288       # width of a dense MLP
    num_hidden_layers: int = 48
    num_attention_heads: int = 48        # of a layer the list below lacks
    num_attention_heads_per_layer: tuple = ()
    layer_types: tuple = ()              # FULL or WINDOW, a layer each
    mlp_only_layers: tuple = (0,)        # layers with a dense MLP
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512            # keys a window query sees, itself in
    # layer type -> the published group; a layer type mapped to None (or
    # "none") carries no position signal at all: its queries and keys are
    # not rotated
    rope_parameters: Any = None
    rms_norm_eps: float = 1e-6
    num_experts: int = 256               # the router's width
    experts_held: int | None = None      # None: all of them
    first_expert_held: int = 0
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    norm_topk_prob: bool = True
    moe_routed_scaling_factor: float = 2.5
    # a sigmoid gate a query head on the attention output, or none
    gating: str = "per-head"
    # the router reads the normed state after attention, or the layer's
    # normed INPUT (computed before attention, applied after it)
    router_input: str = "post_attention"
    # a softmax over all experts then the k largest, or the k largest
    # logits then a softmax over those k alone
    router_scoring: str = "softmax_topk"
    hidden_act: str = "silu"             # of every gated MLP
    max_position_embeddings: int = 1048576
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.experts_held is None:
            self.experts_held = self.num_experts
        assert 0 <= self.first_expert_held and \
            self.first_expert_held + self.experts_held <= self.num_experts, \
            "the held experts lie past the router"
        # the published lists are as long as the published depth: a cut in
        # depth keeps the leading layers
        types = tuple(self.layer_types) or (FULL,) * n
        heads = tuple(self.num_attention_heads_per_layer) \
            or (self.num_attention_heads,) * n
        assert len(types) >= n and len(heads) >= n, \
            "layer_types / num_attention_heads_per_layer shorter than the depth"
        self.layer_types = types[:n]
        self.num_attention_heads_per_layer = tuple(int(h) for h in heads[:n])
        assert set(self.layer_types) <= {FULL, WINDOW}, self.layer_types
        self.mlp_only_layers = tuple(
            int(l) for l in self.mlp_only_layers if int(l) < n)
        assert all(h % self.num_key_value_heads == 0
                   for h in self.num_attention_heads_per_layer), \
            "query heads are a multiple of the key-value heads"
        for value, allowed, key in (
                (self.gating, GATINGS, "gating"),
                (self.router_input, ROUTER_INPUTS, "router_input"),
                (self.router_scoring, ROUTER_SCORINGS, "router_scoring"),
                (self.hidden_act, ACTIVATIONS, "hidden_act")):
            assert value in allowed, \
                f"{key}: {value!r} is not one of {allowed}"
        given = dict(self.rope_parameters or {})
        groups = {FULL: given.get("full_attention", _ROPE[FULL]),
                  WINDOW: given.get("sliding_attention", _ROPE[WINDOW])}
        self.rope_parameters = {
            t: None if g in (None, "none") else dict(g)
            for t, g in groups.items()}

    # ----------------------------------------------------- the layer pattern
    def kind_of(self, layer: int) -> str:
        """The stack a layer's parameters live in: layers of one shape."""
        attn = "window" if self.layer_types[layer] == WINDOW else "full"
        mlp = "dense" if layer in self.mlp_only_layers else "moe"
        return f"{attn}_{mlp}"

    def heads_of(self, kind: str) -> int:
        """Query heads of the layers of ``kind`` (one count a kind)."""
        counts = {self.num_attention_heads_per_layer[l]
                  for l in range(self.num_hidden_layers)
                  if self.kind_of(l) == kind}
        assert len(counts) == 1, f"layers of {kind} differ in heads: {counts}"
        return counts.pop()

    def kinds(self) -> dict:
        """kind -> how many layers it stacks, in order of first appearance."""
        out: dict = {}
        for l in range(self.num_hidden_layers):
            out[self.kind_of(l)] = out.get(self.kind_of(l), 0) + 1
        return out

    def runs(self) -> list:
        """The published order as runs of consecutive layers of one kind:
        ``(kind, first index in the kind's stack, layers, first index among
        the layers of the same attention type)`` — the last is the layer's
        place in its cache."""
        out, in_stack, in_cache = [], {}, {"full": 0, "window": 0}
        for l in range(self.num_hidden_layers):
            kind = self.kind_of(l)
            attn = kind.split("_")[0]
            at, cache_at = in_stack.get(kind, 0), in_cache[attn]
            if out and out[-1][0] == kind:
                out[-1][2] += 1
            else:
                out.append([kind, at, 1, cache_at])
            in_stack[kind] = at + 1
            in_cache[attn] = cache_at + 1
        return [tuple(r) for r in out]

    def layers_of(self, attn: str) -> int:
        """Layers whose attention is ``attn`` (``full`` or ``window``)."""
        want = WINDOW if attn == "window" else FULL
        return sum(t == want for t in self.layer_types)


_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
           "float16": jnp.float16}


def config_from_dict(d: dict) -> SWAMoEConfig:
    """Build the config from a YAML ``Model:`` section (unknown keys, such
    as ``name``, are dropped). The keys on which the family's members
    differ (``MEMBER_KEYS``) take no default on this way: a recipe that
    omits one is refused by name."""
    missing = [k for k in MEMBER_KEYS if d.get(k) is None]
    if missing:
        raise ValueError(
            "a recipe of Model.module SWAMoEModule states every key the "
            f"family's members differ in; missing: {', '.join(missing)}")
    rope = d["rope_parameters"]
    absent = [t for t in (FULL, WINDOW) if t not in rope]
    if absent:
        raise ValueError(
            "rope_parameters names both layer types (a group, or 'none' for "
            "a layer type that does not rotate); missing: "
            + ", ".join(absent))
    known = {f.name for f in dataclasses.fields(SWAMoEConfig)}
    kwargs = {k: v for k, v in d.items() if k in known and v is not None}
    for key in ("dtype", "param_dtype"):
        if isinstance(kwargs.get(key), str):
            kwargs[key] = _DTYPES[kwargs[key]]
    for key in ("num_attention_heads_per_layer", "layer_types",
                "mlp_only_layers"):
        if key in kwargs:
            kwargs[key] = tuple(kwargs[key])
    return SWAMoEConfig(**kwargs)
