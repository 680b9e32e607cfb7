"""Configuration of the windowed-attention sparse-expert decoder family.

The keys are those of the published ``config.json`` of the Laguna line of
models (``layer_types``, ``num_attention_heads_per_layer``,
``mlp_only_layers``, ``rope_parameters``, ``num_experts`` ...), so a recipe
reads like the model card. The layer pattern is DATA: which layers attend
over a window, how many query heads each layer has and which layers have a
dense MLP are lists the model walks, nothing in the code names a period.
Three keys describe what the published file cannot: the chip's share of
the expert layer (``experts_held`` and ``first_expert_held``: the router
still scores all ``num_experts``) and of the vocabulary (``vocab_size`` is
the number of ids held here; traffic, logits and sampling are over them).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp

FULL, WINDOW = "full_attention", "sliding_attention"

#: the published ``rope_parameters`` of Laguna-S-2.1, the defaults here
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
    rope_parameters: Any = None          # layer type -> the published group
    rms_norm_eps: float = 1e-6
    num_experts: int = 256               # the router's width
    experts_held: int | None = None      # None: all of them
    first_expert_held: int = 0
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    norm_topk_prob: bool = True
    moe_routed_scaling_factor: float = 2.5
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
        given = dict(self.rope_parameters or {})
        self.rope_parameters = {
            FULL: dict(given.get("full_attention") or _ROPE[FULL]),
            WINDOW: dict(given.get("sliding_attention") or _ROPE[WINDOW])}

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
    as ``name``, are dropped)."""
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
