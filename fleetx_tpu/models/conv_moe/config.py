"""Configuration of the short-convolution decoder family: gated
depth-wise convolution layers beside grouped-query attention layers, over
sparse experts chosen by a sigmoid router.

The keys are those of the published ``config.json`` of LFM2-24B-A2B
(``model_type: lfm2_moe``; ``docs/conv_moe.md``): the layer pattern is DATA
(``layer_types`` names each layer ``conv`` or ``full_attention``;
``num_dense_layers`` leading layers carry a dense MLP, the rest experts). A
recipe states EVERY published key (``PUBLISHED_KEYS``): the dataclass's
defaults are for toy tests, and ``config_from_dict`` refuses a recipe that
omits one by name. The head's width is ``hidden_size / num_attention_heads``
(the published ``head_dim`` is null). What the published keys name without
defining is not an option: each reading is one function of
``models/conv_moe/model.py`` (``docs/conv_moe.md`` "Assumed").
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp

CONV, FULL = "conv", "full_attention"
#: a layer type -> the first word of its stack's name
_STACK = {CONV: "conv", FULL: "full"}

#: every key of the published config.json that describes the model (what
#: ``config_from_dict`` insists on)
PUBLISHED_KEYS = (
    "vocab_size", "max_position_embeddings", "hidden_size",
    "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
    "layer_types", "num_dense_layers", "num_attention_heads",
    "num_key_value_heads", "conv_L_cache", "conv_bias", "norm_eps",
    "norm_topk_prob", "num_experts", "num_experts_per_tok",
    "rope_parameters", "routed_scaling_factor", "use_expert_bias")


@dataclasses.dataclass(eq=False)
class ConvMoEConfig:
    """Architecture and execution settings (YAML ``Model:`` section)."""

    module: str = "ConvMoEModule"
    vocab_size: int = 65536
    max_position_embeddings: int = 128000
    hidden_size: int = 2048
    intermediate_size: int = 11776       # width of a dense MLP
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 9
    layer_types: tuple = ()              # "conv" / "full_attention" a layer
    num_dense_layers: int = 1            # leading layers with a dense MLP
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3                # taps of the causal convolution
    conv_bias: bool = False
    norm_eps: float = 1e-5
    rope_parameters: Any = None          # {"rope_theta", "rope_type"}
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        n = self.num_hidden_layers
        self.layer_types = tuple(self.layer_types)
        assert len(self.layer_types) == n, \
            f"layer_types names {len(self.layer_types)} layers of {n}"
        assert set(self.layer_types) <= {CONV, FULL}, self.layer_types
        assert 0 <= self.num_dense_layers <= n
        assert not self.conv_bias, "conv_bias: no biased projection is written"
        assert self.conv_L_cache >= 2, "a convolution of one tap has no tail"
        assert self.hidden_size % self.num_attention_heads == 0
        assert self.num_attention_heads % self.num_key_value_heads == 0, \
            "query heads are a multiple of the key-value heads"
        self.rope_parameters = dict(self.rope_parameters or {})
        assert self.rope_parameters.get("rope_type", "default") == "default", \
            f"rope_type {self.rope_parameters.get('rope_type')!r} is not " \
            f"written for this family"
        assert "rope_theta" in self.rope_parameters, "rope_parameters: no theta"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    # names ``models/swa_moe/model.py:held_experts`` reads of a config: a
    # chip holds every expert of a layer, and the experts gate with SiLU
    @property
    def experts_held(self) -> int:
        return self.num_experts

    first_expert_held = 0
    hidden_act = "silu"

    # ----------------------------------------------------- the layer pattern
    def kind_of(self, layer: int) -> str:
        """The stack a layer's parameters live in: layers of one shape."""
        mlp = "dense" if layer < self.num_dense_layers else "moe"
        return f"{_STACK[self.layer_types[layer]]}_{mlp}"

    def kinds(self) -> dict:
        """kind -> how many layers it stacks, in order of first appearance."""
        out: dict = {}
        for l in range(self.num_hidden_layers):
            out[self.kind_of(l)] = out.get(self.kind_of(l), 0) + 1
        return out

    def layers_of(self, layer_type: str) -> int:
        return sum(t == layer_type for t in self.layer_types)

    def runs(self) -> list:
        """The published order as runs of consecutive layers of one kind:
        ``(kind, first index in the kind's stack, layers, first index among
        the layers of the same type)`` — the last is the layer's place in
        its cache."""
        out, in_stack, in_cache = [], {}, {CONV: 0, FULL: 0}
        for l, layer_type in enumerate(self.layer_types):
            kind = self.kind_of(l)
            at, cache_at = in_stack.get(kind, 0), in_cache[layer_type]
            if out and out[-1][0] == kind:
                out[-1][2] += 1
            else:
                out.append([kind, at, 1, cache_at])
            in_stack[kind] = at + 1
            in_cache[layer_type] = cache_at + 1
        return [tuple(r) for r in out]


_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
           "float16": jnp.float16}


def config_from_dict(d: dict) -> ConvMoEConfig:
    """Build the config from a YAML ``Model:`` section (unknown keys, such
    as ``name``, are dropped). Every published key has to be stated: a
    recipe that omits one is refused by name."""
    missing = [k for k in PUBLISHED_KEYS if d.get(k) is None]
    if missing:
        raise ValueError(
            "a recipe of Model.module ConvMoEModule states every published "
            f"key; missing: {', '.join(missing)}")
    known = {f.name for f in dataclasses.fields(ConvMoEConfig)}
    kwargs = {k: v for k, v in d.items() if k in known and v is not None}
    for key in ("dtype", "param_dtype"):
        if isinstance(kwargs.get(key), str):
            kwargs[key] = _DTYPES[kwargs[key]]
    return ConvMoEConfig(**kwargs)
