"""Configuration of the hybrid decoder family: gated-delta-rule (linear
attention) layers beside latent-attention layers, over sparse experts held
as a share.

The keys are those of the published ``config.json`` of
GigaChat3.5-432B-A28B (``docs/gdn_mla.md``): DeepSeek-V3's latent-attention
and routing keys (``q_lora_rank``, ``kv_lora_rank``, ``n_routed_experts``
...), the Qwen3-Next modelling code's linear-attention keys
(``linear_conv_kernel_dim``, ``linear_num_key_heads`` ...) and the model's
own (``full_attention_layers``, ``layernorm_type``, ``swiglu_limit`` ...).
A recipe states EVERY published key (``PUBLISHED_KEYS``): the dataclass's
defaults are for toy tests, and ``config_from_dict`` refuses a recipe that
omits one by name. Three keys describe what no published file can: the
chip's share of the expert layer (``experts_held``, ``first_expert_held``:
the router still scores all ``n_routed_experts``) and of the vocabulary
(``vocab_size`` is the number of ids held here). The readings this family
takes of what the published keys name without defining are not options:
each is one function of ``models/gdn_mla/model.py`` (``docs/gdn_mla.md``
"Assumed").
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp

LINEAR, LATENT = "linear", "latent"

#: every key of the published config.json that describes the model (what
#: ``config_from_dict`` insists on)
PUBLISHED_KEYS = (
    "vocab_size", "max_position_embeddings", "hidden_size",
    "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
    "num_attention_heads", "n_shared_experts", "n_routed_experts",
    "routed_scaling_factor", "kv_lora_rank", "q_lora_rank",
    "qk_rope_head_dim", "v_head_dim", "qk_nope_head_dim", "n_group",
    "topk_group", "num_experts_per_tok", "first_k_dense_replace",
    "norm_topk_prob", "rope_interleave", "hidden_act", "rms_norm_eps",
    "rope_theta", "rope_scaling", "norm_type", "layernorm_type",
    "layernorm_gating_weight", "gated_attention",
    "use_shared_expert_sigmoid", "use_mla_scaling_factor",
    "linear_attention_type", "full_attention_layers", "linear_key_head_dim",
    "linear_value_head_dim", "linear_conv_kernel_dim",
    "linear_num_key_heads", "linear_num_value_heads", "linear_gating_type",
    "linear_sigmoid_gate_scale", "linear_attn_o_norm_eps", "swiglu_limit",
    "num_nextn_predict_layers")

#: what the family can compute of each key that names a mechanism
_SUPPORTED = {
    "norm_type": ("ZeroCenteredGatedNorm",),
    "layernorm_type": ("pre_post",),
    "linear_attention_type": ("GigaChat35GatedDeltaNet",),
    "linear_gating_type": ("gated_rmsnorm_sigmoid_zero_centered",),
    "hidden_act": ("silu",),
    "rope_interleave": (True,),
    "use_shared_expert_sigmoid": (False,),
    "n_group": (1,), "topk_group": (1,),
    "num_nextn_predict_layers": (0,),
}


@dataclasses.dataclass(eq=False)
class GDNMLAConfig:
    """Architecture and execution settings (YAML ``Model:`` section)."""

    module: str = "GDNMLAModule"
    vocab_size: int = 128256             # ids held here (the chip's slice)
    max_position_embeddings: int = 262144
    hidden_size: int = 7168
    intermediate_size: int = 18432       # width of a dense MLP
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 3       # leading layers with a dense MLP
    full_attention_layers: tuple = ()    # latent attention; the rest linear
    # latent attention (DeepSeek-V3's keys)
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 100000.0
    rope_scaling: Any = None             # the published YaRN group, or None
    rope_interleave: bool = True
    use_mla_scaling_factor: bool = True
    gated_attention: bool = True
    # the gated delta rule (the Qwen3-Next modelling code's keys)
    linear_attention_type: str = "GigaChat35GatedDeltaNet"
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    linear_num_key_heads: int = 32
    linear_num_value_heads: int = 64
    linear_gating_type: str = "gated_rmsnorm_sigmoid_zero_centered"
    linear_sigmoid_gate_scale: float = 2.0
    linear_attn_o_norm_eps: float = 1e-6
    # norms
    norm_type: str = "ZeroCenteredGatedNorm"
    layernorm_type: str = "pre_post"
    layernorm_gating_weight: float = 2.0
    rms_norm_eps: float = 1e-6
    # feed-forward
    hidden_act: str = "silu"
    swiglu_limit: float = 10.0
    n_routed_experts: int = 256          # the router's width
    experts_held: int | None = None      # None: all of them
    first_expert_held: int = 0
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    use_shared_expert_sigmoid: bool = False
    num_nextn_predict_layers: int = 0    # none is built: nothing drafts
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.experts_held is None:
            self.experts_held = self.n_routed_experts
        assert 0 <= self.first_expert_held and \
            self.first_expert_held + self.experts_held \
            <= self.n_routed_experts, "the held experts lie past the router"
        assert self.first_k_dense_replace <= n
        self.full_attention_layers = tuple(
            int(l) for l in self.full_attention_layers if int(l) < n)
        for key, allowed in _SUPPORTED.items():
            assert getattr(self, key) in allowed, \
                f"{key}: {getattr(self, key)!r} is not one of {allowed}"
        assert self.linear_num_value_heads % self.linear_num_key_heads == 0, \
            "value heads are a multiple of the key heads"
        assert self.n_shared_experts in (0, 1)
        self.rope_scaling = dict(self.rope_scaling) \
            if self.rope_scaling else None
        if self.rope_scaling is not None:
            assert self.rope_scaling.get("type") == "yarn", self.rope_scaling

    # names ``models/swa_moe/model.py:held_experts`` reads of a config
    @property
    def num_experts(self) -> int:
        return self.n_routed_experts

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """What a token leaves in the latent cache: ``(c_kv, k_r)``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def conv_channels(self) -> int:
        """Channels of the causal convolution: ``[q; k; v]``."""
        return 2 * self.linear_num_key_heads * self.linear_key_head_dim \
            + self.linear_num_value_heads * self.linear_value_head_dim

    # ----------------------------------------------------- the layer pattern
    def mixer_of(self, layer: int) -> str:
        return LATENT if layer in self.full_attention_layers else LINEAR

    def kind_of(self, layer: int) -> str:
        """The stack a layer's parameters live in: layers of one shape."""
        mlp = "dense" if layer < self.first_k_dense_replace else "moe"
        return f"{self.mixer_of(layer)}_{mlp}"

    def kinds(self) -> dict:
        """kind -> how many layers it stacks, in order of first appearance."""
        out: dict = {}
        for l in range(self.num_hidden_layers):
            out[self.kind_of(l)] = out.get(self.kind_of(l), 0) + 1
        return out

    def layers_of(self, mixer: str) -> int:
        return sum(self.mixer_of(l) == mixer
                   for l in range(self.num_hidden_layers))

    def runs(self) -> list:
        """The published order as runs of consecutive layers of one kind:
        ``(kind, first index in the kind's stack, layers, first index among
        the layers of the same mixer)`` — the last is the layer's place in
        its cache."""
        out, in_stack, in_cache = [], {}, {LINEAR: 0, LATENT: 0}
        for l in range(self.num_hidden_layers):
            kind, mixer = self.kind_of(l), self.mixer_of(l)
            at, cache_at = in_stack.get(kind, 0), in_cache[mixer]
            if out and out[-1][0] == kind:
                out[-1][2] += 1
            else:
                out.append([kind, at, 1, cache_at])
            in_stack[kind] = at + 1
            in_cache[mixer] = cache_at + 1
        return [tuple(r) for r in out]


_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
           "float16": jnp.float16}


def config_from_dict(d: dict) -> GDNMLAConfig:
    """Build the config from a YAML ``Model:`` section (unknown keys, such
    as ``name``, are dropped). Every published key has to be stated: a
    recipe that omits one is refused by name."""
    # (``rope_scaling: null`` is a statement: plain rotary)
    missing = [k for k in PUBLISHED_KEYS
               if k not in d or (d[k] is None and k != "rope_scaling")]
    if missing:
        raise ValueError(
            "a recipe of Model.module GDNMLAModule states every published "
            f"key; missing: {', '.join(missing)}")
    known = {f.name for f in dataclasses.fields(GDNMLAConfig)}
    kwargs = {k: v for k, v in d.items() if k in known and v is not None}
    for key in ("dtype", "param_dtype"):
        if isinstance(kwargs.get(key), str):
            kwargs[key] = _DTYPES[kwargs[key]]
    kwargs["full_attention_layers"] = tuple(kwargs["full_attention_layers"])
    return GDNMLAConfig(**kwargs)
