"""Configuration of the scan / multi-query family: selective-scan (Mamba-1)
layers whose step, ``B`` and ``C`` pass an RMS norm each, and — one layer in
every ``attn_layer_period`` — causal softmax attention with MANY query heads
over FEW (one) key-value heads and no position signal of any kind (the scan
layers carry order). Every layer closes with the same dense gated MLP.

The keys are those of the published ``config.json`` of AI21-Jamba2-3B
(``model_type: jamba``; ``docs/ssm_mqa.md``). A recipe states EVERY
published key (``PUBLISHED_KEYS``): the dataclass's defaults are for toy
tests, and ``config_from_dict`` refuses a recipe that omits one by name.

The layer map is a function of two keys (ASSUMED to read as the ``jamba``
convention reads them: the catalog row lists "order of the layer types"
as not given): layer ``l`` attends where ``l mod attn_layer_period ==
attn_layer_offset``, and scans otherwise — 28 layers, period 14, offset 7:
layers 7 and 21 attend, the other 26 scan. ``num_experts`` is 1, so every
feed-forward part is the dense MLP whatever ``expert_layer_period`` /
``expert_layer_offset`` say (a sparse layer is not written, and a recipe
with more than one expert is refused).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp

SCAN, FULL = "scan", "full"
KINDS = (SCAN, FULL)

#: every key of the published config.json that describes the model (what
#: ``config_from_dict`` insists on). ``sliding_window`` is published null
#: (no window) and a recipe may leave it out
PUBLISHED_KEYS = (
    "vocab_size", "max_position_embeddings", "hidden_size",
    "intermediate_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "attn_layer_period", "attn_layer_offset",
    "expert_layer_period", "expert_layer_offset", "num_experts",
    "num_experts_per_tok", "mamba_d_state", "mamba_d_conv", "mamba_expand",
    "mamba_dt_rank", "mamba_conv_bias", "mamba_proj_bias", "rms_norm_eps",
    "hidden_act", "tie_word_embeddings")


@dataclasses.dataclass(eq=False)
class SSMMQAConfig:
    """Architecture and execution settings (YAML ``Model:`` section)."""

    module: str = "SSMMQAModule"
    vocab_size: int = 65536
    max_position_embeddings: int = 262144
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_hidden_layers: int = 28
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    expert_layer_period: int = 2
    expert_layer_offset: int = 1
    num_experts: int = 1
    num_experts_per_tok: int = 1
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    rms_norm_eps: float = 1e-6
    hidden_act: str = "silu"
    tie_word_embeddings: bool = True
    sliding_window: Any = None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        n = self.num_hidden_layers
        assert 0 <= self.attn_layer_offset < self.attn_layer_period, \
            "attn_layer_offset lies inside the period"
        assert self.layers_of(FULL) >= 1 and self.layers_of(SCAN) >= 1, \
            f"{n} layers at period {self.attn_layer_period}, offset " \
            f"{self.attn_layer_offset}: the map needs a layer of each kind"
        assert self.num_experts == 1 and self.num_experts_per_tok == 1, \
            "a sparse feed-forward layer is not written for this family"
        assert self.hidden_act == "silu", self.hidden_act
        assert self.tie_word_embeddings, "an untied head is not written"
        assert self.mamba_conv_bias and not self.mamba_proj_bias, \
            "the scan's convolution has a bias, its in / x / out products none"
        assert self.sliding_window is None, \
            "a window on the attention layers is not written"
        assert self.hidden_size % self.num_attention_heads == 0
        assert self.num_attention_heads % self.num_key_value_heads == 0, \
            "query heads are a multiple of the key-value heads"
        assert self.mamba_d_conv >= 2, "a convolution of one tap has no tail"

    @property
    def head_dim(self) -> int:
        """ASSUMED: ``hidden_size / num_attention_heads`` (the catalog
        row's ``head_dim`` is null)."""
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_lanes(self) -> int:
        """A token's keys (or values), all key-value heads side by side."""
        return self.num_key_value_heads * self.head_dim

    # what ``models/scan_mixer.py`` and ``serving/programs.py:scan_mixer``
    # read of a config
    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def d_state(self) -> int:
        return self.mamba_d_state

    @property
    def d_conv(self) -> int:
        return self.mamba_d_conv

    @property
    def dt_rank(self) -> int:
        return self.mamba_dt_rank

    # ----------------------------------------------------- the layer map
    def kind_of(self, layer: int) -> str:
        """The stack a published layer's parameters live in."""
        return FULL if layer % self.attn_layer_period \
            == self.attn_layer_offset else SCAN

    def layers_of(self, kind: str) -> int:
        return sum(self.kind_of(l) == kind
                   for l in range(self.num_hidden_layers))

    def kinds(self) -> dict:
        """kind -> how many layers it stacks."""
        return {k: self.layers_of(k) for k in KINDS}

    def runs(self) -> list:
        """The published order as runs of consecutive layers of one kind
        (``serving/programs.py:walk_runs``): ``(kind, first index in the
        kind's stack, layers, first index in the kind's cache)`` — a scan
        layer's place in the states and tails and an attention layer's in
        the pool are its place in its stack."""
        out, at = [], {k: 0 for k in KINDS}
        for l in range(self.num_hidden_layers):
            kind = self.kind_of(l)
            if out and out[-1][0] == kind:
                out[-1][2] += 1
            else:
                out.append([kind, at[kind], 1, at[kind]])
            at[kind] += 1
        return [tuple(r) for r in out]


_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
           "float16": jnp.float16}


def config_from_dict(d: dict) -> SSMMQAConfig:
    """Build the config from a YAML ``Model:`` section (unknown keys, such
    as ``name``, are dropped). Every published key has to be stated: a
    recipe that omits one is refused by name."""
    missing = [k for k in PUBLISHED_KEYS if d.get(k) is None]
    if missing:
        raise ValueError(
            "a recipe of Model.module SSMMQAModule states every published "
            f"key; missing: {', '.join(missing)}")
    known = {f.name for f in dataclasses.fields(SSMMQAConfig)}
    kwargs = {k: v for k, v in d.items() if k in known and v is not None}
    for key in ("dtype", "param_dtype"):
        if isinstance(kwargs.get(key), str):
            kwargs[key] = _DTYPES[kwargs[key]]
    return SSMMQAConfig(**kwargs)
