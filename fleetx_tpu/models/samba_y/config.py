"""Configuration of the decoder-hybrid-decoder family ("SambaY",
arXiv:2507.06607): selective-scan (Mamba-1) layers alternating with
differential attention (arXiv:2410.05258) over a short window, then ONE full
attention layer whose keys and values are the model's only key-value cache,
then gated memory units and cross-attention layers that read what the lower
half left behind.

The keys are those of the published ``config.json`` of
Phi-4-mini-flash-reasoning (``model_type: phi4flash``; ``docs/samba_y.md``).
A recipe states EVERY published key (``PUBLISHED_KEYS``): the dataclass's
defaults are for toy tests, and ``config_from_dict`` refuses a recipe that
omits one by name. What the published keys do not give — the scan's four
sizes and its biases — is ASSUMED (``ASSUMED_KEYS``: Mamba-1's defaults; a
recipe may state them, and the shipped one does).

The layer map is a function of ``num_hidden_layers`` (``N``) and
``mb_per_layer`` (2) alone, published numbering ``l``:

- ``l`` even, ``l ≤ N/2``: selective scan (``scan``); layer ``N/2`` also
  hands its scan output ``m`` on;
- ``l`` odd, ``l < N/2``: differential attention over the last
  ``sliding_window`` tokens (``window``);
- ``l = N/2 + 1``: differential attention over every earlier token
  (``full``); its K and V are the one paged cache;
- ``l`` even, ``l > N/2 + 1``: gated memory unit over ``m`` (``gmu``);
- ``l`` odd, ``l > N/2 + 1``: differential cross attention, its own queries
  against layer ``N/2 + 1``'s K and V (``cross``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax.numpy as jnp

SCAN, WINDOW, FULL, GMU, CROSS = "scan", "window", "full", "gmu", "cross"
KINDS = (SCAN, WINDOW, FULL, GMU, CROSS)

#: every key of the published config.json that describes the model (what
#: ``config_from_dict`` insists on)
PUBLISHED_KEYS = (
    "vocab_size", "max_position_embeddings", "hidden_size",
    "intermediate_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "sliding_window", "mb_per_layer",
    "layer_norm_eps", "hidden_act", "tie_word_embeddings", "mlp_bias",
    "lm_head_bias")
#: not in the published config: Mamba-1's defaults (docs/samba_y.md
#: "Assumed")
ASSUMED_KEYS = ("d_state", "d_conv", "expand", "dt_rank")


@dataclasses.dataclass(eq=False)
class SambaYConfig:
    """Architecture and execution settings (YAML ``Model:`` section)."""

    module: str = "SambaYModule"
    vocab_size: int = 200064
    max_position_embeddings: int = 262144
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    hidden_act: str = "silu"
    tie_word_embeddings: bool = True
    mlp_bias: bool = False
    lm_head_bias: bool = False
    # ASSUMED (Mamba-1's defaults): state a channel, taps, inner / hidden,
    # and the rank of the step's projection (0: ceil(hidden / 16))
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        n = self.num_hidden_layers
        assert self.mb_per_layer == 2, \
            "mb_per_layer: the layer map is written for a scan layer every " \
            "second layer"
        assert n % 4 == 0 and n >= 8, \
            f"{n} layers: the map needs N/2 even (the last scan layer) and " \
            f"a layer of every kind"
        assert self.hidden_act == "silu", self.hidden_act
        assert self.tie_word_embeddings and not self.mlp_bias \
            and not self.lm_head_bias, \
            "an untied head or a biased MLP / head is not written"
        assert self.hidden_size % self.num_attention_heads == 0
        # differential attention: query heads in pairs, key heads in pairs,
        # two query pairs to a key-value pair
        assert self.num_attention_heads % 2 == 0 \
            and self.num_key_value_heads % 2 == 0
        assert self.num_attention_heads % self.num_key_value_heads == 0
        assert self.d_conv >= 2, "a convolution of one tap has no tail"
        if not self.dt_rank:
            self.dt_rank = math.ceil(self.hidden_size / 16)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    @property
    def kv_lanes(self) -> int:
        """A token's keys (or values), all heads side by side."""
        return self.num_key_value_heads * self.head_dim

    # ----------------------------------------------------- the layer map
    @property
    def half(self) -> int:
        return self.num_hidden_layers // 2

    def kind_of(self, layer: int) -> str:
        """The stack a published layer's parameters live in."""
        half = self.half
        if layer % self.mb_per_layer == 0:
            return SCAN if layer <= half else GMU
        if layer < half:
            return WINDOW
        return FULL if layer == half + 1 else CROSS

    def layers_of(self, kind: str) -> int:
        return sum(self.kind_of(l) == kind
                   for l in range(self.num_hidden_layers))

    def kinds(self) -> dict:
        """kind -> how many layers it stacks."""
        return {k: self.layers_of(k) for k in KINDS}

    def published_index(self, kind: str, at: int) -> int:
        """The published index of layer ``at`` of ``kind``'s stack (what
        ``λ_init`` is a function of)."""
        return [l for l in range(self.num_hidden_layers)
                if self.kind_of(l) == kind][at]

    def lambda_init(self, kind: str) -> tuple:
        """``λ_init(l) = 0.8 − 0.6 exp(−0.3 l)`` for each layer of an
        attention ``kind``'s stack, ``l`` the published index."""
        return tuple(0.8 - 0.6 * math.exp(-0.3 * self.published_index(kind, i))
                     for i in range(self.layers_of(kind)))


_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
           "float16": jnp.float16}


def config_from_dict(d: dict) -> SambaYConfig:
    """Build the config from a YAML ``Model:`` section (unknown keys, such
    as ``name``, are dropped). Every published key has to be stated: a
    recipe that omits one is refused by name."""
    missing = [k for k in PUBLISHED_KEYS if d.get(k) is None]
    if missing:
        raise ValueError(
            "a recipe of Model.module SambaYModule states every published "
            f"key; missing: {', '.join(missing)}")
    known = {f.name for f in dataclasses.fields(SambaYConfig)}
    kwargs = {k: v for k, v in d.items() if k in known and v is not None}
    for key in ("dtype", "param_dtype"):
        if isinstance(kwargs.get(key), str):
            kwargs[key] = _DTYPES[kwargs[key]]
    return SambaYConfig(**kwargs)
