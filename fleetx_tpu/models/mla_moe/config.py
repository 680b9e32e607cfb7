"""Configuration of the latent-attention sparse-expert decoder family.

The keys are those of the published ``config.json`` of the DeepSeek-V3
line of models (``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_head_dim``,
``first_k_dense_replace``, ``n_routed_experts`` ...), so a recipe reads
like the model card. Three keys describe what the published file cannot:
the chip's share of the expert layer (``experts_held`` and
``first_expert_held``: the router still scores all ``n_routed_experts``)
and the weight of the multi-token-prediction loss.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp


@dataclasses.dataclass(unsafe_hash=True)
class MLAMoEConfig:
    """Architecture and execution settings (YAML ``Model:`` section)."""

    vocab_size: int = 129280
    hidden_size: int = 2048
    num_hidden_layers: int = 40          # dense + expert layers
    first_k_dense_replace: int = 1       # leading layers with a dense MLP
    intermediate_size: int = 7168        # width of the dense MLP
    num_attention_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32000000.0
    rms_norm_eps: float = 1e-6
    n_routed_experts: int = 256          # the router's width
    experts_held: int | None = None      # None: all of them
    first_expert_held: int = 0
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    moe_intermediate_size: int = 768
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    num_nextn_predict_layers: int = 1
    mtp_loss_weight: float = 0.3
    max_position_embeddings: int = 131072
    use_flash_attention: bool = True
    # rows of the sorted (token, expert) pairs multiplied per pass of the
    # grouped expert products, and rows per tile (one expert a tile); the
    # defaults of these three are the recipe's, the CPU tests shrink them
    moe_chunk_rows: int = 16384
    moe_tile_rows: int = 256
    # tokens per block of the output head's loss (the [rows, vocab]
    # float32 logits exist for one block at a time)
    loss_chunk_rows: int = 2048
    grad_accum_dtype: Any = jnp.float32
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = self.n_routed_experts
        assert 0 <= self.first_expert_held and \
            self.first_expert_held + self.experts_held \
            <= self.n_routed_experts, "the held experts lie past the router"
        assert self.first_k_dense_replace <= self.num_hidden_layers
        assert self.moe_chunk_rows % self.moe_tile_rows == 0
        assert self.num_nextn_predict_layers in (0, 1), \
            "one prediction module at most"

    @property
    def num_expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
           "float16": jnp.float16}


def config_from_dict(d: dict) -> MLAMoEConfig:
    """Build the config from a YAML ``Model:`` section (unknown keys, such
    as ``module`` and ``name``, are the registry's)."""
    known = {f.name for f in dataclasses.fields(MLAMoEConfig)}
    kwargs = {k: v for k, v in d.items() if k in known and v is not None}
    for key in ("dtype", "param_dtype", "grad_accum_dtype"):
        if isinstance(kwargs.get(key), str):
            kwargs[key] = _DTYPES[kwargs[key]]
    return MLAMoEConfig(**kwargs)
