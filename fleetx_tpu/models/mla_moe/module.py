"""Task module of the latent-attention sparse-expert family.

``MLAMoEModule`` is to ``models/mla_moe`` what ``core/module.py:GPTModule``
is to ``models/gpt``: it builds the configuration from the recipe's
``Model:`` section, initialises the parameter tree and hands the engine
pure loss functions. The tree is a plain dict; its sharding comes from
the ``mla_moe`` table of ``parallel/rules.py``.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from fleetx_tpu.core.module import LanguageModule
from fleetx_tpu.models.mla_moe import model as model_lib
from fleetx_tpu.models.mla_moe.config import config_from_dict
from fleetx_tpu.observability.metrics import get_registry
from fleetx_tpu.utils.log import logger

#: step metrics kept as histograms of the process's ``MetricsRegistry``
#: (``record_step_metrics``): name -> what it counts
STEP_COUNTERS = {
    "moe_load_max_over_mean": "rows of the fullest held expert over the "
                              "mean of the held experts, worst layer",
    "moe_held_share": "share of the step's token-expert pairs that land "
                      "on held experts, mean over layers",
    "moe_bias_abs_max": "largest |selection bias| of any expert layer",
    "loss_mtp": "the multi-token-prediction module's loss",
}


def train_flops_per_token(cfg, seq: int) -> float:
    """Operations the forward and backward passes require per trained
    token for the share held here: causal attention (half the products),
    the held experts at their expected load, no recomputation."""
    h, heads = cfg.hidden_size, cfg.num_attention_heads
    attn = (h * cfg.q_lora_rank + cfg.q_lora_rank * heads * cfg.qk_head_dim
            + h * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
            + cfg.kv_lora_rank * heads * (cfg.qk_nope_head_dim
                                          + cfg.v_head_dim)
            + heads * cfg.v_head_dim * h
            + heads * (seq / 2) * (cfg.qk_head_dim + cfg.v_head_dim))
    expert = 3 * h * cfg.moe_intermediate_size
    held_per_token = cfg.num_experts_per_tok * cfg.experts_held \
        / cfg.n_routed_experts
    moe = (h * cfg.n_routed_experts + cfg.n_shared_experts * expert
           + held_per_token * expert)
    head = cfg.vocab_size * h
    macs = (cfg.first_k_dense_replace * (attn + 3 * h * cfg.intermediate_size)
            + cfg.num_expert_layers * (attn + moe) + head
            + cfg.num_nextn_predict_layers * (2 * h * h + attn + moe + head))
    return 3 * 2 * macs


class MLAMoEModule(LanguageModule):
    """Pretraining task for the latent-attention sparse-expert decoder."""

    spec_family = "mla_moe"

    def __init__(self, cfg: Any):
        model_cfg = dict(cfg.get("Model", cfg))
        self.model_cfg = config_from_dict(model_cfg)
        glb = dict(cfg.get("Global") or {}) if "Model" in cfg else {}
        self.tokens_per_sample = int(
            glb.get("max_seq_len") or self.model_cfg.max_position_embeddings)
        super().__init__(cfg)
        c = self.model_cfg
        logger.info(
            "latent-attention expert model: %d dense + %d expert layers "
            "(+%d prediction), hidden=%d heads=%d, experts held %d..%d of "
            "%d, %d a token, vocab=%d",
            c.first_k_dense_replace, c.num_expert_layers,
            c.num_nextn_predict_layers, c.hidden_size, c.num_attention_heads,
            c.first_expert_held, c.first_expert_held + c.experts_held - 1,
            c.n_routed_experts, c.num_experts_per_tok, c.vocab_size)

    def get_model(self):
        return model_lib

    def flops_per_token(self) -> float:
        return train_flops_per_token(self.model_cfg, self.tokens_per_sample)

    def init_variables(self, rng: jax.Array, batch: dict) -> Any:
        del batch
        return model_lib.init_params(self.model_cfg, rng)

    def training_loss(self, params, batch, rng, step):
        del rng, step       # no dropout in this family
        return model_lib.training_loss(params, self.model_cfg, batch)

    def validation_loss(self, params, batch):
        loss, metrics = model_lib.training_loss(params, self.model_cfg, batch)
        return metrics["loss_main"], {"loss": metrics["loss_main"]}

    def predict_step(self, params, batch):
        return model_lib.logits(params, self.model_cfg, batch["tokens"],
                                batch.get("position_ids"))

    def record_step_metrics(self, host_metrics: dict) -> None:
        """The step's expert-load counters and second loss, already on the
        host with the rest of the step's metrics, into the registry."""
        registry = get_registry()
        for name in STEP_COUNTERS:
            if name in host_metrics:
                registry.histogram(name).record(float(host_metrics[name]))

    def input_spec(self):
        s = self.tokens_per_sample
        return {"tokens": jax.ShapeDtypeStruct((1, s), jnp.int32),
                "position_ids": jax.ShapeDtypeStruct((1, s), jnp.int32)}
