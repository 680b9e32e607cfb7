"""Task module of the windowed-attention sparse-expert family.

The family is SERVED (``tools/serve.py``, ``serving/registry.py``); nothing
trains it: at 16 bytes a parameter no cut of the published model inside the
floors of a ``model_config`` change fits one chip. The module exists so that
the tools that walk the recipe zoo by ``Model.module`` (``tools/shardcheck.py``,
the shard-rule lint) build its parameter tree and audit it against the
``swa_moe`` table of ``parallel/rules.py`` like every other family's.
"""

from __future__ import annotations

from typing import Any

import jax

from fleetx_tpu.core.module import LanguageModule
from fleetx_tpu.models.swa_moe import model as model_lib
from fleetx_tpu.models.swa_moe.config import config_from_dict

_SERVED_ONLY = "models/swa_moe is served (tools/serve.py), not trained"


class SWAMoEModule(LanguageModule):
    """The family's parameter tree and shapes; no loss."""

    spec_family = "swa_moe"

    def __init__(self, cfg: Any):
        self.model_cfg = config_from_dict(dict(cfg.get("Model", cfg)))
        self.tokens_per_sample = int(self.model_cfg.sliding_window)
        super().__init__(cfg)

    def get_model(self):
        return model_lib

    def flops_per_token(self):
        return None

    def init_variables(self, rng: jax.Array, batch: dict) -> Any:
        del batch
        return model_lib.init_params(self.model_cfg, rng)

    def kv_pool_shape(self, num_pages: int, page_size: int) -> tuple:
        """The paged pool of the layers that keep every token (K or V):
        what ``Serving.num_pages`` sizes and the ``serving_kv`` rule
        places."""
        c = self.model_cfg
        return (c.layers_of("full"), int(num_pages), int(page_size),
                c.num_key_value_heads * c.head_dim)

    def training_loss(self, params, batch, rng, step):
        raise NotImplementedError(_SERVED_ONLY)

    def validation_loss(self, params, batch):
        raise NotImplementedError(_SERVED_ONLY)
