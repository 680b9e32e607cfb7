"""Task module of the short-convolution family (gated convolution layers
beside grouped-query attention over sparse experts).

The family is SERVED (``tools/serve.py``, ``serving/registry.py``); nothing
trains it: at 16 bytes a parameter one chip holds an eighth of an expert
layer, and what is left of a step is plain matrix products
(``docs/conv_moe.md`` "The path"). The module exists so that the tools that
walk the recipe zoo by ``Model.module`` (``tools/shardcheck.py``, the
shard-rule lint) build its parameter tree and audit it against the
``conv_moe`` table of ``parallel/rules.py`` like every other family's.
"""

from __future__ import annotations

from typing import Any

import jax

from fleetx_tpu.core.module import LanguageModule
from fleetx_tpu.models.conv_moe import model as model_lib
from fleetx_tpu.models.conv_moe.config import FULL, config_from_dict

_SERVED_ONLY = "models/conv_moe is served (tools/serve.py), not trained"


class ConvMoEModule(LanguageModule):
    """The family's parameter tree and shapes; no loss."""

    spec_family = "conv_moe"

    def __init__(self, cfg: Any):
        self.model_cfg = config_from_dict(dict(cfg.get("Model", cfg)))
        self.tokens_per_sample = 1
        super().__init__(cfg)

    def get_model(self):
        return model_lib

    def flops_per_token(self):
        return None

    def init_variables(self, rng: jax.Array, batch: dict) -> Any:
        del batch
        return model_lib.init_params(self.model_cfg, rng)

    def kv_pool_shape(self, num_pages: int, page_size: int) -> tuple:
        """The paged pool of the attention layers (K; V has the same
        shape): what ``Serving.num_pages`` sizes and the ``serving_kv``
        rule places. The convolution layers keep nothing there."""
        c = self.model_cfg
        return (max(c.layers_of(FULL), 1), int(num_pages), int(page_size),
                c.num_key_value_heads * c.head_dim)

    def training_loss(self, params, batch, rng, step):
        raise NotImplementedError(_SERVED_ONLY)

    def validation_loss(self, params, batch):
        raise NotImplementedError(_SERVED_ONLY)
