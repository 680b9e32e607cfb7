"""Task module of the scan / multi-query family (selective-scan layers with
normed step, ``B`` and ``C`` beside multi-query attention).

The family is SERVED (``tools/serve.py``, ``serving/registry.py``); nothing
trains it: ``ops/selective_scan.py`` has no backward and one period of the
layer pattern with the vocabulary is 25.6 GB at 16 bytes a parameter
(``docs/ssm_mqa.md`` "The path"). The module exists so that the tools that
walk the recipe zoo by ``Model.module`` (``tools/shardcheck.py``, the
shard-rule lint) build its parameter tree and audit it against the
``ssm_mqa`` table of ``parallel/rules.py`` like every other family's.
"""

from __future__ import annotations

from typing import Any

import jax

from fleetx_tpu.core.module import LanguageModule
from fleetx_tpu.models.ssm_mqa import model as model_lib
from fleetx_tpu.models.ssm_mqa.config import FULL, config_from_dict

_SERVED_ONLY = "models/ssm_mqa is served (tools/serve.py), not trained"


class SSMMQAModule(LanguageModule):
    """The family's parameter tree and shapes; no loss."""

    spec_family = "ssm_mqa"

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
        rule places."""
        return (self.model_cfg.layers_of(FULL), int(num_pages),
                int(page_size), self.model_cfg.kv_lanes)

    def training_loss(self, params, batch, rng, step):
        raise NotImplementedError(_SERVED_ONLY)

    def validation_loss(self, params, batch):
        raise NotImplementedError(_SERVED_ONLY)
