"""Task module of the decoder-hybrid-decoder family (selective-scan layers,
differential attention, one shared key-value layer, gated memory units).

The family is SERVED (``tools/serve.py``, ``serving/registry.py``); nothing
trains it: ``ops/selective_scan.py`` has no backward and the smallest cut
that keeps every kind of layer is 14 GB at 16 bytes a parameter
(``docs/samba_y.md`` "The path"). The module exists so that the tools that
walk the recipe zoo by ``Model.module`` (``tools/shardcheck.py``, the
shard-rule lint) build its parameter tree and audit it against the
``samba_y`` table of ``parallel/rules.py`` like every other family's.
"""

from __future__ import annotations

from typing import Any

import jax

from fleetx_tpu.core.module import LanguageModule
from fleetx_tpu.models.samba_y import model as model_lib
from fleetx_tpu.models.samba_y.config import config_from_dict

_SERVED_ONLY = "models/samba_y is served (tools/serve.py), not trained"


class SambaYModule(LanguageModule):
    """The family's parameter tree and shapes; no loss."""

    spec_family = "samba_y"

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
        """The paged pool of the ONE layer that keeps every token (K; V
        has the same shape): what ``Serving.num_pages`` sizes and the
        ``serving_kv`` rule places."""
        return (1, int(num_pages), int(page_size), self.model_cfg.kv_lanes)

    def training_loss(self, params, batch, rng, step):
        raise NotImplementedError(_SERVED_ONLY)

    def validation_loss(self, params, batch):
        raise NotImplementedError(_SERVED_ONLY)
