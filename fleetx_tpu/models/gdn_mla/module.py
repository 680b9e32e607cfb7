"""Task module of the hybrid family (gated-delta-rule layers beside latent
attention over sparse experts).

The family is SERVED (``tools/serve.py``, ``serving/registry.py``); nothing
trains it: the rule has no backward pass here (ROADMAP R5), and at 16 bytes
a parameter no cut inside the floors of a ``model_config`` change fits one
chip. The module exists so that the tools that walk the recipe zoo by
``Model.module`` (``tools/shardcheck.py``, the shard-rule lint) build its
parameter tree and audit it against the ``gdn_mla`` table of
``parallel/rules.py`` like every other family's.
"""

from __future__ import annotations

from typing import Any

import jax

from fleetx_tpu.core.module import LanguageModule
from fleetx_tpu.models.gdn_mla import model as model_lib
from fleetx_tpu.models.gdn_mla.config import config_from_dict

_SERVED_ONLY = "models/gdn_mla is served (tools/serve.py), not trained"


class GDNMLAModule(LanguageModule):
    """The family's parameter tree and shapes; no loss."""

    spec_family = "gdn_mla"

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
        """The paged pool of latents (there is no K and V: the one buffer
        holds both): what ``Serving.num_pages`` sizes and the ``serving_kv``
        rule places."""
        from fleetx_tpu.models.gdn_mla.config import LATENT
        from fleetx_tpu.ops.mla_paged_attention import lanes_of

        c = self.model_cfg
        return (max(c.layers_of(LATENT), 1), int(num_pages), int(page_size),
                lanes_of(c.latent_width))

    def training_loss(self, params, batch, rng, step):
        raise NotImplementedError(_SERVED_ONLY)

    def validation_loss(self, params, batch):
        raise NotImplementedError(_SERVED_ONLY)
