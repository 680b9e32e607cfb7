"""Serving family ``ConvMoEModule``: gated short-convolution layers beside
grouped-query attention over sparse experts held whole
(``fleetx_tpu/models/conv_moe``) behind ``ServingEngine``.

Found by the recipe's ``Model.module``. Both calls are the program's own
(``fleetx_tpu/serving/registry.py``, which ``tools/serve.py`` builds its
engine through as well): the template is the tree the programs take, each
leaf with the dtype it is served in — bfloat16 but the norms' weights and
the routers with their selection biases — so the seeded weights are made
in those and the engine casts no leaf.
"""

from __future__ import annotations


def served_template(cfg):
    """Recipe config -> ``(model config, abstract parameter tree)``."""
    from fleetx_tpu.serving import registry

    return registry.served_template(cfg)


def serving_engine(cfg, model_cfg, params, eos_token_id: int, seed: int):
    """Recipe config + a parameter tree like the template -> a greedy
    ``ServingEngine`` on one chip."""
    from fleetx_tpu.serving import registry
    from fleetx_tpu.serving.decode import SamplingParams

    return registry.build_engine(
        cfg, model_cfg, params, sampling=SamplingParams(do_sample=False),
        eos_token_id=eos_token_id, seed=seed)
