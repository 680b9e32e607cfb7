"""Serving family ``GPTModule``: the GPT block behind ``ServingEngine``.

A family file is found by the recipe's ``Model.module`` and is the one
place in the harness that names a model class. It makes the calls
``tools/serve.py:_build_engine`` makes, split in two so that the harness
can put its seeded weights between them. Once the program has a function
pair of its own for this (PERF.md section 7), a family file calls that.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def served_template(cfg):
    """Recipe config -> ``(model config, abstract parameter tree)``: each
    leaf with the shape AND the dtype the engine holds it in, as the
    program's own ``serving_params`` leaves an initialised tree (the
    layer norms in ``param_dtype``, all else in ``Model.dtype``). Nothing
    is initialised: the tree is an ``eval_shape``."""
    from flax.core import meta
    from fleetx_tpu.models.gpt.model import GPTForPretraining, config_from_dict
    from fleetx_tpu.serving.decode import serving_params

    model_cfg = config_from_dict(dict(cfg.get("Model") or {}))
    model = GPTForPretraining(model_cfg)

    def served():
        params = model.init({"params": jax.random.PRNGKey(0)},
                            jnp.zeros((1, 8), jnp.int32), None,
                            deterministic=True)["params"]
        return serving_params(meta.unbox(params), model_cfg)

    return model_cfg, jax.eval_shape(served)


def serving_engine(cfg, model_cfg, params, eos_token_id: int, seed: int):
    """Recipe config + a parameter tree like the template -> a greedy
    ``ServingEngine`` on one chip."""
    from fleetx_tpu.serving.decode import SamplingParams
    from fleetx_tpu.serving.engine import ServingConfig, ServingEngine

    return ServingEngine(
        model_cfg, params,
        ServingConfig.from_dict(dict(cfg.get("Serving") or {})),
        SamplingParams(do_sample=False), eos_token_id=eos_token_id,
        mesh=None, seed=seed)
