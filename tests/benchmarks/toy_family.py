"""A toy second serving family for the rehearsal (tests only; copied into a
rehearsal root as ``benchmarks/families/ToyLayersModule.py``): the GPT
block behind another ``Model.module`` name and another parameter tree —
one sub-tree a layer, ``{"embed", "block_0", ..., "final_norm"}``, where
the program's own tree stacks the layers. A later PR's family file has
this form: two functions, found by the recipe's ``Model.module``."""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from benchmarks.manifest import ROOT, load_module

_gpt = load_module(os.path.join(ROOT, "benchmarks/families/GPTModule.py"))


def served_template(cfg):
    model_cfg, tree = _gpt.served_template(cfg)
    gpt = tree["gpt"]
    out = {"embed": gpt["embeddings"], "final_norm": gpt["ln_f"]}
    for l in range(model_cfg.num_layers):
        out[f"block_{l}"] = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
            gpt["layers"])
    return model_cfg, out


def serving_engine(cfg, model_cfg, params, eos_token_id: int, seed: int):
    blocks = [params[f"block_{l}"] for l in range(model_cfg.num_layers)]
    gpt = {"embeddings": params["embed"], "ln_f": params["final_norm"],
           "layers": jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)}
    return _gpt.serving_engine(cfg, model_cfg, {"gpt": gpt}, eos_token_id,
                               seed)
