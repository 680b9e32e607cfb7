"""A toy reference that asks for its weights as it goes (tests only; copied
into a rehearsal root's ``benchmarks/reference/``; it walks ``gpt_ref``'s
block). It defines ``logits_streamed``, so the check hands
it ``leaf`` and never the whole tree. Two layouts of the same numbers'
names: ``sizes["layout"] == "per_layer"`` names every layer's leaves apart
(``fc_w.0``, ``fc_w.1``: asked for as ``leaf(name)``); otherwise the
layers are stacked as in ``gpt_ref`` and asked for as ``leaf(name,
layer)``."""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from benchmarks.manifest import ROOT, load_module

_gpt = load_module(os.path.join(ROOT, "benchmarks/reference/gpt_ref.py"))
PRECISIONS = _gpt.PRECISIONS


def _per_layer(sizes: dict) -> bool:
    return sizes.get("layout") == "per_layer"


def weight_spec(sizes: dict) -> dict:
    spec = _gpt.weight_spec(sizes)
    if not _per_layer(sizes):
        return spec
    out = {k: v for k, v in spec.items() if k not in _gpt._PER_LAYER}
    for k in _gpt._PER_LAYER:
        shape, kind = spec[k]
        for l in range(shape[0]):
            out[f"{k}.{l}"] = (tuple(shape[1:]), kind)
    return out


@functools.lru_cache(maxsize=None)
def _block(eps: float, precision: str):
    return jax.jit(lambda x, lw: _gpt._block(x, lw, eps, precision))


def logits_streamed(leaf, sizes: dict, tokens, precision: str = "float32"):
    """``leaf(name)`` / ``leaf(name, layer)`` -> one float32 weight; one
    layer's weights are alive at a time."""
    eps = float(sizes.get("layer_norm_epsilon", 1e-5))
    get = (lambda k, l: leaf(f"{k}.{l}")) if _per_layer(sizes) else leaf
    block = _block(eps, precision)
    wte = leaf("wte")
    x = wte[tokens] + leaf("wpe")[jnp.arange(tokens.shape[1])]
    for l in range(int(sizes["num_layers"])):
        x = block(x, {k: get(k, l) for k in _gpt._PER_LAYER})
    x = _gpt._layer_norm(x, leaf("lnf_g"), leaf("lnf_b"), eps)
    return _gpt._product("bsh,vh->bsv", x, wte, precision)
