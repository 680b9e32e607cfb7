"""Serving family ``SSMMQAModule``: selective-scan layers whose step, ``B``
and ``C`` pass an RMS norm each, beside a few multi-query attention layers
(``fleetx_tpu/models/ssm_mqa``) behind ``ServingEngine``.

Found by the recipe's ``Model.module``. Both calls are the program's own
(``fleetx_tpu/serving/registry.py``, which ``tools/serve.py`` builds its
engine through as well): the template is the tree the programs take, each
leaf with the dtype it is served in — bfloat16 but every norm's weight (the
scan's inner three among them) and the scan's own vectors — so the seeded
weights are made in those and the engine casts no leaf.

One leaf is not served as the harness draws it (``WEIGHT_SCALE_LOG2``): the
weight of the RMS norm on the scan's ``C`` is held at a sixteenth.
``benchmarks/weights.py`` draws every norm's weight 1 + 0.1 N(0, 1); ``B``
and ``C`` are NORMED, so each of their 16 entries is ~1 whatever the matrix
in front of them is (scaling ``W_x``, the fifth family's cure, changes
nothing here), ``|B . C|`` is ~4, and a state fed ``Δ x B`` and read through
``C`` answers ~3 times its skip ``D x``: a third-order term of the layer's
input rules the residual stream, and through 26 such layers a rounding grows
until a bfloat16 program's choices are as far from the float32 reference's
as a random token's — ``correct`` could not tell float8 from sound (the CPU
emulation of PERF.md section 6, PR 51: bfloat16 5.3-5.6 against float8
6.3-7.0 as drawn). The state's answer is linear in ``C``: at a sixteenth it
stands at ~0.2 of the skip (the same product as ``B`` and ``C`` at a quarter
each, in one leaf), a rounding no longer grows (bfloat16 0.15-0.29, float8
2.8-3.7) and the state still rules the logits (its answer dropped, the
choices fall 5.1-5.9). A power of two is exact in float32, the dtype the
leaf is served in. Shapes, bytes and every product are what they were. The
reference scales its own copy by its own table; a test holds the two equal.
"""

from __future__ import annotations

#: path in the program's tree -> log2 of the factor on the harness's draw
WEIGHT_SCALE_LOG2 = {"scan/ssm/c_norm": -4}


def seeded(params):
    """The tree the harness made, each leaf of ``WEIGHT_SCALE_LOG2`` times
    its power of two (exact in every float dtype)."""
    import jax

    def scale(path, leaf):
        keys = [str(getattr(p, "key", getattr(p, "name", p))) for p in path]
        by = WEIGHT_SCALE_LOG2.get("/".join(k for k in keys if k != "value"))
        return leaf if by is None else leaf * leaf.dtype.type(2.0 ** by)

    return jax.tree_util.tree_map_with_path(scale, params)


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
        cfg, model_cfg, seeded(params), sampling=SamplingParams(do_sample=False),
        eos_token_id=eos_token_id, seed=seed)
