"""Serving family ``SambaYModule``: selective-scan layers alternating with
differential attention over a window, one full attention layer whose keys
and values every later attention layer reads, gated memory units
(``fleetx_tpu/models/samba_y``) behind ``ServingEngine``.

Found by the recipe's ``Model.module``. Both calls are the program's own
(``fleetx_tpu/serving/registry.py``, which ``tools/serve.py`` builds its
engine through as well): the template is the tree the programs take, each
leaf with the dtype it is served in — bfloat16 but the norms' weights and
biases, the scan's own vectors and the λ vectors — so the seeded weights are
made in those and the engine casts no leaf.

One leaf is not served as the harness draws it (``WEIGHT_SCALE_LOG2``): the
matrix that projects the scan's input onto its step, ``B`` and ``C`` is held
at an eighth. ``benchmarks/weights.py`` draws every matrix N(0, 0.02), which
at 5,120 channels puts ``B`` and ``C`` at ~1.7 each, and a state fed ``Δ x
B`` and read through ``C`` then answers ~10 times its skip ``D x``: a term of
fifth order in the layer's input rules the residual stream, and through nine
such layers a rounding grows until a bfloat16 program's choices are as far
from the float32 reference's as a random token's — ``correct`` could not tell
float8 from sound (PERF.md section 6, PR 48). At 0.02 / 8 a rounding no longer
grows through the scan layers and the state still rules the logits (Mamba-1's
own start for this matrix is uniform in ± 1 / sqrt(5,120), 0.0081, under steps
of 1e-3 .. 1e-1 where this draw's are 0.69: the state's drive is still ~20
times a released scan's); a power of two is exact in bfloat16, so the
program's leaf is still the reference's rounded once. The reference scales
its own copy by its own table; a test holds the two equal.
"""

from __future__ import annotations

#: path in the program's tree -> log2 of the factor on the harness's draw
WEIGHT_SCALE_LOG2 = {"scan/ssm/x": -3}


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
