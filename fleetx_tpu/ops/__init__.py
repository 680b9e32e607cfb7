"""Pallas TPU kernels and the two facts every one of them shares.

- ``interpret()``: the kernels compile through Mosaic on a TPU and run in
  Pallas interpret mode on the CPU backend (how tier-1 pins their numerics
  without hardware). ONE helper decides, so ``tests/test_tpu_lowering.py``
  can force it off and cross-lower every program for the TPU from the CPU
  host.
- ``local_shape()``: the installed JAX lowers a Mosaic call under a
  multi-device mesh only inside a ``shard_map`` that is manual over EVERY
  mesh axis (``jax/_src/tpu_custom_call.py``), so each kernel's mesh wrapper
  names how its operands are laid out over all five axes and its
  ``*_supported`` predicate judges the PER-DEVICE shape this returns.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
from jax.sharding import NamedSharding


def interpret() -> bool:
    """True when Pallas kernels run interpreted: the CPU backend, which a
    process only gets by asking for it (``utils/check.py`` refuses a TPU
    config on any other platform unless ``JAX_PLATFORMS=cpu`` is set)."""
    return jax.default_backend() == "cpu"


def local_shape(shape: tuple, spec: Any, mesh: Any) -> Optional[tuple]:
    """Per-device shape of an array laid out by ``spec`` over ``mesh``, or
    None when some dim does not divide its mesh axes evenly (``shard_map``
    would refuse it)."""
    try:
        return NamedSharding(mesh, spec).shard_shape(tuple(shape))
    except ValueError:
        return None
