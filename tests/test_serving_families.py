"""Every family behind ``serving/registry.py`` at once: the record a family
fills is complete, and kernel-or-gather is one decision with one warning.

Nothing is compiled: ``Family.programs`` allocates toy caches and wraps two
functions in ``jax.jit``; each family's own file runs them."""

from __future__ import annotations

import dataclasses
import logging
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import conv_moe_toy  # noqa: E402
import device_scope_programs as toys  # noqa: E402
import gdn_mla_toy  # noqa: E402
import samba_y_toy  # noqa: E402
import ssm_mqa_toy  # noqa: E402

from fleetx_tpu.observability.metrics import get_registry  # noqa: E402
from fleetx_tpu.serving import registry  # noqa: E402
from fleetx_tpu.serving.engine import ServingConfig  # noqa: E402
from fleetx_tpu.serving.programs import SamplingParams  # noqa: E402
from fleetx_tpu.utils.log import logger as program_logger  # noqa: E402


def _gpt(hidden: int) -> dict:
    return dict(vocab_size=97, hidden_size=hidden, num_layers=2,
                num_attention_heads=4, max_position_embeddings=64,
                use_flash_attention=False, dtype="float32",
                param_dtype="float32")


#: family -> (a ``Model:`` section the decode kernel refuses, one it admits,
#: the page size, words of the refusal)
GEOMETRIES = {
    "GPTFamily": (_gpt(16), _gpt(64), 4,
                  "head_dim 4 is not a multiple of 8"),
    "SWAMoEFamily": (toys._laguna_toy(),
                     dict(toys._laguna_toy(), head_dim=128), 8,
                     "full_dense layers: head_dim 16 is neither whole "
                     "128-lane tiles nor half of one"),
    "GDNMLAFamily": (gdn_mla_toy.model_section(),
                     gdn_mla_toy.model_section(kv_lora_rank=128), 8,
                     "value width 16 are not whole 128-lane tiles"),
    "ConvMoEFamily": (conv_moe_toy.model_section(hidden_size=256),
                      conv_moe_toy.model_section(), 8,
                      "head_dim 32 is neither whole 128-lane tiles nor "
                      "half of one"),
    # (a key-value PAIR of two 8-wide heads is what the kernel is asked)
    "SambaYFamily": (samba_y_toy.model_section(),
                     samba_y_toy.model_section(**samba_y_toy.KERNEL_WIDTHS),
                     8, "attention: head_dim 16 is neither whole 128-lane "
                     "tiles nor half of one"),
    # (4 query heads over ONE key-value head)
    "SSMMQAFamily": (ssm_mqa_toy.model_section(),
                     ssm_mqa_toy.model_section(**ssm_mqa_toy.KERNEL_WIDTHS),
                     8, "attention: head_dim 16 is neither whole 128-lane "
                     "tiles nor half of one"),
}
FAMILIES = pytest.mark.parametrize(
    "family", registry._FAMILIES, ids=lambda f: type(f).__name__)


def _build(family, model: dict, page: int, **serving):
    """``(config, ServingConfig, Programs, what the program's log said)``."""
    cfg = family.model_config(model, {})
    sc = ServingConfig(max_batch=3, page_size=page, num_pages=25,
                       max_seq_len=64, prefill_chunk=8, **serving)
    said = []
    handler = logging.Handler()
    handler.emit = lambda record: said.append(record.getMessage())
    program_logger.addHandler(handler)
    try:
        built = family.programs(cfg, sc, SamplingParams(), None, 64 // page)
    finally:
        program_logger.removeHandler(handler)
    return cfg, sc, built, said


@FAMILIES
def test_a_family_fills_the_whole_record(family):
    """``Programs`` has no optional field and the family leaves none empty;
    the two programs are jitted under the names the metrics find them by;
    every answer the engine asks of a family has the documented form."""
    refused, _, page, _ = GEOMETRIES[type(family).__name__]
    cfg, sc, built, _ = _build(family, refused, page)
    for field in dataclasses.fields(registry.Programs):
        assert field.default is dataclasses.MISSING and \
            field.default_factory is dataclasses.MISSING, field.name
    assert built.cache and all(isinstance(a, jax.Array) for a in built.cache)
    assert built.tokens.shape == (sc.max_batch,) and \
        built.tokens.dtype == np.int32
    assert sorted(built.fns) == ["decode", "prefill"]
    for name, fn in built.fns.items():
        assert fn.__name__ == name and hasattr(fn, "lower")
    assert type(family).programs is not registry.Family.programs
    said = family.describe(cfg, sc, built.cache)
    assert isinstance(said, str) and (
        not said or said.startswith(" (") and said.endswith(")"))
    sizes = family.cache_bytes(built.cache)
    assert sorted(sizes) == ["latent", "state"] and \
        all(isinstance(n, int) for n in sizes.values())
    assert sum(sizes.values()) <= sum(a.nbytes for a in built.cache)
    extra = family.prefill_extra(2)
    assert extra in ((), (np.int32(2),))
    lens = np.array([5, -1, 30], np.int32)
    full, window = family.kv_tokens(cfg, lens)
    assert full == 35 and 0 <= window <= full
    assert isinstance(family.stats_snapshot(get_registry()), dict)
    assert callable(family.stats_recorder(cfg))


@FAMILIES
def test_kernel_or_gather_is_decided_once_with_one_warning(family):
    """A geometry the family's predicate refuses: the gathered view, ONE
    warning that names the reason, no kernel walk. Switched off by the
    recipe: the gathered view in silence. An admitted geometry: the kernel,
    its walk shape and a fold for each cache it reads, in silence."""
    refused, admitted, page, reason = GEOMETRIES[type(family).__name__]
    _, _, built, said = _build(family, refused, page)
    fell = [s for s in said if "falls back to the gathered view" in s]
    assert len(fell) == 1 and reason in fell[0], said
    assert fell[0].startswith(family.decode_attention)
    assert not built.paged_kernel_active and built.kernel is None
    assert built.kv_folds == {}

    _, _, built, said = _build(family, refused, page, paged_kernel=False)
    assert not built.paged_kernel_active and not said

    _, sc, built, said = _build(family, admitted, page)
    assert not [s for s in said if "falls back" in s], said
    assert built.paged_kernel_active
    span, folds = built.kernel.walk_shape
    assert span % sc.page_size == 0 and span * folds >= 64
    assert built.kv_folds and set(built.kv_folds) <= {"full", "window",
                                                     "latent"}
    for pages, copies in built.kv_folds.values():
        assert pages >= 1 and copies in (1, pages)
