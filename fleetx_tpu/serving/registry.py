"""The model families ``ServingEngine`` can serve, keyed on the recipe's
``Model.module`` (as ``models/__init__.py:build_module`` is for training).

A family is what differs between two served models and nothing else: how
the recipe's ``Model:`` section becomes a model config, the parameter tree
(seeded, and as the programs hold it: shapes AND dtypes), the cache
buffers, and the two jitted programs. The engine class, scheduler,
admission, preemption, timelines and metrics are one
(``serving/engine.py``); the engine asks its family for ``Programs`` once,
when it is built.

Who calls what: ``tools/serve.py:_build_engine`` and every
``benchmarks/families/<Model.module>.py`` written since call
``model_config`` / ``served_template`` / ``init_params`` / ``build_engine``
here; ``ServingEngine(model_cfg, …)`` built directly (tests, the first
family file) finds its family by the ``module`` its config carries.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

__all__ = ["Family", "Programs", "families", "family", "family_of",
           "model_config", "served_template", "init_params", "build_engine"]


@dataclasses.dataclass
class Programs:
    """What one engine holds of its family."""

    cache: list                     # device buffers, donated every call
    fns: dict                       # {"prefill", "decode"}: jitted
    # [max_batch] int32 on the device: what the first ``decode`` call takes
    # as the last tokens; every later one takes the call before's output
    tokens: object
    paged_kernel_active: bool
    # (tokens one fold of the decode kernel covers, folds of a table row)
    walk_shape: Optional[tuple] = None
    # cache kind ("full", "window", "latent") -> (pages one fold of the
    # decode kernel takes, copies a cache buffer that fetch them); a kind
    # the engine does not have, or every kind on the gathered view, is absent
    kv_folds: dict = dataclasses.field(default_factory=dict)
    # bytes of the caches that are not lists of keys and values: "latent"
    # (a paged pool of latents) and "state" (constant-size state a slot: a
    # recurrent state, a convolution's tail); absent in a family without
    # them
    cache_bytes: dict = dataclasses.field(default_factory=dict)
    # slot -> further arguments of ``prefill`` after the rng and the draw
    prefill_extra: Callable = lambda slot: ()
    # (metrics registry, what ``decode`` returned after its logits)
    record_stats: Optional[Callable] = None
    # host lengths [slots] -> (tokens held in full layers' pages, tokens
    # held in window layers' rings), a layer each
    kv_tokens: Callable = lambda lens: (int(lens[lens >= 0].sum()), 0)
    describe: str = ""


class Family:
    """One served model family; subclasses fill the functions."""

    modules: tuple = ()             # the ``Model.module`` names it serves

    def model_config(self, model: dict, quantization: dict):
        """The recipe's ``Model:`` (and ``Quantization:``) -> config."""
        raise NotImplementedError

    def init_params(self, model_cfg, seed: int):
        """Seeded parameters, in the model's ``param_dtype``."""
        raise NotImplementedError

    def serving_params(self, params, model_cfg):
        """The tree the programs take (cast once; ``decode.py``)."""
        raise NotImplementedError

    def served_template(self, model_cfg):
        """That tree as ``ShapeDtypeStruct`` leaves, nothing initialised."""
        import jax

        return jax.eval_shape(lambda: self.serving_params(
            self.init_params(model_cfg, 0), model_cfg))

    def programs(self, model_cfg, serving, sampling, mesh,
                 pages_per_req: int) -> Programs:
        """The cache buffers and the two jitted programs of one engine."""
        raise NotImplementedError


class GPTFamily(Family):
    """The GPT block (``models/gpt``, ``serving/decode.py``)."""

    modules = ("GPTModule", "GPTGenerationModule", "GPTEvalModule",
               "LoRAGPTModule")

    def model_config(self, model: dict, quantization: dict):
        """See ``Family.model_config``."""
        from fleetx_tpu.models.gpt.model import config_from_dict

        model = dict(model)
        if quantization.get("weight_bits"):
            model["qat_bits"] = int(quantization["weight_bits"])
        if quantization.get("activation_bits"):
            model["qat_act_bits"] = int(quantization["activation_bits"])
        return config_from_dict(model)

    def init_params(self, model_cfg, seed: int):
        """See ``Family.init_params``."""
        import jax
        import jax.numpy as jnp
        from flax.core import meta

        from fleetx_tpu.models.gpt.model import GPTForPretraining

        return meta.unbox(GPTForPretraining(model_cfg).init(
            {"params": jax.random.PRNGKey(seed)},
            jnp.zeros((1, 8), jnp.int32), None,
            deterministic=True)["params"])

    def serving_params(self, params, model_cfg):
        """See ``Family.serving_params``."""
        from fleetx_tpu.serving.decode import serving_params

        return serving_params(params, model_cfg)

    def programs(self, model_cfg, serving, sampling, mesh,
                 pages_per_req: int) -> Programs:
        """See ``Family.programs``."""
        import jax

        import jax.numpy as jnp

        from fleetx_tpu.ops import paged_attention as PA
        from fleetx_tpu.serving.decode import (make_step_fns,
                                               paged_kernel_enabled,
                                               token_sharding)
        from fleetx_tpu.serving.paged_cache import init_pool, pool_shardings

        sc = serving
        pool_k, pool_v = init_pool(model_cfg, sc.num_pages, sc.page_size)
        tokens = jnp.zeros((sc.max_batch,), jnp.int32)
        sharding = None
        if mesh is not None:
            sharding = pool_shardings(mesh)
            pool_k = jax.device_put(pool_k, sharding)
            pool_v = jax.device_put(pool_v, sharding)
            tokens = jax.device_put(tokens, token_sharding(mesh))
        # kernel-vs-gather is decided HERE, once: the support predicates
        # are static functions of the config/pool/mesh, so the decode
        # program compiles exactly one attention path and the jit cache
        # stays pinned at one entry (test_serving pins this)
        active = bool(sc.paged_kernel) and paged_kernel_enabled(
            model_cfg, page_size=sc.page_size, num_pages=sc.num_pages,
            pages_per_req=pages_per_req, pool_sharding=sharding)
        fns = make_step_fns(
            model_cfg, max_batch=sc.max_batch, pages_per_req=pages_per_req,
            prefill_chunk=sc.prefill_chunk, sampling=sampling,
            quantize=bool(sc.quantize_decode), pool_sharding=sharding,
            paged_kernel=active)
        walk, folds = None, {}
        if active:
            geometry = dict(
                num_heads=model_cfg.num_attention_heads // (
                    mesh.shape["tensor"] if mesh is not None else 1),
                head_dim=model_cfg.head_dim, page_size=sc.page_size,
                pages_per_req=pages_per_req, dtype=model_cfg.dtype)
            walk = PA.page_walk_shape(**geometry)
            folds = {"full": PA.fold_shape(**geometry)}
        return Programs(cache=[pool_k, pool_v], fns=fns, tokens=tokens,
                        paged_kernel_active=active, walk_shape=walk,
                        kv_folds=folds)


def _expert_counters(cfg) -> Callable:
    """``Programs.record_stats`` of a family with sparse experts (``cfg``:
    its model config, whose ``kinds()`` name the stacks with experts
    ``*moe``): what its decode program returns after its logits -> the
    ``serving_moe_*`` metrics."""
    expert_layers = sum(n for kind, n in cfg.kinds().items()
                        if kind.endswith("moe"))
    per_tok = cfg.num_experts_per_tok

    def record(metrics, stats):
        if not expert_layers:
            return
        metrics.histogram("serving_moe_experts_hit").record(
            float(stats["hit"]) / expert_layers)
        metrics.counter("serving_moe_pairs_held_total").inc(
            int(stats["pairs_held"]))
        metrics.counter("serving_moe_pairs_total").inc(
            int(stats["rows"]) * per_tok * expert_layers)
        metrics.histogram("serving_moe_load_max_over_mean").record(
            float(stats["load_max_over_mean"]))
        metrics.counter("serving_moe_passes_total").inc(
            int(stats["passes"]))

    return record


class SWAMoEFamily(Family):
    """Windowed and full grouped-query attention over sparse experts, all
    of them held or a share (``models/swa_moe``, ``serving/swa_moe.py``;
    ``docs/swa_moe.md`` has the family's members)."""

    modules = ("SWAMoEModule",)

    def model_config(self, model: dict, quantization: dict):
        """See ``Family.model_config``."""
        from fleetx_tpu.models.swa_moe.config import config_from_dict

        assert not quantization.get("weight_bits") and \
            not quantization.get("activation_bits"), \
            "quantized decode is not written for this family"
        return config_from_dict(dict(model))

    def init_params(self, model_cfg, seed: int):
        """See ``Family.init_params``."""
        import jax

        from fleetx_tpu.models.swa_moe.model import init_params

        # made as it is served: the recipe's tree is 6.4 GB in bfloat16
        # and would be 12.8 GB in float32 beside its cast
        return jax.jit(lambda key: init_params(model_cfg, key, served=True))(
            jax.random.PRNGKey(seed))

    def serving_params(self, params, model_cfg):
        """See ``Family.serving_params``."""
        from fleetx_tpu.serving.swa_moe import serving_params

        return serving_params(params, model_cfg)

    def served_template(self, model_cfg):
        """See ``Family.served_template``."""
        from fleetx_tpu.models.swa_moe.model import served_template

        return served_template(model_cfg)

    def programs(self, model_cfg, serving, sampling, mesh,
                 pages_per_req: int) -> Programs:
        """See ``Family.programs``."""
        import jax.numpy as jnp
        import numpy as np

        from fleetx_tpu.ops import paged_attention as PA
        from fleetx_tpu.serving import swa_moe as S

        sc, cfg = serving, model_cfg
        if mesh is not None:
            from fleetx_tpu.parallel.rules import kv_pool_spec

            kv_pool_spec(num_kv_heads=cfg.num_key_value_heads,
                         tensor_degree=int(dict(mesh.shape).get("tensor", 1)))
            assert mesh.size == 1, \
                "this family serves on one chip: its programs place " \
                "neither cache on a mesh yet"
        assert not sc.quantize_decode, \
            "quantized decode is not written for this family"
        geometry = dict(num_pages=sc.num_pages, page_size=sc.page_size,
                        max_batch=sc.max_batch,
                        prefill_chunk=sc.prefill_chunk)
        cache = list(S.init_cache(cfg, **geometry))
        refused = S.gather_fallbacks(cfg, page_size=sc.page_size,
                                     pages_per_req=pages_per_req)
        active = bool(sc.paged_kernel) and not refused
        if sc.paged_kernel and refused:
            from fleetx_tpu.utils.log import logger

            # said once, when the engine is built: decode attention reads
            # a gathered view of BOTH caches, not the kernel's page walk
            logger.warning(
                "decode attention falls back to the gathered view: %s",
                "; ".join(f"{kind} layers: {why}" for kind, why in refused))
        fns = S.make_step_fns(
            cfg, prefill_chunk=sc.prefill_chunk, page_size=sc.page_size,
            sampling=sampling, paged_kernel=active)
        window = cfg.sliding_window
        ring = S.ring_pages(cfg, sc.page_size, sc.prefill_chunk)
        walk, folds = None, {}
        if active:
            geometry = dict(
                num_heads=max(cfg.num_attention_heads_per_layer),
                head_dim=cfg.head_dim, page_size=sc.page_size,
                dtype=cfg.dtype, num_kv_heads=cfg.num_key_value_heads)
            walk = PA.page_walk_shape(pages_per_req=pages_per_req, **geometry)
            folds = {"full": PA.fold_shape(pages_per_req=pages_per_req,
                                           **geometry),
                     "window": PA.fold_shape(pages_per_req=ring,
                                             ring_pages=ring, **geometry)}

        def kv_tokens(lens):
            live = lens[lens >= 0]
            return int(live.sum()), int(np.minimum(live, window).sum())

        return Programs(
            cache=cache, fns=fns,
            tokens=jnp.zeros((sc.max_batch,), jnp.int32),
            paged_kernel_active=active, walk_shape=walk, kv_folds=folds,
            prefill_extra=lambda slot: (np.int32(slot),),
            record_stats=_expert_counters(cfg), kv_tokens=kv_tokens,
            describe="%d full layers paged, %d window layers a ring of %d "
                     "pages a slot" % (cfg.layers_of("full"),
                                       cfg.layers_of("window"), ring))


class GDNMLAFamily(Family):
    """Gated-delta-rule layers beside latent attention over sparse experts
    held as a share (``models/gdn_mla``, ``serving/gdn_mla.py``;
    ``docs/gdn_mla.md``): a paged pool of latents, and a recurrent state
    and a convolution tail a slot."""

    modules = ("GDNMLAModule",)

    def model_config(self, model: dict, quantization: dict):
        """See ``Family.model_config``."""
        from fleetx_tpu.models.gdn_mla.config import config_from_dict

        assert not quantization.get("weight_bits") and \
            not quantization.get("activation_bits"), \
            "quantized decode is not written for this family"
        return config_from_dict(dict(model))

    def init_params(self, model_cfg, seed: int):
        """See ``Family.init_params``."""
        import jax

        from fleetx_tpu.models.gdn_mla.model import init_params

        # made as it is served: the recipe's tree is 9.5 GB in bfloat16
        return jax.jit(lambda key: init_params(model_cfg, key, served=True))(
            jax.random.PRNGKey(seed))

    def serving_params(self, params, model_cfg):
        """See ``Family.serving_params``."""
        from fleetx_tpu.serving.gdn_mla import serving_params

        return serving_params(params, model_cfg)

    def served_template(self, model_cfg):
        """See ``Family.served_template``."""
        from fleetx_tpu.models.gdn_mla.model import served_template

        return served_template(model_cfg)

    def programs(self, model_cfg, serving, sampling, mesh,
                 pages_per_req: int) -> Programs:
        """See ``Family.programs``."""
        import jax.numpy as jnp
        import numpy as np

        from fleetx_tpu.models.gdn_mla.config import LATENT, LINEAR
        from fleetx_tpu.ops import mla_paged_attention as LA
        from fleetx_tpu.serving import gdn_mla as S

        sc, cfg = serving, model_cfg
        assert mesh is None or mesh.size == 1, \
            "this family serves on one chip: its programs place none of " \
            "its caches on a mesh yet"
        assert not sc.quantize_decode, \
            "quantized decode is not written for this family"
        cache = list(S.init_cache(cfg, num_pages=sc.num_pages,
                                  page_size=sc.page_size,
                                  max_batch=sc.max_batch))
        refused = S.latent_kernel_refusal(cfg, page_size=sc.page_size)
        active = bool(sc.paged_kernel) and not refused
        if sc.paged_kernel and refused:
            from fleetx_tpu.utils.log import logger

            logger.warning("latent decode attention falls back to the "
                           "gathered view: %s", refused)
        fns = S.make_step_fns(cfg, prefill_chunk=sc.prefill_chunk,
                              sampling=sampling,
                              kernels=bool(sc.paged_kernel),
                              latent_kernel=active)
        folds, walk = {}, None
        if active:
            g = LA.fold_pages(sc.page_size, cache[0].shape[3], pages_per_req,
                              cfg.dtype)
            folds = {"latent": (g, g)}      # a copy a page, one buffer
            walk = (g * sc.page_size, -(-pages_per_req // g))

        return Programs(
            cache=cache, fns=fns,
            tokens=jnp.zeros((sc.max_batch,), jnp.int32),
            paged_kernel_active=active, walk_shape=walk, kv_folds=folds,
            prefill_extra=lambda slot: (np.int32(slot),),
            record_stats=_expert_counters(cfg),
            cache_bytes={"latent": int(cache[0].nbytes),
                         "state": int(cache[1].nbytes + cache[2].nbytes)},
            describe="%d latent layers paged (%d lanes a token), %d linear "
                     "layers a state and a convolution tail a slot" % (
                         cfg.layers_of(LATENT), cache[0].shape[3],
                         cfg.layers_of(LINEAR)))


class ConvMoEFamily(Family):
    """Gated short-convolution layers beside grouped-query attention over
    sparse experts held whole (``models/conv_moe``, ``serving/conv_moe.py``;
    ``docs/conv_moe.md``): a paged key-value pool for the attention layers
    only, and a convolution tail a slot."""

    modules = ("ConvMoEModule",)

    def model_config(self, model: dict, quantization: dict):
        """See ``Family.model_config``."""
        from fleetx_tpu.models.conv_moe.config import config_from_dict

        assert not quantization.get("weight_bits") and \
            not quantization.get("activation_bits"), \
            "quantized decode is not written for this family"
        return config_from_dict(dict(model))

    def init_params(self, model_cfg, seed: int):
        """See ``Family.init_params``."""
        import jax

        from fleetx_tpu.models.conv_moe.model import init_params

        # made as it is served: the recipe's tree is 10.3 GB in bfloat16
        return jax.jit(lambda key: init_params(model_cfg, key, served=True))(
            jax.random.PRNGKey(seed))

    def serving_params(self, params, model_cfg):
        """See ``Family.serving_params``."""
        from fleetx_tpu.serving.conv_moe import serving_params

        return serving_params(params, model_cfg)

    def served_template(self, model_cfg):
        """See ``Family.served_template``."""
        from fleetx_tpu.models.conv_moe.model import served_template

        return served_template(model_cfg)

    def programs(self, model_cfg, serving, sampling, mesh,
                 pages_per_req: int) -> Programs:
        """See ``Family.programs``."""
        import jax.numpy as jnp
        import numpy as np

        from fleetx_tpu.models.conv_moe.config import CONV, FULL
        from fleetx_tpu.ops import paged_attention as PA
        from fleetx_tpu.serving import conv_moe as S

        sc, cfg = serving, model_cfg
        assert mesh is None or mesh.size == 1, \
            "this family serves on one chip: its programs place neither " \
            "the key-value pool nor the convolution tails on a mesh yet"
        assert not sc.quantize_decode, \
            "quantized decode is not written for this family"
        cache = list(S.init_cache(cfg, num_pages=sc.num_pages,
                                  page_size=sc.page_size,
                                  max_batch=sc.max_batch))
        geometry = S.kernel_geometry(cfg, page_size=sc.page_size,
                                     pages_per_req=pages_per_req)
        refused = PA.paged_attention_refusal(**geometry)
        active = bool(sc.paged_kernel) and not refused
        if sc.paged_kernel and refused:
            from fleetx_tpu.utils.log import logger

            logger.warning("decode attention falls back to the gathered "
                           "view: %s", refused)
        fns = S.make_step_fns(cfg, prefill_chunk=sc.prefill_chunk,
                              sampling=sampling, paged_kernel=active)
        walk, folds = None, {}
        if active:
            walk = PA.page_walk_shape(**geometry)
            folds = {"full": PA.fold_shape(**geometry)}

        return Programs(
            cache=cache, fns=fns,
            tokens=jnp.zeros((sc.max_batch,), jnp.int32),
            paged_kernel_active=active, walk_shape=walk, kv_folds=folds,
            prefill_extra=lambda slot: (np.int32(slot),),
            record_stats=_expert_counters(cfg),
            cache_bytes={"state": int(cache[2].nbytes)},
            describe="%d attention layers paged (%d lanes a token), %d "
                     "convolution layers a tail of %d rows a slot" % (
                         cfg.layers_of(FULL), cache[0].shape[3],
                         cfg.layers_of(CONV), cache[2].shape[1]))


_FAMILIES = (GPTFamily(), SWAMoEFamily(), GDNMLAFamily(), ConvMoEFamily())


def families() -> dict:
    """``Model.module`` -> family."""
    return {m: f for f in _FAMILIES for m in f.modules}


def family(module: str) -> Family:
    """The family that serves the recipes whose ``Model.module`` is
    ``module``; an unknown one is an error that names the served ones."""
    table = families()
    if module not in table:
        raise ValueError(
            f"no serving family for Model.module {module!r}; the "
            f"{len(_FAMILIES)} served families: " + "; ".join(
                f"{type(f).__name__} ({', '.join(f.modules)})"
                for f in _FAMILIES))
    return table[module]


def family_of(model_cfg) -> Family:
    """The family of a model config: the ``Model.module`` it carries (a
    ``GPTConfig`` carries none and is the GPT block's)."""
    return family(getattr(model_cfg, "module", "GPTModule"))


def _family_for(cfg) -> Family:
    return family((cfg.get("Model") or {}).get("module", "GPTModule"))


def model_config(cfg):
    """Recipe config -> the served model's config."""
    return _family_for(cfg).model_config(
        dict(cfg.get("Model") or {}), dict(cfg.get("Quantization") or {}))


def served_template(cfg) -> tuple:
    """Recipe config -> ``(model config, abstract parameter tree)``: each
    leaf with the shape AND the dtype the engine holds it in. A caller
    that makes its own weights makes them so, and the engine finds no
    leaf to cast."""
    model_cfg = model_config(cfg)
    return model_cfg, _family_for(cfg).served_template(model_cfg)


def init_params(cfg, model_cfg=None):
    """Seeded parameters for the recipe (``Global.seed``)."""
    model_cfg = model_cfg or model_config(cfg)
    seed = int((cfg.get("Global") or {}).get("seed", 0))
    return family_of(model_cfg).init_params(model_cfg, seed)


def build_engine(cfg, model_cfg, params, *, mesh=None, sampling=None,
                 eos_token_id: Optional[int] = None,
                 seed: Optional[int] = None):
    """Recipe config + a parameter tree -> ``ServingEngine``; ``Serving:``
    and ``Generation:`` are read here, once, for every caller."""
    from fleetx_tpu.serving.decode import SamplingParams
    from fleetx_tpu.serving.engine import ServingConfig, ServingEngine

    gen = dict(cfg.get("Generation") or {})
    if sampling is None:
        strategy = gen.get("decode_strategy") or "greedy_search"
        sampling = SamplingParams(
            do_sample=strategy == "sampling",
            temperature=float(gen.get("temperature", 1.0)),
            top_k=int(gen.get("top_k", 0)),
            top_p=float(gen.get("top_p", 0.0)))
    if eos_token_id is None:
        eos_token_id = int(gen.get("eos_token_id", 50256))
    if seed is None:
        seed = int((cfg.get("Global") or {}).get("seed", 0))
    return ServingEngine(
        model_cfg, params,
        ServingConfig.from_dict(dict(cfg.get("Serving") or {})), sampling,
        eos_token_id=eos_token_id, mesh=mesh, seed=seed)
