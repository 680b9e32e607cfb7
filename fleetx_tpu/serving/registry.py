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
import importlib
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from fleetx_tpu.ops import paged_attention as PA
from fleetx_tpu.serving import programs
from fleetx_tpu.utils.log import logger

__all__ = ["Family", "KernelWalk", "Programs", "families", "family",
           "family_of", "model_config", "served_template", "init_params",
           "build_engine"]


@dataclasses.dataclass(frozen=True)
class KernelWalk:
    """How the decode kernel walks an engine's caches."""

    # (tokens one fold of the kernel covers, folds of a table row)
    walk_shape: tuple
    # cache kind ("full", "window", "latent") the engine has -> (pages one
    # fold takes, copies a cache buffer that fetch them)
    kv_folds: dict


@dataclasses.dataclass
class Programs:
    """What one engine holds of its family."""

    cache: list                     # device buffers, donated every call
    fns: dict                       # {"prefill", "decode"}: jitted
    # [max_batch] int32 on the device: what the first ``decode`` call takes
    # as the last tokens; every later one takes the call before's output
    tokens: object
    # None: decode attention reads the gathered view
    kernel: Optional[KernelWalk]

    @property
    def paged_kernel_active(self) -> bool:
        return self.kernel is not None

    @property
    def kv_folds(self) -> dict:
        return self.kernel.kv_folds if self.kernel else {}


class Family:
    """One served model family: a class of attributes, its ``programs()``
    and the answers below that differ from the default written here.

    ``model_package`` names a package with ``config.config_from_dict`` and
    ``model.init_params(cfg, key, served=True)`` / ``served_template`` /
    ``served_dtype``; ``serving_module`` the family's caches and forward."""

    modules: tuple = ()             # the ``Model.module`` names it serves
    model_package: str = ""         # "fleetx_tpu.models.<family>"
    serving_module: str = ""        # "fleetx_tpu.serving.<family>"
    #: what the one warning calls the attention that fell back
    decode_attention = "decode attention"
    #: what the programs do not place on a mesh yet
    unplaced = "its caches"

    def _model(self, part: str):
        return importlib.import_module(f"{self.model_package}.{part}")

    def _serving(self):
        return importlib.import_module(self.serving_module)

    # ---------------------------------------------------------- parameters
    def model_config(self, model: dict, quantization: dict):
        """The recipe's ``Model:`` (and ``Quantization:``) -> config."""
        assert not quantization.get("weight_bits") and \
            not quantization.get("activation_bits"), \
            f"quantized decode is not written for {type(self).__name__}"
        return self._model("config").config_from_dict(dict(model))

    def init_params(self, model_cfg, seed: int):
        """Seeded parameters, made as they are served: a recipe's tree is
        6–10 GB in bfloat16 and would be twice that beside its cast."""
        init = self._model("model").init_params
        return jax.jit(lambda key: init(model_cfg, key, served=True))(
            jax.random.PRNGKey(seed))

    def serving_params(self, params, model_cfg):
        """The tree the programs take (cast once; ``programs.py``)."""
        return programs.serving_params(params, model_cfg,
                                       self._model("model").served_dtype)

    def served_template(self, model_cfg):
        """That tree as ``ShapeDtypeStruct`` leaves, nothing initialised."""
        return self._model("model").served_template(model_cfg)

    # ------------------------------------------------------------ programs
    def programs(self, model_cfg, serving, sampling, mesh,
                 pages_per_req: int) -> Programs:
        """The cache buffers and the two jitted programs of one engine."""
        raise NotImplementedError

    def _one_chip_unquantized(self, serving, mesh) -> None:
        """What every family but GPT refuses, with a sentence."""
        assert mesh is None or mesh.size == 1, \
            f"{type(self).__name__} serves on one chip: its programs " \
            f"place {self.unplaced} on a mesh yet"
        assert not serving.quantize_decode, \
            f"quantized decode is not written for {type(self).__name__}"

    def _kernel_serves(self, serving, refusal: str) -> bool:
        """Kernel or gather, decided HERE, once, for every family.
        ``refusal``: why the decode kernel cannot take this geometry ("":
        it can) — a static function of the config, the pool and the mesh,
        so the decode program compiles exactly one attention path and the
        jit cache stays pinned at one entry."""
        if serving.paged_kernel and refusal:
            # said once, when the engine is built
            logger.warning("%s falls back to the gathered view: %s",
                           self.decode_attention, refusal)
        return bool(serving.paged_kernel) and not refusal

    # ------------------------------------- what a family says about itself
    def describe(self, model_cfg, serving, cache: list) -> str:
        """What the engine's start-up line says after the caches' bytes:
        ``" (<the caches, in words>)"``."""
        return " (%s)" % self._serving().describe(model_cfg, serving, cache)

    def cache_bytes(self, cache: list) -> dict:
        """Bytes of the caches that are not lists of keys and values:
        "latent" (a paged pool of latents) and "state" (constant-size state
        a slot: a recurrent state, a convolution's tail)."""
        return {"latent": 0, "state": 0}

    def prefill_extra(self, slot: int) -> tuple:
        """Further arguments of ``prefill`` after the rng and the draw: the
        slot whose ring, state or tail the request owns."""
        return (np.int32(slot),)

    def kv_tokens(self, model_cfg, lens) -> tuple:
        """Host lengths [slots] -> (tokens held in full layers' pages,
        tokens held in window layers' rings), a layer each."""
        return int(lens[lens >= 0].sum()), 0

    def stats_recorder(self, model_cfg):
        """``record(metrics, what decode returned after its logits)``: the
        step's expert counters -> the ``serving_moe_*`` metrics."""
        return programs.expert_stats_recorder(model_cfg)

    def stats_snapshot(self, metrics) -> dict:
        """The ``serving_snapshot()`` keys of what the recorder keeps."""
        return programs.expert_stats_snapshot(metrics)


class GPTFamily(Family):
    """The GPT block (``models/gpt``, ``serving/decode.py``): flax
    parameters, QAT bits, pools placed on a mesh; no experts, no slot."""

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
        """Seeded parameters, in the model's ``param_dtype``."""
        from flax.core import meta

        from fleetx_tpu.models.gpt.model import GPTForPretraining

        return meta.unbox(GPTForPretraining(model_cfg).init(
            {"params": jax.random.PRNGKey(seed)},
            jnp.zeros((1, 8), jnp.int32), None,
            deterministic=True)["params"])

    def serving_params(self, params, model_cfg):
        """``decode.py``'s own rule: the layer norms' leaves as they came."""
        from fleetx_tpu.serving.decode import serving_params

        return serving_params(params, model_cfg)

    def served_template(self, model_cfg):
        """See ``Family.served_template``."""
        return jax.eval_shape(lambda: self.serving_params(
            self.init_params(model_cfg, 0), model_cfg))

    def programs(self, model_cfg, serving, sampling, mesh,
                 pages_per_req: int) -> Programs:
        """See ``Family.programs``."""
        from fleetx_tpu.serving import decode as S
        from fleetx_tpu.serving.paged_cache import init_pool, pool_shardings

        sc = serving
        cache = list(init_pool(model_cfg, sc.num_pages, sc.page_size))
        tokens = jnp.zeros((sc.max_batch,), jnp.int32)
        sharding = None
        if mesh is not None:
            sharding = pool_shardings(mesh)
            cache = [jax.device_put(pool, sharding) for pool in cache]
            tokens = jax.device_put(tokens, S.token_sharding(mesh))
        geometry = dict(page_size=sc.page_size, pages_per_req=pages_per_req,
                        pool_sharding=sharding)
        kernel = self._kernel_serves(sc, S.kernel_refusal(
            model_cfg, num_pages=sc.num_pages, **geometry))
        fns = S.make_step_fns(
            model_cfg, max_batch=sc.max_batch, pages_per_req=pages_per_req,
            prefill_chunk=sc.prefill_chunk, sampling=sampling,
            quantize=bool(sc.quantize_decode), pool_sharding=sharding,
            paged_kernel=kernel)
        walk = None
        if kernel:
            one_shard = S.kernel_geometry(model_cfg, **geometry)
            walk = KernelWalk(PA.page_walk_shape(**one_shard),
                              {"full": PA.fold_shape(**one_shard)})
        return Programs(cache=cache, fns=fns, tokens=tokens, kernel=walk)

    def describe(self, model_cfg, serving, cache: list) -> str:
        return ""

    def prefill_extra(self, slot: int) -> tuple:
        return ()

    def stats_recorder(self, model_cfg):
        """No experts: ``decode`` returns nothing after its logits."""
        return lambda metrics, counters: None

    def stats_snapshot(self, metrics) -> dict:
        return {}


class SWAMoEFamily(Family):
    """Windowed and full grouped-query attention over sparse experts, all
    of them held or a share (``models/swa_moe``, ``serving/swa_moe.py``;
    ``docs/swa_moe.md`` has the family's members)."""

    modules = ("SWAMoEModule",)
    model_package = "fleetx_tpu.models.swa_moe"
    serving_module = "fleetx_tpu.serving.swa_moe"
    unplaced = "neither cache"

    def programs(self, model_cfg, serving, sampling, mesh,
                 pages_per_req: int) -> Programs:
        """See ``Family.programs``."""
        S, sc = self._serving(), serving
        if mesh is not None:
            from fleetx_tpu.parallel.rules import kv_pool_spec

            kv_pool_spec(num_kv_heads=model_cfg.num_key_value_heads,
                         tensor_degree=int(dict(mesh.shape).get("tensor", 1)))
        self._one_chip_unquantized(sc, mesh)
        geometry = dict(page_size=sc.page_size, pages_per_req=pages_per_req)
        cache = S.init_cache(
            model_cfg, num_pages=sc.num_pages, page_size=sc.page_size,
            max_batch=sc.max_batch, prefill_chunk=sc.prefill_chunk)
        kernel = self._kernel_serves(
            sc, S.kernel_refusal(model_cfg, **geometry))
        fns = S.make_step_fns(
            model_cfg, prefill_chunk=sc.prefill_chunk, page_size=sc.page_size,
            sampling=sampling, paged_kernel=kernel)
        walk = KernelWalk(*S.kernel_walk(
            model_cfg, prefill_chunk=sc.prefill_chunk, **geometry)) \
            if kernel else None
        return Programs(cache=list(cache), fns=fns, kernel=walk,
                        tokens=jnp.zeros((sc.max_batch,), jnp.int32))

    def kv_tokens(self, model_cfg, lens) -> tuple:
        """See ``Family.kv_tokens``: a ring holds the window's at most."""
        live = lens[lens >= 0]
        return int(live.sum()), int(
            np.minimum(live, model_cfg.sliding_window).sum())


class GDNMLAFamily(Family):
    """Gated-delta-rule layers beside latent attention over sparse experts
    held as a share (``models/gdn_mla``, ``serving/gdn_mla.py``;
    ``docs/gdn_mla.md``): a paged pool of latents, and a recurrent state
    and a convolution tail a slot."""

    modules = ("GDNMLAModule",)
    model_package = "fleetx_tpu.models.gdn_mla"
    serving_module = "fleetx_tpu.serving.gdn_mla"
    decode_attention = "latent decode attention"
    unplaced = "none of its caches"

    def programs(self, model_cfg, serving, sampling, mesh,
                 pages_per_req: int) -> Programs:
        """See ``Family.programs``."""
        from fleetx_tpu.ops import mla_paged_attention as LA

        S, sc = self._serving(), serving
        self._one_chip_unquantized(sc, mesh)
        cache = S.init_cache(model_cfg, num_pages=sc.num_pages,
                             page_size=sc.page_size, max_batch=sc.max_batch)
        kernel = self._kernel_serves(
            sc, S.kernel_refusal(model_cfg, page_size=sc.page_size))
        fns = S.make_step_fns(
            model_cfg, prefill_chunk=sc.prefill_chunk, sampling=sampling,
            kernels=bool(sc.paged_kernel), latent_kernel=kernel)
        walk = None
        if kernel:
            g = LA.fold_pages(sc.page_size, cache[0].shape[3], pages_per_req,
                              model_cfg.dtype)
            # a copy a page, one buffer
            walk = KernelWalk((g * sc.page_size, -(-pages_per_req // g)),
                              {"latent": (g, g)})
        return Programs(cache=list(cache), fns=fns, kernel=walk,
                        tokens=jnp.zeros((sc.max_batch,), jnp.int32))

    def cache_bytes(self, cache: list) -> dict:
        return {"latent": int(cache[0].nbytes),
                "state": int(cache[1].nbytes + cache[2].nbytes)}


class ConvMoEFamily(Family):
    """Gated short-convolution layers beside grouped-query attention over
    sparse experts held whole (``models/conv_moe``, ``serving/conv_moe.py``;
    ``docs/conv_moe.md``): a paged key-value pool for the attention layers
    only, and a convolution tail a slot."""

    modules = ("ConvMoEModule",)
    model_package = "fleetx_tpu.models.conv_moe"
    serving_module = "fleetx_tpu.serving.conv_moe"
    unplaced = "neither the key-value pool nor the convolution tails"

    def programs(self, model_cfg, serving, sampling, mesh,
                 pages_per_req: int) -> Programs:
        """See ``Family.programs``."""
        S, sc = self._serving(), serving
        self._one_chip_unquantized(sc, mesh)
        cache = S.init_cache(model_cfg, num_pages=sc.num_pages,
                             page_size=sc.page_size, max_batch=sc.max_batch)
        geometry = dict(page_size=sc.page_size, pages_per_req=pages_per_req)
        kernel = self._kernel_serves(
            sc, S.kernel_refusal(model_cfg, **geometry))
        fns = S.make_step_fns(model_cfg, prefill_chunk=sc.prefill_chunk,
                              sampling=sampling, paged_kernel=kernel)
        walk = None
        if kernel:
            asked = S.kernel_geometry(model_cfg, **geometry)
            walk = KernelWalk(PA.page_walk_shape(**asked),
                              {"full": PA.fold_shape(**asked)})
        return Programs(cache=list(cache), fns=fns, kernel=walk,
                        tokens=jnp.zeros((sc.max_batch,), jnp.int32))

    def cache_bytes(self, cache: list) -> dict:
        return {"latent": 0, "state": int(cache[2].nbytes)}


class SambaYFamily(Family):
    """Selective-scan layers alternating with differential attention over a
    window, one full attention layer whose keys and values every later
    attention layer reads, gated memory units (``models/samba_y``,
    ``serving/samba_y.py``; ``docs/samba_y.md``): one paged pool that one
    layer writes and eight read, a ring a slot for the window layers, a
    float32 state and a convolution tail a slot for the scan layers. No
    experts."""

    modules = ("SambaYModule",)
    model_package = "fleetx_tpu.models.samba_y"
    serving_module = "fleetx_tpu.serving.samba_y"
    decode_attention = "decode attention and the selective scan"
    unplaced = "none of its four caches"

    def programs(self, model_cfg, serving, sampling, mesh,
                 pages_per_req: int) -> Programs:
        """See ``Family.programs``."""
        S, sc = self._serving(), serving
        self._one_chip_unquantized(sc, mesh)
        geometry = dict(page_size=sc.page_size, pages_per_req=pages_per_req,
                        prefill_chunk=sc.prefill_chunk)
        cache = S.init_cache(
            model_cfg, num_pages=sc.num_pages, page_size=sc.page_size,
            max_batch=sc.max_batch, prefill_chunk=sc.prefill_chunk)
        kernels = self._kernel_serves(
            sc, S.kernel_refusal(model_cfg, **geometry))
        fns = S.make_step_fns(
            model_cfg, prefill_chunk=sc.prefill_chunk, page_size=sc.page_size,
            sampling=sampling, kernels=kernels)
        walk = KernelWalk(*S.kernel_walk(model_cfg, **geometry)) \
            if kernels else None
        return Programs(cache=list(cache), fns=fns, kernel=walk,
                        tokens=jnp.zeros((sc.max_batch,), jnp.int32))

    def cache_bytes(self, cache: list) -> dict:
        return {"latent": 0, "state": int(cache[4].nbytes + cache[5].nbytes)}

    def kv_tokens(self, model_cfg, lens) -> tuple:
        """See ``Family.kv_tokens``: the one paged layer holds every token,
        a ring the window's at most."""
        live = lens[lens >= 0]
        return int(live.sum()), int(
            np.minimum(live, model_cfg.sliding_window).sum())

    def stats_recorder(self, model_cfg):
        """No experts: nothing of what ``decode`` returns after its logits
        is recorded."""
        return lambda metrics, counters: None

    def stats_snapshot(self, metrics) -> dict:
        return {}


class SSMMQAFamily(Family):
    """Selective-scan layers whose step, ``B`` and ``C`` are normed, beside
    a few multi-query attention layers (``models/ssm_mqa``,
    ``serving/ssm_mqa.py``; ``docs/ssm_mqa.md``): a paged key-value pool
    one lane tile wide for the attention layers only, a float32 state and a
    convolution tail a slot for the scan layers. No experts."""

    modules = ("SSMMQAModule",)
    model_package = "fleetx_tpu.models.ssm_mqa"
    serving_module = "fleetx_tpu.serving.ssm_mqa"
    decode_attention = "decode attention and the selective scan"
    unplaced = "none of its three caches"

    def programs(self, model_cfg, serving, sampling, mesh,
                 pages_per_req: int) -> Programs:
        """See ``Family.programs``."""
        S, sc = self._serving(), serving
        self._one_chip_unquantized(sc, mesh)
        geometry = dict(page_size=sc.page_size, pages_per_req=pages_per_req)
        cache = S.init_cache(model_cfg, num_pages=sc.num_pages,
                             page_size=sc.page_size, max_batch=sc.max_batch)
        kernels = self._kernel_serves(sc, S.kernel_refusal(
            model_cfg, prefill_chunk=sc.prefill_chunk,
            max_batch=sc.max_batch, **geometry))
        fns = S.make_step_fns(model_cfg, prefill_chunk=sc.prefill_chunk,
                              sampling=sampling, kernels=kernels)
        walk = None
        if kernels:
            asked = S.kernel_geometry(model_cfg, **geometry)
            walk = KernelWalk(PA.page_walk_shape(**asked),
                              {"full": PA.fold_shape(**asked)})
        return Programs(cache=list(cache), fns=fns, kernel=walk,
                        tokens=jnp.zeros((sc.max_batch,), jnp.int32))

    def cache_bytes(self, cache: list) -> dict:
        return {"latent": 0, "state": int(cache[2].nbytes + cache[3].nbytes)}

    def stats_recorder(self, model_cfg):
        """No experts: nothing of what ``decode`` returns after its logits
        is recorded."""
        return lambda metrics, counters: None

    def stats_snapshot(self, metrics) -> dict:
        return {}


_FAMILIES = (GPTFamily(), SWAMoEFamily(), GDNMLAFamily(), ConvMoEFamily(),
             SambaYFamily(), SSMMQAFamily())


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
    from fleetx_tpu.serving.engine import ServingConfig, ServingEngine

    gen = dict(cfg.get("Generation") or {})
    if sampling is None:
        strategy = gen.get("decode_strategy") or "greedy_search"
        sampling = programs.SamplingParams(
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
