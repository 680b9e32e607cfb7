"""The device programs of every family at toy widths, as ``(what, jitted,
args)`` for ``utils.env.log_compile`` or for ``jitted.lower(*args)``: the
three train steps (GPT on one device, GPT under ZeRO stage 2 with the
overlapped update, the latent-attention expert family) and ``prefill`` +
``decode`` of the served ones (GPT, the two members of the
windowed-attention expert family, the linear-attention / latent-attention
family, the short-convolution family, the two selective-scan families). Tests only; arguments are abstract
wherever nothing has to be initialised."""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GPT_RECIPE = "fleetx_tpu/configs/nlp/gpt/pretrain_gpt_345M_synthetic.yaml"
JOYAI_RECIPE = \
    "fleetx_tpu/configs/nlp/mla_moe/pretrain_joyai_flash_share16_synthetic.yaml"

GPT_TOY = ["Model.vocab_size=512", "Model.hidden_size=128",
           "Model.num_layers=2", "Model.num_attention_heads=2",
           "Model.max_position_embeddings=128", "Global.max_seq_len=128",
           "Model.hidden_dropout_prob=0.0",
           "Model.attention_probs_dropout_prob=0.0", "Model.dtype=float32"]
JOYAI_TOY = dict(
    hidden_size=64, intermediate_size=96, num_attention_heads=4,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, moe_intermediate_size=32, num_experts_per_tok=3,
    vocab_size=256, num_hidden_layers=3, rope_theta=10000.0,
    n_routed_experts=16, experts_held=4, first_expert_held=4,
    moe_chunk_rows=64, moe_tile_rows=8, loss_chunk_rows=64)
I32, U32 = jnp.int32, jnp.uint32


def _abstract(tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        tree)


def _arr(shape, dtype=I32):
    return jax.ShapeDtypeStruct(shape, dtype)


# ------------------------------------------------------------------- training
def train_step(recipe: str, overrides: list, devices: list) -> tuple:
    """Recipe + overrides -> the engine's jitted train step and its
    arguments (state initialised, one zero batch on the mesh), through the
    calls ``tools/train.py`` makes."""
    from fleetx_tpu.core.engine import EagerEngine
    from fleetx_tpu.models import build_module
    from fleetx_tpu.optims import build_lr_scheduler, build_optimizer
    from fleetx_tpu.parallel.mesh import build_mesh, set_mesh
    from fleetx_tpu.utils import config as config_mod

    cfg = config_mod.get_config(os.path.join(ROOT, recipe), list(overrides),
                                num_devices=len(devices))
    mesh = set_mesh(build_mesh(cfg.get("Distributed"), devices=devices))
    opt_cfg = dict(cfg.get("Optimizer") or {})
    lr = build_lr_scheduler(opt_cfg.get("lr"))
    engine = EagerEngine(cfg, build_module(cfg),
                         optimizer=build_optimizer(opt_cfg, lr),
                         lr_schedule=lr, mesh=mesh)
    rows = int(cfg["Global"]["local_batch_size"]) * len(devices)
    seq = int(cfg["Global"]["max_seq_len"])
    batch = {"tokens": np.zeros((rows, seq), np.int32),
             "labels": np.zeros((rows, seq), np.int32),
             "loss_mask": np.ones((rows, seq), np.float32),
             "position_ids": np.broadcast_to(
                 np.arange(seq, dtype=np.int32), (rows, seq)).copy()}
    engine.prepare(batch)
    return engine, (engine.state, engine.shard_batch(batch))


def gpt_train(devices: list) -> tuple:
    engine, args = train_step(GPT_RECIPE, GPT_TOY + [
        "Global.local_batch_size=2", "Global.micro_batch_size=2"],
        devices[:1])
    return "train step", engine._train_step, args


def gpt_train_zero2(devices: list) -> tuple:
    """The four-chip cell's layout: fsdp 4, stage 2, overlapped update,
    full recompute."""
    engine, args = train_step(GPT_RECIPE, GPT_TOY + [
        "Global.local_batch_size=2", "Global.micro_batch_size=2",
        "Model.use_recompute=True", "Model.recompute_granularity=full",
        "Distributed.dp_degree=1", "Distributed.fsdp_degree=4",
        "Distributed.sharding.sharding_degree=4",
        "Distributed.sharding.sharding_stage=2",
        "Distributed.sharding.overlap_update=True"], devices[:4])
    return "train step", engine._train_step, args


def joyai_train(devices: list) -> tuple:
    engine, args = train_step(JOYAI_RECIPE, [
        f"Model.{k}={v}" for k, v in JOYAI_TOY.items()] + [
        "Model.dtype=float32", "Global.max_seq_len=128",
        "Global.local_batch_size=2", "Global.micro_batch_size=2"],
        devices[:1])
    return "train step", engine._train_step, args


# -------------------------------------------------------------------- serving
def gpt_serve(batch=4, pages=33, page=4, per_req=8, chunk=8) -> list:
    from flax.core import meta

    from fleetx_tpu.models.gpt.model import (GPTForPretraining,
                                             config_from_dict)
    from fleetx_tpu.serving.decode import (SamplingParams, make_step_fns,
                                           serving_params)
    from fleetx_tpu.serving.paged_cache import init_pool

    cfg = config_from_dict(dict(
        vocab_size=97, hidden_size=64, num_layers=2, num_attention_heads=4,
        max_position_embeddings=64, use_flash_attention=False,
        dtype="float32", param_dtype="float32"))
    tree = meta.unbox(jax.eval_shape(lambda: GPTForPretraining(cfg).init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), I32), None,
        deterministic=True)["params"]))
    params = jax.eval_shape(lambda t: serving_params(t, cfg), tree)
    pool = _abstract(jax.eval_shape(lambda: init_pool(cfg, pages, page))[0])
    fns = make_step_fns(cfg, max_batch=batch, pages_per_req=per_req,
                        prefill_chunk=chunk, sampling=SamplingParams())
    rng = _arr((2,), U32)
    return [
        ("serving prefill", fns["prefill"],
         (params, pool, pool, _arr((1, chunk)), _arr((1, per_req)), _arr(()),
          _arr(()), rng, _arr((), U32))),
        ("serving decode", fns["decode"],
         (params, pool, pool, _arr((batch,)), _arr(()), _arr((1,)),
          _arr((batch, per_req)), _arr((batch,)), rng, _arr((), U32)))]


def _laguna_toy() -> dict:
    with open(os.path.join(ROOT, "benchmarks/configs/laguna-s-2.1.json")) as f:
        rope = json.load(f)["rope_parameters"]
    return dict(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        num_hidden_layers=9,
        num_attention_heads_per_layer=[4, 6, 6, 6] * 2 + [4],
        layer_types=(["full_attention"] + ["sliding_attention"] * 3) * 2
        + ["full_attention"],
        mlp_only_layers=[0], num_key_value_heads=2, head_dim=16,
        sliding_window=8, num_experts=16, experts_held=4,
        first_expert_held=4, num_experts_per_tok=3, moe_intermediate_size=24,
        shared_expert_intermediate_size=24, moe_routed_scaling_factor=2.5,
        gating="per-head", router_input="post_attention",
        router_scoring="softmax_topk", hidden_act="silu",
        rope_parameters=rope, dtype="float32", param_dtype="float32",
        max_position_embeddings=4096)


def _smallthinker_toy() -> dict:
    rotary = {"rope_type": "default", "rope_theta": 1500000,
              "partial_rotary_factor": 1}
    return dict(
        vocab_size=64, hidden_size=32, intermediate_size=0,
        num_hidden_layers=8, num_attention_heads_per_layer=[14] * 8,
        layer_types=(["full_attention"] + ["sliding_attention"] * 3) * 2,
        mlp_only_layers=[], num_key_value_heads=2, head_dim=16,
        sliding_window=8,
        rope_parameters={"sliding_attention": rotary,
                         "full_attention": "none"},
        num_experts=16, experts_held=16, first_expert_held=0,
        num_experts_per_tok=3, moe_intermediate_size=24,
        shared_expert_intermediate_size=0, moe_routed_scaling_factor=1.0,
        norm_topk_prob=True, gating="none", router_input="pre_attention",
        router_scoring="topk_softmax", hidden_act="relu", dtype="float32",
        param_dtype="float32", max_position_embeddings=4096)


def swa_moe_serve(toy: dict, batch=3, page=4, chunk=8, max_seq=64) -> list:
    from fleetx_tpu.models.swa_moe import model as M
    from fleetx_tpu.models.swa_moe.config import config_from_dict
    from fleetx_tpu.serving import swa_moe as S
    from fleetx_tpu.serving.decode import SamplingParams

    cfg = config_from_dict(toy)
    per_req = max_seq // page
    params = M.served_template(cfg)
    cache = _abstract(jax.eval_shape(lambda: S.init_cache(
        cfg, num_pages=1 + batch * per_req, page_size=page, max_batch=batch,
        prefill_chunk=chunk)))
    fns = S.make_step_fns(cfg, page_size=page, prefill_chunk=chunk,
                          sampling=SamplingParams())
    rng = _arr((2,), U32)
    return [
        ("serving prefill", fns["prefill"],
         (params, *cache, _arr((1, chunk)), _arr((1, per_req)), _arr(()),
          _arr(()), rng, _arr((), U32), _arr(()))),
        ("serving decode", fns["decode"],
         (params, *cache, _arr((batch,)), _arr(()), _arr((1,)),
          _arr((batch, per_req)), _arr((batch,)), rng, _arr((), U32)))]


def laguna_serve() -> list:
    return swa_moe_serve(_laguna_toy())


def smallthinker_serve() -> list:
    return swa_moe_serve(_smallthinker_toy())


def gigachat_serve(batch=3, page=4, chunk=8, max_seq=64) -> list:
    """The linear-attention / latent-attention family at toy widths."""
    import gdn_mla_toy

    from fleetx_tpu.models.gdn_mla import model as M
    from fleetx_tpu.models.gdn_mla.config import config_from_dict
    from fleetx_tpu.serving import gdn_mla as S
    from fleetx_tpu.serving.decode import SamplingParams

    cfg = config_from_dict(gdn_mla_toy.model_section())
    per_req = max_seq // page
    params = M.served_template(cfg)
    cache = _abstract(jax.eval_shape(lambda: S.init_cache(
        cfg, num_pages=1 + batch * per_req, page_size=page,
        max_batch=batch)))
    fns = S.make_step_fns(cfg, prefill_chunk=chunk,
                          sampling=SamplingParams())
    rng = _arr((2,), U32)
    return [
        ("serving prefill", fns["prefill"],
         (params, *cache, _arr((1, chunk)), _arr((1, per_req)), _arr(()),
          _arr(()), rng, _arr((), U32), _arr(()))),
        ("serving decode", fns["decode"],
         (params, *cache, _arr((batch,)), _arr(()), _arr((1,)),
          _arr((batch, per_req)), _arr((batch,)), rng, _arr((), U32)))]


def lfm2_serve(batch=3, page=4, chunk=8, max_seq=64) -> list:
    """The short-convolution / grouped-query family at toy widths."""
    import conv_moe_toy

    from fleetx_tpu.models.conv_moe import model as M
    from fleetx_tpu.models.conv_moe.config import config_from_dict
    from fleetx_tpu.serving import conv_moe as S
    from fleetx_tpu.serving.decode import SamplingParams

    cfg = config_from_dict(conv_moe_toy.model_section())
    per_req = max_seq // page
    params = M.served_template(cfg)
    cache = _abstract(jax.eval_shape(lambda: S.init_cache(
        cfg, num_pages=1 + batch * per_req, page_size=page,
        max_batch=batch)))
    fns = S.make_step_fns(cfg, prefill_chunk=chunk,
                          sampling=SamplingParams())
    rng = _arr((2,), U32)
    return [
        ("serving prefill", fns["prefill"],
         (params, *cache, _arr((1, chunk)), _arr((1, per_req)), _arr(()),
          _arr(()), rng, _arr((), U32), _arr(()))),
        ("serving decode", fns["decode"],
         (params, *cache, _arr((batch,)), _arr(()), _arr((1,)),
          _arr((batch, per_req)), _arr((batch,)), rng, _arr((), U32)))]


def phi4flash_serve(batch=3, page=4, chunk=8, max_seq=64) -> list:
    """The decoder-hybrid-decoder family (selective scan, differential
    attention, one shared key-value layer) at toy widths."""
    import samba_y_toy

    from fleetx_tpu.models.samba_y import model as M
    from fleetx_tpu.models.samba_y.config import config_from_dict
    from fleetx_tpu.serving import samba_y as S
    from fleetx_tpu.serving.decode import SamplingParams

    cfg = config_from_dict(samba_y_toy.model_section())
    per_req = max_seq // page
    params = M.served_template(cfg)
    cache = _abstract(jax.eval_shape(lambda: S.init_cache(
        cfg, num_pages=1 + batch * per_req, page_size=page,
        max_batch=batch, prefill_chunk=chunk)))
    fns = S.make_step_fns(cfg, prefill_chunk=chunk, page_size=page,
                          sampling=SamplingParams())
    rng = _arr((2,), U32)
    return [
        ("serving prefill", fns["prefill"],
         (params, *cache, _arr((1, chunk)), _arr((1, per_req)), _arr(()),
          _arr(()), rng, _arr((), U32), _arr(()))),
        ("serving decode", fns["decode"],
         (params, *cache, _arr((batch,)), _arr(()), _arr((1,)),
          _arr((batch, per_req)), _arr((batch,)), rng, _arr((), U32)))]


def jamba2_serve(batch=3, page=4, chunk=8, max_seq=64) -> list:
    """The scan / multi-query family (selective scan with normed step, B
    and C; multi-query attention) at toy widths."""
    import ssm_mqa_toy

    from fleetx_tpu.models.ssm_mqa import model as M
    from fleetx_tpu.models.ssm_mqa.config import config_from_dict
    from fleetx_tpu.serving import ssm_mqa as S
    from fleetx_tpu.serving.decode import SamplingParams

    cfg = config_from_dict(ssm_mqa_toy.model_section())
    per_req = max_seq // page
    params = M.served_template(cfg)
    cache = _abstract(jax.eval_shape(lambda: S.init_cache(
        cfg, num_pages=1 + batch * per_req, page_size=page,
        max_batch=batch)))
    fns = S.make_step_fns(cfg, prefill_chunk=chunk,
                          sampling=SamplingParams())
    rng = _arr((2,), U32)
    return [
        ("serving prefill", fns["prefill"],
         (params, *cache, _arr((1, chunk)), _arr((1, per_req)), _arr(()),
          _arr(()), rng, _arr((), U32), _arr(()))),
        ("serving decode", fns["decode"],
         (params, *cache, _arr((batch,)), _arr(()), _arr((1,)),
          _arr((batch, per_req)), _arr((batch,)), rng, _arr((), U32)))]


#: family -> the programs' builder; a train builder takes the devices
TRAIN = {"gpt": gpt_train, "gpt_zero2": gpt_train_zero2,
         "joyai": joyai_train}
SERVE = {"gpt": gpt_serve, "laguna": laguna_serve,
         "smallthinker": smallthinker_serve, "gigachat": gigachat_serve,
         "lfm2": lfm2_serve, "phi4flash": phi4flash_serve,
         "jamba2": jamba2_serve}
