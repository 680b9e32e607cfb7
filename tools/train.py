"""Training entry point (reference ``tools/train.py:38-72``).

Usage::

    python tools/train.py -c fleetx_tpu/configs/nlp/gpt/pretrain_gpt_345M_single_card.yaml \
        -o Engine.max_steps=100 -o Model.hidden_size=512

The reference bootstraps NCCL groups via ``fleet.init``; here process
bootstrap is ``jax.distributed.initialize`` (multi-host) or nothing (single
host), and the mesh is built from the ``Distributed`` config section.
"""

import json
import sys
import os

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax

from fleetx_tpu.core.checkpoint import peek_meta
from fleetx_tpu.core.engine import EagerEngine
from fleetx_tpu.data import build_dataloader
from fleetx_tpu.models import build_module
from fleetx_tpu.observability.memory import device_placement
from fleetx_tpu.optims import build_lr_scheduler, build_optimizer
from fleetx_tpu.parallel.mesh import build_mesh, set_mesh
from fleetx_tpu.utils import config as config_mod
from fleetx_tpu.utils import env as env_mod
from fleetx_tpu.utils.log import logger


def main(auto_layout: bool = False):
    args = config_mod.parse_args("fleetx_tpu train")
    env_mod.init_dist_env()
    env_mod.init_compile_cache()
    cfg = config_mod.get_config(args.config, args.override, show=True,
                                auto_layout=auto_layout)

    from fleetx_tpu.utils.check import check_config
    check_config(cfg)

    mesh = set_mesh(build_mesh(cfg.get("Distributed")))
    module = build_module(cfg)

    opt_cfg = dict(cfg.get("Optimizer") or {})
    lr = build_lr_scheduler(opt_cfg.get("lr"))
    optimizer = build_optimizer(opt_cfg, lr)
    engine = EagerEngine(cfg, module, optimizer=optimizer, lr_schedule=lr,
                         mesh=mesh)

    # sampler-level resume (reference wires this via GPTBatchSampler
    # consumed_samples, batch_sampler.py:116-131)
    consumed = 0
    ckpt_dir = engine.ckpt_dir or engine.output_dir
    meta = peek_meta(ckpt_dir) if ckpt_dir else None
    if meta:
        consumed = int(meta.get("consumed_samples", 0))
        engine.ckpt_dir = ckpt_dir
        logger.info("resuming: consumed_samples=%d", consumed)

    glb = cfg.get("Global", {})
    n_proc = jax.process_count()
    per_host_bs = int(glb.get("global_batch_size", 8)) // n_proc
    data_cfg = cfg.get("Data") or {}
    shape_kwargs = dict(
        seq_length=int(glb.get("max_seq_len", 1024)),
        vocab_size=int((cfg.get("Model") or {}).get("vocab_size") or 50304))
    train_dl = build_dataloader(
        data_cfg, "Train", num_replicas=n_proc, rank=jax.process_index(),
        consumed_samples=consumed,  # global-sample units, same as the sampler
        batch_size=per_host_bs, **shape_kwargs)
    valid_dl = None
    # eval_freq 0 disables evaluation — don't build (or require) eval data
    if engine.eval_freq and (data_cfg.get("Eval") or {}).get("dataset"):
        valid_dl = build_dataloader(
            data_cfg, "Eval", num_replicas=n_proc, rank=jax.process_index(),
            batch_size=per_host_bs, **shape_kwargs)

    engine._consumed_samples = consumed
    engine.fit(train_dl, valid_dl,
               epoch_num=int(cfg.get("Engine", {}).get("num_train_epochs", 1)))
    logger.info("placement: %s", json.dumps(device_placement(
        {"params": engine.state.params, "opt_state": engine.state.opt_state})))
    if engine.save_steps:
        engine.save()


if __name__ == "__main__":
    main()
