"""LoRA fine-tuning entry point (docs/finetune.md).

Usage::

    python tools/finetune.py \
        -c fleetx_tpu/configs/nlp/gpt/finetune_gpt_345M_lora.yaml \
        -o FineTune.base_ckpt=./output/pretrain \
        -o Engine.max_steps=200

The config is an ordinary training recipe whose ``Model.module`` is
``LoRAGPTModule`` plus a ``FineTune:`` section naming the pretrain
checkpoint. The run restores the base (integrity-verified, registry-
sharded), fits only the adapter leaves under the masked optimizer, audits
the base bitwise frozen, and publishes the adapter-only artifact that
``tools/serve.py`` merges for quantized serving.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402

import numpy as np  # noqa: E402

from fleetx_tpu.core.engine import EagerEngine  # noqa: E402
from fleetx_tpu.data import build_dataloader  # noqa: E402
from fleetx_tpu.finetune import lora_optimizer  # noqa: E402
from fleetx_tpu.finetune.module import LoRAGPTModule  # noqa: E402
from fleetx_tpu.finetune.recipe import finetune  # noqa: E402
from fleetx_tpu.models import build_module  # noqa: E402
from fleetx_tpu.optims import build_lr_scheduler, build_optimizer  # noqa: E402
from fleetx_tpu.parallel.mesh import build_mesh, set_mesh  # noqa: E402
from fleetx_tpu.utils import config as config_mod  # noqa: E402
from fleetx_tpu.utils import env as env_mod  # noqa: E402
from fleetx_tpu.utils.log import logger  # noqa: E402


def _sample_batch(module: LoRAGPTModule) -> dict:
    """Synthetic 1-row batch for state init (shapes only — the restored
    base overwrites every value the init produced)."""
    s = int(module.model_cfg.max_position_embeddings)
    tok = np.zeros((1, s), np.int32)
    return {"tokens": tok, "position_ids": tok.copy()}


def main() -> int:
    """CLI entry: config → engine → the end-to-end fine-tune recipe."""
    args = config_mod.parse_args("fleetx_tpu lora finetune")
    env_mod.init_dist_env()
    env_mod.init_compile_cache()
    cfg = config_mod.get_config(args.config, args.override, show=True)

    mesh = set_mesh(build_mesh(cfg.get("Distributed")))
    module = build_module(cfg)
    assert isinstance(module, LoRAGPTModule), \
        "finetune.py requires Model.module: LoRAGPTModule"
    base_dir = module.base_ckpt
    assert base_dir, "FineTune.base_ckpt must name the pretrain " \
                     "checkpoint directory"

    opt_cfg = dict(cfg.get("Optimizer") or {})
    lr = build_lr_scheduler(opt_cfg.get("lr"))
    # the one optax mask: only adapter leaves update, the base pytree is
    # bitwise frozen (audited by the recipe after fit)
    optimizer = lora_optimizer(build_optimizer(opt_cfg, lr))
    engine = EagerEngine(cfg, module, optimizer=optimizer, lr_schedule=lr,
                         mesh=mesh)

    glb = cfg.get("Global", {})
    n_proc = jax.process_count()
    per_host_bs = int(glb.get("global_batch_size", 8)) // n_proc
    train_dl = build_dataloader(
        cfg.get("Data") or {}, "Train", num_replicas=n_proc,
        rank=jax.process_index(), batch_size=per_host_bs,
        seq_length=int(glb.get("max_seq_len", 1024)),
        vocab_size=int((cfg.get("Model") or {}).get("vocab_size") or 50304))

    adapter_dir = module.adapter_dir or \
        os.path.join(engine.output_dir, "adapter")
    losses, path = finetune(
        engine, train_dl, sample_batch=_sample_batch(module),
        base_dir=base_dir, adapter_dir=adapter_dir,
        epoch_num=int(cfg.get("Engine", {}).get("num_train_epochs", 1)))
    logger.info("fine-tune done: %d logged windows, adapter at %s",
                len(losses), path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
