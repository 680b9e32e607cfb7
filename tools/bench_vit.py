"""ViT images/sec benchmark child — BASELINE.json north-star metric #2.

Reference recipe: ViT-B/16 224px ImageNet pretrain, fp16 O2, 256 images per
card (``/root/reference/ppfleetx/configs/vis/vit/
ViT_base_patch16_224_pt_in1k_2n16c_dp_fp16o2.yaml:84-88``). VERDICT r4 asks
for ViT-L/16 (fall back to ViT-B if HBM-bound) bf16 images/sec + MFU.

Prints exactly ONE JSON line. Run as a fresh process:

    python tools/bench_vit.py                      # ViT-L/16, bs from env
    FLEETX_VIT_NAME=ViT_base_patch16_224 python tools/bench_vit.py
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    import jax

    name = os.environ.get("FLEETX_VIT_NAME", "ViT_large_patch16_224")
    bsz = int(os.environ.get("FLEETX_VIT_BS", 128))

    dev = jax.devices()[0]
    platform = dev.platform
    scaled = platform == "cpu"
    if scaled:  # keep a runnable cpu fallback for harness self-tests
        name, bsz = "ViT_tiny_patch16_224", 8
    warmup, n_steps = (1, 2) if scaled else (3, 10)

    from _bench_harness import time_engine_steps
    from fleetx_tpu.core.engine import EagerEngine
    from fleetx_tpu.models.vision.module import GeneralClsModule
    from fleetx_tpu.optims.lr_scheduler import build_lr_scheduler
    from fleetx_tpu.optims.optimizer import build_optimizer

    cfg = {
        "Model": dict(name=name, num_classes=1000,
                      drop_path_rate=0.1,
                      use_recompute=not scaled,
                      loss={"epsilon": 0.0001}),
        "Engine": {"max_steps": 10_000, "logging_freq": 100},
        "Global": {"seed": 0, "prng_impl": "rbg"},
    }
    module = GeneralClsModule(cfg)
    lr = build_lr_scheduler({"max_lr": 3e-3, "warmup_steps": 100,
                             "decay_steps": 1000})
    opt = build_optimizer({"name": "AdamW", "weight_decay": 0.3}, lr)
    engine = EagerEngine(cfg, module, optimizer=opt, lr_schedule=lr)

    size = module.vit_cfg.image_size
    rng = np.random.RandomState(0)
    batch = {
        "images": rng.randn(bsz, size, size, 3).astype(np.float32),
        "labels": rng.randint(0, 1000, size=(bsz,)).astype(np.int32),
    }

    dt, loss, n_params = time_engine_steps(engine, batch, warmup, n_steps)

    images_per_s = bsz / dt
    result = {
        "metric": f"{name.lower()}_train_images_per_s_{platform}",
        "value": round(images_per_s, 1),
        "unit": "images/s",
        "step_time_s": round(dt, 4),
        "batch_size": bsz,
        "loss": round(loss, 3),
        "n_params": int(n_params),
        "device_kind": getattr(dev, "device_kind", platform),
    }
    from fleetx_tpu.utils.hardware import gpt_flops_per_token, peak_flops

    peak = peak_flops(dev)
    if peak:
        # per-token transformer FLOPs formula applies to the encoder too;
        # tokens per image = patches + cls
        vc = module.vit_cfg
        tokens = vc.num_patches + 1
        flops = gpt_flops_per_token(vc.num_layers, vc.hidden_size, tokens,
                                    num_params=n_params) * tokens * bsz
        result["mfu"] = round(flops / dt / (peak * jax.device_count()), 4)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
