"""Imagen images/sec benchmark child — the one model family never timed.

Reference recipe: 397M base64 text→image stage, bs16/card
(``/root/reference/ppfleetx/configs/multimodal/imagen/
imagen_397M_text2im_64x64.yaml``). Trains the base stage on synthetic
NHWC images + T5-width text embeds, same harness shape as
``tools/bench_vit.py``.

Prints exactly ONE JSON line. Run as a fresh process:

    python tools/bench_imagen.py                  # 397M base64, bs from env
    FLEETX_IMAGEN_BS=32 python tools/bench_imagen.py
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    import jax

    bsz = int(os.environ.get("FLEETX_IMAGEN_BS", 16))

    dev = jax.devices()[0]
    platform = dev.platform
    scaled = platform == "cpu"
    model = dict(preset="base64", dim=128, image_size=64,
                 text_embed_dim=1024, cond_dim=512, timesteps=1000,
                 schedule="cosine", pred_type="eps", cond_drop_prob=0.1,
                 dtype="bfloat16", param_dtype="float32")
    if scaled:  # runnable cpu fallback for harness self-tests
        bsz = 2
        model.update(dim=16, image_size=16, text_embed_dim=32, cond_dim=32,
                     dtype="float32")
    warmup, n_steps = (1, 2) if scaled else (3, 10)

    from _bench_harness import time_engine_steps
    from fleetx_tpu.core.engine import EagerEngine
    from fleetx_tpu.models.imagen.module import ImagenModule
    from fleetx_tpu.optims.lr_scheduler import build_lr_scheduler
    from fleetx_tpu.optims.optimizer import build_optimizer

    cfg = {
        "Model": model,
        "Engine": {"max_steps": 10_000, "logging_freq": 100},
        "Global": {"seed": 0, "prng_impl": "rbg"},
    }
    module = ImagenModule(cfg)
    lr = build_lr_scheduler({"max_lr": 1e-4, "warmup_steps": 100,
                             "decay_steps": 1000})
    opt = build_optimizer({"name": "AdamW", "weight_decay": 0.01,
                           "grad_clip": {"clip_norm": 1.0}}, lr)
    engine = EagerEngine(cfg, module, optimizer=opt, lr_schedule=lr)

    size = int(model["image_size"])
    rng = np.random.RandomState(0)
    batch = {
        "images": rng.uniform(-1, 1, (bsz, size, size, 3)).astype(np.float32),
        "text_embeds": rng.randn(bsz, 16, model["text_embed_dim"]
                                 ).astype(np.float32),
        "text_mask": np.ones((bsz, 16), np.int32),
    }

    dt, loss, n_params = time_engine_steps(engine, batch, warmup, n_steps)

    print(json.dumps({
        "metric": f"imagen_base64_train_images_per_s_{platform}",
        "value": round(bsz / dt, 1),
        "unit": "images/s",
        "step_time_s": round(dt, 4),
        "batch_size": bsz,
        "loss": round(loss, 4),
        "n_params": int(n_params),
        "device_kind": getattr(dev, "device_kind", platform),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
