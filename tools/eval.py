"""Offline evaluation entry point (reference ``tools/eval.py:106-126``)."""

import sys
import os

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax

from fleetx_tpu.core.engine import EagerEngine
from fleetx_tpu.data import build_dataloader
from fleetx_tpu.models import build_module
from fleetx_tpu.optims import build_lr_scheduler, build_optimizer
from fleetx_tpu.parallel.mesh import build_mesh, set_mesh
from fleetx_tpu.utils import config as config_mod
from fleetx_tpu.utils import env as env_mod


def _offline_eval(cfg, module):
    """WikiText PPL / LAMBADA accuracy path (reference ``tools/eval.py`` with
    ``GPTEvalModule``; datasets from ``Offline_Eval`` section)."""
    from fleetx_tpu.core.checkpoint import latest_step, load_params
    from fleetx_tpu.data.dataloader import DataLoader
    from fleetx_tpu.data.dataset import eval_dataset as ev
    from fleetx_tpu.data.sampler.batch_sampler import DistributedBatchSampler
    from fleetx_tpu.data.tokenizers.gpt_tokenizer import GPTTokenizer
    from fleetx_tpu.utils.log import logger

    section = dict(cfg.get("Offline_Eval") or {})
    seq = int(cfg.get("Global", {}).get("max_seq_len", 1024))
    tok_dir = section.get("tokenizer_dir")
    if not tok_dir:
        raise ValueError(
            "Offline_Eval.tokenizer_dir is required (a directory with "
            "vocab.json + merges.txt) — eval datasets tokenize raw text")
    tokenizer = GPTTokenizer.from_pretrained(tok_dir)
    if section.get("eval_type", "ppl") == "acc":
        ds = ev.lambada_from_jsonl(section["eval_path"], tokenizer, seq)
    else:
        ds = ev.lm_eval_from_text(section["eval_path"], tokenizer, seq,
                                  int(section.get("overlapping_eval", 32)))
    bs = int(section.get("batch_size", 8))
    loader = DataLoader(ds, DistributedBatchSampler(
        len(ds), bs, num_replicas=1, rank=0, drop_last=False))

    ckpt_dir = cfg.get("Engine", {}).get("save_load", {}).get("ckpt_dir")
    if ckpt_dir and latest_step(ckpt_dir) is not None:
        params = load_params(ckpt_dir)
    else:
        logger.warning(
            "NO CHECKPOINT FOUND (ckpt_dir=%r) — evaluating RANDOMLY "
            "INITIALIZED weights; the numbers below are meaningless for any "
            "trained model", ckpt_dir)
        rng = jax.random.PRNGKey(int(cfg.get("Global", {}).get("seed", 0)))
        params = module.init_variables(rng, {
            "tokens": jax.numpy.zeros((1, seq), jax.numpy.int32),
            "position_ids": jax.numpy.zeros((1, seq), jax.numpy.int32)})
    results = module.run_offline_eval(params, loader)
    print({k: round(float(v), 6) for k, v in results.items()})


def main():
    args = config_mod.parse_args("fleetx_tpu eval")
    env_mod.init_dist_env()
    env_mod.init_compile_cache()
    cfg = config_mod.get_config(args.config, args.override, show=True)

    mesh = set_mesh(build_mesh(cfg.get("Distributed")))
    module = build_module(cfg)

    if cfg.get("Offline_Eval"):
        _offline_eval(cfg, module)
        return

    engine = EagerEngine(cfg, module, mesh=mesh, mode="eval")
    n_proc = jax.process_count()
    eval_dl = build_dataloader(cfg.get("Data") or {}, "Eval",
                               num_replicas=n_proc, rank=jax.process_index())
    first = next(iter(eval_dl))
    engine.prepare(first)
    loss = engine.evaluate(eval_dl)
    print(f"eval loss: {loss:.6f}")


if __name__ == "__main__":
    main()
