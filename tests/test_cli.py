"""CLI entry points driven end-to-end in fresh subprocesses.

The unit suite exercises the library; these run the actual ``tools/``
commands a user types (the reference's runnable-recipe discipline,
SURVEY.md §4), scaled to seconds.
"""

import functools
import os
import re
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# batch/topology flags consistent with the 8-virtual-device harness
BATCH_FLAGS = [
    "-o", "Global.global_batch_size=16", "-o", "Global.local_batch_size=2",
    "-o", "Global.micro_batch_size=2", "-o", "Distributed.dp_degree=8",
]

# harness flags shared by every train smoke (overrides are last-wins, so
# tests append their own -o flags to specialize)
TINY_RUN = [
    "-o", "Engine.max_steps=2", "-o", "Engine.logging_freq=1",
    "-o", "Engine.eval_freq=0", "-o", "Engine.save_load.save_steps=0",
] + BATCH_FLAGS

# tiny GPT shape on top of the shared harness flags
GPT_SHAPES = [
    "-o", "Model.num_layers=2", "-o", "Model.hidden_size=64",
    "-o", "Model.num_attention_heads=4", "-o", "Model.vocab_size=512",
    "-o", "Model.dtype=float32", "-o", "Model.max_position_embeddings=64",
    "-o", "Global.max_seq_len=64",
]

TINY = TINY_RUN + GPT_SHAPES


def _run(args, timeout=420):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run([sys.executable] + args, cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)
    return proc


def _losses(text):
    return [float(m) for m in re.findall(r"loss: ([0-9.]+)", text)]


#: every script under tools/ that parses arguments (tools/auto.py wraps
#: train.py and tools/bench_losscurve.py takes none)
TOOLS = ("eval", "export", "finetune", "inference", "lint", "make_corpus",
         "metrics_report", "postmortem", "preprocess_data", "serve",
         "shardcheck", "slo_report", "supervise", "train", "verify_ckpt")


@pytest.mark.parametrize("tool", TOOLS)
def test_tool_answers_help(tool):
    """An entry point whose imports no longer resolve fails here and not on
    somebody's restart: ``--help`` runs the module's top-level imports and
    builds its parser in a fresh CPU process."""
    from fleetx_tpu.utils.hardware import clean_cpu_env

    proc = subprocess.run(
        [sys.executable, os.path.join("tools", f"{tool}.py"), "--help"],
        cwd=REPO, env=clean_cpu_env(REPO), capture_output=True, text=True,
        timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "usage:" in proc.stdout


def test_train_cli_gpt_synthetic():
    proc = _run(["tools/train.py", "-c",
                 "fleetx_tpu/configs/nlp/gpt/pretrain_gpt_345M_synthetic.yaml"]
                + TINY)
    assert proc.returncode == 0, proc.stderr[-2000:]
    losses = _losses(proc.stderr + proc.stdout)
    assert len(losses) >= 2, (proc.stdout, proc.stderr[-1000:])
    # first-step loss ≈ ln(512): tokens uniform over the model's vocab
    assert abs(losses[0] - 6.24) < 0.5, losses


def _planner_flags():
    """TINY minus the explicit dp override — an explicit degree would
    (correctly) bypass the mesh planner the auto tests exercise."""
    return [f for pair in zip(TINY[::2], TINY[1::2])
            for f in pair if "dp_degree" not in pair[1]]


def _cpu_mesh_env():
    return dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
                XLA_FLAGS="--xla_force_host_platform_device_count=8")


def test_auto_cli_plans_the_mesh():
    """tools/auto.py runs the mesh-degree planner (the reference auto
    stack's planning half) before batch derivation, then trains normally."""
    flags = _planner_flags()
    proc = _run(["tools/auto.py", "-c",
                 "fleetx_tpu/configs/nlp/gpt/auto/pretrain_gpt_345M_single_card.yaml",
                 "-o", "Data.Train.dataset.name=SyntheticGPTDataset"]
                + flags)
    text = proc.stdout + proc.stderr
    assert proc.returncode == 0, text[-2000:]
    assert "auto layout" in text, text[-1500:]
    losses = _losses(text)
    assert losses and abs(losses[0] - 6.24) < 0.5, losses


def test_train_cli_ernie_synthetic():
    proc = _run(["tools/train.py", "-c",
                 "fleetx_tpu/configs/nlp/ernie/pretrain_ernie_base.yaml",
                 "-o", "Data.Train.dataset.name=SyntheticErnieDataset"]
                + TINY)
    assert proc.returncode == 0, proc.stderr[-2000:]
    losses = _losses(proc.stderr + proc.stdout)
    # MLM ln(512) + NSP ln(2)
    assert losses and abs(losses[0] - 6.93) < 0.6, losses


def test_raw_corpus_to_training_end_to_end(tmp_path):
    """The full data story a reference user expects: raw jsonl corpus →
    tools/preprocess_data.py → memmap pair → tools/train.py consumes it
    through GPTDataset (real tokens, not the synthetic path)."""
    from fleetx_tpu.data.tokenizers.gpt_tokenizer import train_bpe

    tok_dir = tmp_path / "tok"
    texts = ["the quick brown fox jumps over the lazy dog",
             "pack my box with five dozen liquor jugs",
             "how vexingly quick daft zebras jump"] * 10
    train_bpe(texts, vocab_size=400).save_pretrained(str(tok_dir))

    corpus = tmp_path / "corpus.jsonl"
    with open(corpus, "w") as f:
        import json
        for t in texts:
            f.write(json.dumps({"text": t}) + "\n")

    prefix = str(tmp_path / "data" / "corpus")
    proc = _run(["tools/preprocess_data.py", "--input", str(corpus),
                 "--tokenizer", str(tok_dir), "--output-prefix", prefix,
                 "--workers", "2", "--append-eos", "--eos-id", "0",
                 "--log-interval", "0"])
    assert proc.returncode == 0, proc.stderr[-2000:]

    proc = _run(["tools/train.py", "-c",
                 "fleetx_tpu/configs/nlp/gpt/pretrain_gpt_345M_synthetic.yaml",
                 "-o", "Data.Train.dataset.name=GPTDataset",
                 "-o", f"Data.Train.dataset.input_dir={prefix}",
                 "-o", "Data.Train.dataset.num_samples=64",
                 "-o", "Data.Train.dataset.eos_id=0"] + TINY)
    assert proc.returncode == 0, proc.stderr[-2000:]
    losses = _losses(proc.stderr + proc.stdout)
    # real text is FAR from uniform over the 512-slot vocab: the first-step
    # loss still starts near ln(512) (untrained uniform predictions)
    assert len(losses) >= 2 and all(np.isfinite(losses)), losses
    assert abs(losses[0] - 6.24) < 0.8, losses


def test_train_cli_imagen_synthetic():
    proc = _run(["tools/train.py", "-c",
                 "fleetx_tpu/configs/multimodal/imagen/imagen_397M_text2im_64x64.yaml",
                 "-o", "Data.Train.dataset.name=SyntheticImagenDataset",
                 "-o", "Data.Train.dataset.num_samples=64",
                 "-o", "Data.Train.dataset.text_embed_dim=32",
                 "-o", "Model.text_embed_dim=32",
                 "-o", "Model.image_size=16",
                 "-o", "Data.Train.dataset.image_size=16",
                 "-o", "Model.dim=16", "-o", "Model.cond_dim=32",
                 "-o", "Model.dtype=float32"] + TINY_RUN)
    assert proc.returncode == 0, proc.stderr[-2000:]
    losses = _losses(proc.stderr + proc.stdout)
    # eps-prediction MSE on unit-normal noise starts near 1.0
    assert losses and 0.3 < losses[0] < 3.0, losses


def test_train_cli_vit_synthetic():
    proc = _run(["tools/train.py", "-c",
                 "fleetx_tpu/configs/vis/vit/ViT_base_patch16_224_pretrain.yaml",
                 "-o", "Data.Train.dataset.name=SyntheticVisionDataset",
                 "-o", "Data.Train.dataset.num_samples=64",
                 "-o", "Data.Train.dataset.image_size=32",
                 # the dataset must label within the model's class range —
                 # out-of-range labels one-hot to all-zeros and the loss
                 # silently collapses to the smoothing term
                 "-o", "Data.Train.dataset.num_classes=10",
                 "-o", "Model.image_size=32", "-o", "Model.num_classes=10",
                 "-o", "Model.model.image_size=32",
                 "-o", "Model.model.patch_size=8",
                 "-o", "Model.model.hidden_size=64",
                 "-o", "Model.model.num_layers=2",
                 "-o", "Model.model.num_attention_heads=4",
                 "-o", "Model.model.dtype=float32"] + TINY_RUN)
    assert proc.returncode == 0, proc.stderr[-2000:]
    losses = _losses(proc.stderr + proc.stdout)
    # untrained uniform over 10 classes: ln(10)
    assert losses and abs(losses[0] - 2.3) < 0.7, losses


def test_train_eval_generate_cli_round_trip(tmp_path):
    """The user journey across three CLIs: train (writes checkpoints) →
    offline eval (PPL from the checkpoint) → generation task (continuation
    from the checkpoint) — all on one tiny trained model."""
    from fleetx_tpu.data.tokenizers.gpt_tokenizer import train_bpe

    tok_dir = str(tmp_path / "tok")
    texts = ["the quick brown fox jumps over the lazy dog",
             "pack my box with five dozen liquor jugs"] * 10
    train_bpe(texts, vocab_size=400).save_pretrained(tok_dir)
    eval_path = tmp_path / "wiki.txt"
    eval_path.write_text(" ".join(texts[:6]) + "\n")

    out_dir = str(tmp_path / "output")
    proc = _run(["tools/train.py", "-c",
                 "fleetx_tpu/configs/nlp/gpt/pretrain_gpt_345M_synthetic.yaml"]
                + TINY_RUN + GPT_SHAPES
                + ["-o", "Engine.save_load.save_steps=2",
                   "-o", f"Engine.save_load.output_dir={out_dir}"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    # the save path must have produced a checkpoint — eval/generation fall
    # back to random weights with a warning, which would mask a regression
    assert os.path.isdir(out_dir) and os.listdir(out_dir), out_dir

    proc = _run(["tools/eval.py", "-c",
                 "fleetx_tpu/configs/nlp/gpt/eval_gpt_345M_single_card.yaml",
                 "-o", f"Offline_Eval.tokenizer_dir={tok_dir}",
                 "-o", f"Offline_Eval.eval_path={eval_path}",
                 "-o", "Offline_Eval.batch_size=2"] + TINY_RUN + GPT_SHAPES
                + ["-o", f"Engine.save_load.ckpt_dir={out_dir}"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    text = proc.stdout + proc.stderr
    assert "ppl" in text.lower(), text[-800:]
    assert "NO CHECKPOINT" not in text, text[-800:]

    # LAMBADA accuracy mode over the same checkpoint (eval_type=acc)
    lamb = tmp_path / "lambada.jsonl"
    with open(lamb, "w") as f:
        import json
        for t in texts[:4]:
            f.write(json.dumps({"text": t}) + "\n")
    proc = _run(["tools/eval.py", "-c",
                 "fleetx_tpu/configs/nlp/gpt/eval_gpt_345M_single_card.yaml",
                 "-o", "Offline_Eval.eval_type=acc",
                 "-o", f"Offline_Eval.tokenizer_dir={tok_dir}",
                 "-o", f"Offline_Eval.eval_path={lamb}",
                 "-o", "Offline_Eval.batch_size=2"] + TINY_RUN + GPT_SHAPES
                + ["-o", f"Engine.save_load.ckpt_dir={out_dir}"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    text = proc.stdout + proc.stderr
    # the results dict printed by _offline_eval, not the config echo
    assert "'acc':" in text, text[-800:]
    assert "NO CHECKPOINT" not in text, text[-800:]

    proc = _run(["tasks/gpt/generation.py", "-c",
                 "fleetx_tpu/configs/nlp/gpt/generation_gpt_345M_single_card.yaml",
                 "-o", f"Generation.tokenizer_dir={tok_dir}",
                 "-o", "Generation.input_text=the quick brown",
                 "-o", "Generation.max_dec_len=8"] + TINY_RUN + GPT_SHAPES
                + ["-o", f"Engine.save_load.ckpt_dir={out_dir}"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "no checkpoint" not in (proc.stdout + proc.stderr), \
        (proc.stdout + proc.stderr)[-800:]

    # diverse beam search through the same generation CLI + checkpoint
    proc = _run(["tasks/gpt/generation.py", "-c",
                 "fleetx_tpu/configs/nlp/gpt/generation_gpt_345M_single_card.yaml",
                 "-o", "Generation.decode_strategy=beam_search",
                 "-o", "Generation.num_beams=4",
                 "-o", "Generation.num_beam_groups=2",
                 "-o", "Generation.diversity_rate=0.5",
                 "-o", f"Generation.tokenizer_dir={tok_dir}",
                 "-o", "Generation.input_text=the quick brown",
                 "-o", "Generation.max_dec_len=8"] + TINY_RUN + GPT_SHAPES
                + ["-o", f"Engine.save_load.ckpt_dir={out_dir}"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "no checkpoint" not in (proc.stdout + proc.stderr), \
        (proc.stdout + proc.stderr)[-800:]


def test_generation_cli_dp8_yaml():
    """The dp8 generation recipe parses and decodes on the 8-device env
    (tokens-in → ids-out path; random weights are fine for a smoke — the
    checkpointed journey is covered by the round-trip test). The recipe's
    OWN batch/degree settings stay in force — only the model is shrunk —
    so a corrupted shipped recipe fails here."""
    proc = _run(["tasks/gpt/generation.py", "-c",
                 "fleetx_tpu/configs/nlp/gpt/generation_gpt_345M_dp8.yaml",
                 "-o", "Generation.tokenizer_dir=",  # ids-in/ids-out smoke
                 "-o", "Generation.input_text=5 9 23",
                 "-o", "Generation.max_dec_len=4"] + GPT_SHAPES)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "[[" in proc.stdout, proc.stdout[-500:]  # printed id rows


def test_supervisor_restarts_after_crash(tmp_path):
    """Restart wrapper e2e (VERDICT r3 #8; reference ``max_restart: 3``,
    ``docs/quick_start.md:141``): training is killed mid-run by fault
    injection, the supervisor restarts it, the retry resumes from the last
    checkpoint and completes — one command, zero operator involvement."""
    out_dir = str(tmp_path / "output")
    env = dict(_cpu_mesh_env(), FLEETX_FAULT_STEP="3")
    cmd = [sys.executable, "tools/supervise.py", "--max-restart", "2",
           "--backoff", "0", "--",
           sys.executable, "tools/train.py", "-c",
           "fleetx_tpu/configs/nlp/gpt/pretrain_gpt_345M_synthetic.yaml",
           "-o", "Engine.max_steps=6", "-o", "Engine.logging_freq=1",
           "-o", "Engine.eval_freq=0", "-o", "Engine.save_load.save_steps=2",
           "-o", f"Engine.save_load.output_dir={out_dir}",
           "-o", f"Engine.save_load.ckpt_dir={out_dir}"] \
        + BATCH_FLAGS + GPT_SHAPES
    proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=600)
    text = proc.stdout + proc.stderr
    assert proc.returncode == 0, text[-3000:]
    assert "fault injection: dying at step 3" in text, text[-2000:]
    assert "[supervise] restart 1/2" in text, text[-2000:]
    # the retry resumed (step > 0 checkpoint found) and finished all 6 steps
    from fleetx_tpu.core import checkpoint as ckpt_lib
    assert ckpt_lib.latest_step(out_dir) == 6, os.listdir(out_dir)


def test_launch_scripts_reference_existing_configs():
    """Every projects/ recipe is executable and points at a config that
    exists (the reference's runnable-recipe discipline; catches the parity
    tail added for VERDICT r4 #9 drifting from the config zoo)."""
    import glob
    import stat

    scripts = sorted(glob.glob(os.path.join(REPO, "projects", "*", "*.sh")))
    assert len(scripts) >= 20, scripts  # 13 gpt + 5 imagen + ernie + vit
    for path in scripts:
        assert os.stat(path).st_mode & stat.S_IXUSR, f"not executable: {path}"
        with open(path) as f:
            body = f.read()
        cfgs = re.findall(r"-c (\S+\.yaml)", body)
        assert cfgs, f"no config reference in {path}"
        for cfg in cfgs:
            assert os.path.exists(os.path.join(REPO, cfg)), (path, cfg)


def test_launch_script_smoke_auto_gpt():
    """bash projects/gpt/auto_gpt_345M_single_card.sh end-to-end (tiny
    overrides pass through the script's "$@"): supervisor → tools/auto.py →
    planner → training steps (VERDICT r4 #9 smoke requirement)."""
    proc = subprocess.run(
        ["bash", os.path.join(REPO, "projects", "gpt",
                              "auto_gpt_345M_single_card.sh"),
         "-o", "Data.Train.dataset.name=SyntheticGPTDataset"]
        + _planner_flags(),
        cwd=REPO, env=_cpu_mesh_env(), capture_output=True, text=True,
        timeout=600)
    text = proc.stdout + proc.stderr
    assert proc.returncode == 0, text[-2000:]
    assert "auto layout" in text, text[-1500:]
    assert _losses(text), text[-1500:]


@functools.lru_cache(maxsize=1)
def _flax_allows_modules_in_scan() -> bool:
    """The imagen sampler constructs flax submodules inside a
    ``jax.lax.scan`` body (models/imagen/modeling.py ``sample``); this
    flax/jax pairing refuses that with a JaxTransformError at module
    construction. Probe the exact shape so the skip tracks the feature,
    not a version number. Cached — the probe is a real jax trace and is
    consulted at collection time here AND in test_imagen.py."""
    import flax
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    class _Inner(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(4, name="d")(x)

    class _Outer(nn.Module):
        @nn.compact
        def __call__(self, x):
            inner = _Inner(name="inner")
            x = inner(x)

            def step(c, _):
                return inner(c), None

            y, _ = jax.lax.scan(step, x, None, length=2)
            return y

    try:
        m = _Outer()
        v = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 4)))
        m.apply(v, jnp.zeros((1, 4)))
        return True
    except flax.errors.JaxTransformError:
        return False


@pytest.mark.skipif(
    not _flax_allows_modules_in_scan(),
    reason="this flax/jax build refuses module construction inside "
           "jax.lax.scan (the imagen sampler's denoise loop)")
def test_imagen_generate_cli(tmp_path):
    """tasks/imagen/generate.py samples the cascade (tiny shapes, few
    denoise steps) and writes the image tensor."""
    out = str(tmp_path / "samples.npy")
    proc = _run(["tasks/imagen/generate.py", "-c",
                 "fleetx_tpu/configs/multimodal/imagen/imagen_397M_text2im_64x64.yaml",
                 "-o", "Model.image_size=16", "-o", "Model.dim=16",
                 "-o", "Model.cond_dim=32", "-o", "Model.text_embed_dim=32",
                 "-o", "Model.timesteps=8", "-o", "Model.dtype=float32",
                 "-o", "Generation.batch_size=2",
                 "-o", f"Generation.output_path={out}",
                 # the sampler ignores the train harness; BATCH_FLAGS only
                 # satisfy config validation against the 8-device test env
                 ] + BATCH_FLAGS,
                timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    arr = np.load(out)
    assert arr.shape == (2, 16, 16, 3), arr.shape
    assert np.isfinite(arr).all()
