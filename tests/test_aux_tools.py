"""Coverage for the small aux surfaces: real vision datasets, the parallel
shell runner, and the AutoEngine alias — pieces the reference ships but
never tests (SURVEY.md §4)."""

import os
import pickle

import numpy as np
import pytest
from PIL import Image

from fleetx_tpu.data.dataset.vision_dataset import CIFAR10, GeneralClsDataset
from fleetx_tpu.tools.multiprocess_tool import run_commands


def _write_pngs(root, n=4, size=40):
    rng = np.random.RandomState(0)
    lines = []
    os.makedirs(os.path.join(root, "imgs"), exist_ok=True)
    for i in range(n):
        rel = f"imgs/{i}.png"
        Image.fromarray((rng.rand(size, size, 3) * 255).astype(np.uint8)
                        ).save(os.path.join(root, rel))
        lines.append(f"{rel} {i % 2}")
    list_path = os.path.join(root, "train_list.txt")
    with open(list_path, "w") as f:
        f.write("\n".join(lines) + "\n")
    return list_path


def test_general_cls_dataset_reads_list_file(tmp_path):
    root = str(tmp_path)
    list_path = _write_pngs(root)
    ds = GeneralClsDataset(root, list_path, transform_ops=[
        {"DecodeImage": {}}, {"ResizeImage": {"resize_short": 36}},
        {"CenterCropImage": {"size": 32}}, {"NormalizeImage": {}}])
    assert len(ds) == 4
    s = ds[1]
    assert s["images"].shape == (32, 32, 3)
    assert s["images"].dtype == np.float32
    assert int(s["labels"]) == 1


def test_cifar10_pickle_batches(tmp_path):
    rng = np.random.RandomState(0)
    for name in [f"data_batch_{i}" for i in range(1, 6)] + ["test_batch"]:
        batch = {b"data": (rng.rand(5, 3072) * 255).astype(np.uint8),
                 b"labels": list(rng.randint(0, 10, 5))}
        with open(tmp_path / name, "wb") as f:
            pickle.dump(batch, f)
    train = CIFAR10(str(tmp_path), mode="train")
    test = CIFAR10(str(tmp_path), mode="test")
    assert len(train) == 25 and len(test) == 5
    s = train[0]
    assert s["images"].shape == (32, 32, 3)
    assert 0.0 <= s["images"].max() <= 1.0


def test_run_commands_parallel_and_exit_codes():
    codes = run_commands(["true", "false", "echo hi"], num_workers=2)
    assert codes == [0, 1, 0]


def test_auto_engine_is_the_gspmd_engine():
    """AutoEngine must be the same engine (the auto stack is subsumed by
    GSPMD compilation — reference auto_engine.py:36-133 design note)."""
    from fleetx_tpu.core.engine.auto_engine import AutoEngine
    from fleetx_tpu.core.engine.basic_engine import BasicEngine
    from fleetx_tpu.core.engine.eager_engine import EagerEngine

    assert issubclass(AutoEngine, EagerEngine)
    assert issubclass(EagerEngine, BasicEngine)
    # the BasicEngine protocol surface the reference declares
    for name in ("fit", "evaluate", "predict", "save", "load"):
        assert callable(getattr(AutoEngine, name, None)), name


def test_auto_layout_planner():
    """The auto stack's planning half (reference auto_utils.py:24-108 builds
    a mesh from USER degrees; here the degrees themselves are chosen):
    canonical model scales must land on sane layouts whose product equals
    the device count."""
    from fleetx_tpu.parallel.auto_layout import estimate_params, suggest_layout

    gpt345m = dict(hidden_size=1024, num_layers=24, num_attention_heads=16,
                   ffn_hidden_size=4096, vocab_size=50304,
                   max_position_embeddings=1024)
    gpt67b = dict(hidden_size=4096, num_layers=32, num_attention_heads=32,
                  ffn_hidden_size=16384, vocab_size=50304,
                  max_position_embeddings=1024)
    gpt175b = dict(hidden_size=12288, num_layers=96, num_attention_heads=96,
                   ffn_hidden_size=49152, vocab_size=50304,
                   max_position_embeddings=1024)

    assert 0.3e9 < estimate_params(gpt345m) < 0.42e9
    assert 6.0e9 < estimate_params(gpt67b) < 7.4e9
    assert 1.6e11 < estimate_params(gpt175b) < 1.9e11

    def product(d):
        return (d["dp_degree"] * d["fsdp_degree"] * d["mp_degree"]
                * d["pp_degree"] * d["seq_degree"])

    # small model: pure data parallel
    d = suggest_layout(gpt345m, 8)
    assert d["dp_degree"] == 8 and product(d) == 8

    # 6.7B on 16 devices: ZeRO sharding, no mp/pp needed. The planner
    # escalates to stage 3: stage 2 shards moments+grads
    # (parallel/sharding.zero_grad_specs, docs/zero_sharding.md) but keeps
    # the f32 params + bf16 copy replicated, and 6 B/param × 6.7B = 40GB
    # can never fit a 32GB chip replicated
    d = suggest_layout(gpt67b, 16, hbm_gb=32)
    assert d["fsdp_degree"] >= 8 and d["mp_degree"] == 1 and product(d) == 16
    assert d["sharding"]["sharding_stage"] == 3

    # 175B on 128 devices: megatron-style tensor-inside, pipeline-across —
    # the reference's own mp8 x pp16 recipe shape
    d = suggest_layout(gpt175b, 128, hbm_gb=32)
    assert d["mp_degree"] == 8 and d["pp_degree"] == 16 and product(d) == 128

    # long-context: a seq axis is reserved for ring attention
    long8k = dict(gpt345m, max_position_embeddings=8192)
    d = suggest_layout(long8k, 8)
    assert d["seq_degree"] >= 2 and product(d) == 8

    # non-power-of-two device counts: axis growth must stop at divisors
    # (fsdp runs to 8, dp takes the 3 — not a ValueError at 16)
    d = suggest_layout(gpt67b, 24)
    assert product(d) == 24 and d["fsdp_degree"] == 8 and d["dp_degree"] == 3


def test_auto_layout_flows_through_get_config(tmp_path):
    """tools/auto.py path: Distributed.auto_layout triggers the planner
    inside get_config BEFORE batch-degree derivation, and explicit degrees
    win over the planner."""
    from fleetx_tpu.utils.config import get_config

    yaml_path = tmp_path / "auto.yaml"
    yaml_path.write_text(
        "Global:\n  global_batch_size: 16\n  micro_batch_size: 2\n"
        "Model:\n  module: GPTModule\n  hidden_size: 1024\n  num_layers: 24\n"
        "  num_attention_heads: 16\n  vocab_size: 50304\n"
        "  max_position_embeddings: 1024\n"
        "Distributed:\n  auto_layout: true\n")
    cfg = get_config(str(yaml_path), num_devices=8)
    dist = cfg["Distributed"]
    assert "auto_layout" not in dist
    assert int(dist["dp_degree"]) == 8          # 345M -> all-dp
    # batch math derived AFTER planning: data world = dp x fsdp = 8
    assert int(cfg["Global"]["local_batch_size"]) == 2

    yaml_path.write_text(
        "Global:\n  global_batch_size: 16\n  micro_batch_size: 2\n"
        "Model:\n  module: GPTModule\n  hidden_size: 1024\n  num_layers: 24\n"
        "  num_attention_heads: 16\n  vocab_size: 50304\n"
        "  max_position_embeddings: 1024\n"
        "Distributed:\n  auto_layout: true\n  mp_degree: 2\n")
    cfg = get_config(str(yaml_path), num_devices=8)
    assert int(cfg["Distributed"]["mp_degree"]) == 2  # explicit degree kept


def test_image_folder_directory_tree(tmp_path):
    rng = np.random.RandomState(1)
    for cls in ("cat", "dog"):
        os.makedirs(tmp_path / cls / "sub", exist_ok=True)
        for i in range(2):
            Image.fromarray((rng.rand(36, 36, 3) * 255).astype(np.uint8)
                            ).save(tmp_path / cls / "sub" / f"{i}.png")
        (tmp_path / cls / "notes.txt").write_text("not an image")

    from fleetx_tpu.data.dataset.vision_dataset import ImageFolder
    ds = ImageFolder(str(tmp_path), transform_ops=[
        {"DecodeImage": {}}, {"ResizeImage": {"resize_short": 36}},
        {"CenterCropImage": {"size": 32}}, {"NormalizeImage": {}}])
    assert ds.classes == ["cat", "dog"]
    assert len(ds) == 4  # the .txt files are skipped
    labels = sorted(int(ds[i]["labels"]) for i in range(len(ds)))
    assert labels == [0, 0, 1, 1]
    assert ds[0]["images"].shape == (32, 32, 3)


def test_cached_path_local_and_cache_hit(tmp_path, monkeypatch):
    """download cache: local paths pass through; cached URLs resolve without
    a network fetch; missing local files fail loudly."""
    import pytest

    from fleetx_tpu.utils import download as D

    monkeypatch.setenv("FLEETX_CACHE", str(tmp_path / "cache"))
    # local path passthrough
    f = tmp_path / "vocab.json"
    f.write_text("{}")
    assert D.cached_path(str(f)) == str(f)
    assert D.cached_path(f"file://{f}") == str(f)
    with pytest.raises(FileNotFoundError):
        D.cached_path(str(tmp_path / "missing.txt"))

    # a pre-populated cache entry is returned without any network access
    import hashlib
    url = "https://example.invalid/models/merges.txt"
    key = hashlib.md5(url.encode()).hexdigest()[:8]
    target_dir = tmp_path / "cache" / "tok"
    os.makedirs(target_dir)
    (target_dir / f"{key}_merges.txt").write_text("cached")
    got = D.cached_path(url, sub_dir="tok")
    with open(got) as fh:
        assert fh.read() == "cached"


def test_startup_checks(monkeypatch):
    import jax

    from fleetx_tpu.utils import check as C

    C.check_devices()  # cpu backend acceptable when not expecting tpu
    C.check_config({"Global": {"seed": 1}, "Model": {}})
    # a TPU config on the CPU runs only because conftest asked for the CPU
    # by name ...
    C.check_config({"Global": {"device": "tpu"}})
    # ... and is refused when nobody did: no quiet landing on the CPU
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
        C.check_config({"Global": {"device": "tpu"}})
    # a backend that does not initialise raises, it is not a failed check
    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", no_backend)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        C.check_devices()


def test_step_hbm_estimate_matches_onchip_anchors():
    """The planner's memory model against the 15.75GB v5-lite chip
    (VERDICT r4 weak #6 — a fits() nothing validates). GPT-345M seq1024
    dots-remat at bs8 with the full-logits head runs (`hbm_peak_gb` 14.17:
    ledger, PR 30); the other three boundaries — bs16 full-logits does
    not fit, bs16 with a chunked head does, bs32 chunked does not — are
    the model's own statements, not measured on the chip (ROADMAP S11)."""
    from fleetx_tpu.parallel.auto_layout import estimate_step_hbm_bytes

    chip = 15.75 * (1 << 30)
    gpt345m = dict(hidden_size=1024, num_layers=24, num_attention_heads=16,
                   ffn_hidden_size=4096, vocab_size=50304,
                   max_position_embeddings=1024)
    chunked = dict(gpt345m, vocab_chunk=16768)

    assert estimate_step_hbm_bytes(gpt345m, 8, "dots") <= chip
    assert estimate_step_hbm_bytes(gpt345m, 16, "dots") > chip
    assert estimate_step_hbm_bytes(chunked, 16, "dots") <= chip
    assert estimate_step_hbm_bytes(chunked, 32, "dots") > chip
    # granularity ordering: none > core_attn/dots > full
    mb = 8
    assert estimate_step_hbm_bytes(gpt345m, mb, "none") > \
        estimate_step_hbm_bytes(gpt345m, mb, "dots") > \
        estimate_step_hbm_bytes(gpt345m, mb, "full")


def test_auto_layout_accounts_for_activations():
    """A batch too big for pure-dp must change the plan (activations now
    count): GPT-345M at micro_batch 64 no longer fits a 16GB chip
    unsharded, so the planner must either shard an activation axis or
    warn — it must NOT silently return the state-only dp layout as fine."""
    from fleetx_tpu.parallel.auto_layout import (estimate_step_hbm_bytes,
                                                 suggest_layout)

    gpt345m = dict(hidden_size=1024, num_layers=24, num_attention_heads=16,
                   ffn_hidden_size=4096, vocab_size=50304,
                   max_position_embeddings=1024)
    # the huge-batch estimate itself must blow the budget
    assert estimate_step_hbm_bytes(gpt345m, 64, "dots") > 16 * (1 << 30)
    d64 = suggest_layout(gpt345m, 8, micro_batch=64, recompute="dots")
    d1 = suggest_layout(gpt345m, 8, micro_batch=1, recompute="dots")
    assert d1["dp_degree"] == 8  # small-batch behavior unchanged
    # at mb64 the binding term is ACTIVATIONS, which fsdp does not shard:
    # the planner must grow tensor/pipeline degrees, not burn the device
    # budget on fsdp (review round-5 finding)
    assert d64["mp_degree"] * d64["pp_degree"] >= 4, d64
