"""Toy widths for rehearsing the harness on the CPU: a temporary root with
its own ``BENCHMARK.json``, a copy of ``benchmarks/`` (data files only
matter) and toy configuration and traffic files. Tests only."""

from __future__ import annotations

import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TOY_MODEL = ["Model.vocab_size=512", "Model.hidden_size=128",
             "Model.num_layers=2", "Model.num_attention_heads=2",
             "Model.max_position_embeddings=128", "Global.max_seq_len=128"]


def toy_config() -> dict:
    with open(os.path.join(ROOT, "benchmarks/configs/gpt-345m.json")) as f:
        cfg = json.load(f)
    cfg.update(name="toy", vocab_size=512, hidden_size=128, num_layers=2,
               num_attention_heads=2, head_dim=64, ffn_hidden_size=512,
               max_position_embeddings=128)
    cfg["train"]["overrides"] += TOY_MODEL + [
        "Global.local_batch_size=2", "Global.micro_batch_size=2"]
    cfg["serve"]["overrides"] = TOY_MODEL + [
        "Serving.max_batch=4", "Serving.page_size=16",
        "Serving.num_pages=65", "Serving.max_seq_len=128",
        "Serving.prefill_chunk=32", "Serving.max_queue=0"]
    cfg["check"] = {
        # toy readings (float32 reference on the CPU): the program and the
        # reference in bfloat16 give loss 2e-5, gradient 6e-4, change 3e-3;
        # the reference in float8 gives 2e-4, 3e-3 .. 8e-3, 1.1e-2
        "train": {"loss_rel_gap": 6e-5, "grad_norm_worst_leaf_gap": 1.5e-3,
                  "delta_norm_worst_leaf_gap": 6e-3},
        "serve": {"served_logit_widest_gap": 0.006}}
    return cfg


TOY_TRAFFIC = {
    "toy-train": {"kind": "train_steps", "sequences_per_chip": 2,
                  "seq_len": 128, "check_steps": 3, "warmup_steps": 1,
                  "reference_rows_per_block": 1, "trace_seconds": 0.5},
    "toy-closed": {"kind": "closed_loop", "clients": 4,
                   "prompt_lengths": [24, 40, 56],
                   "output_lengths": [4, 6, 8, 10], "stationary_start": True,
                   "trace_seconds": 0.5,
                   "check": {"requests": 3, "pad_to": 128}},
    # ``pad_to`` is longest prompt + longest output, as in the shipped
    # mixes: a first-round request, lengthened by the fill's chunks behind
    # it, can pass it (99 tokens for seed 3000000024)
    "toy-closed-tight": {"kind": "closed_loop", "clients": 4,
                         "prompt_lengths": [24, 56, 88],
                         "output_lengths": [4, 6, 8, 10],
                         "stationary_start": True, "trace_seconds": 0.5,
                         "check": {"requests": 3, "pad_to": 98}},
    "toy-open": {"kind": "open_loop", "rate_rps": 6.0,
                 "prompt_lengths": [24, 40], "output_lengths": [3, 5],
                 "trace_seconds": 0.5, "check": {"requests": 3,
                                                 "pad_to": 128}},
}


def make_root(tmp: str, chips: int = 1) -> str:
    """A rehearsal root under ``tmp`` (``chips`` for the train cell);
    returns its path."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(tmp, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(tmp, "benchmarks/configs/toy.json"), "w") as f:
        json.dump(toy_config(), f)
    for name, mix in TOY_TRAFFIC.items():
        with open(os.path.join(tmp, f"benchmarks/traffic/{name}.json"),
                  "w") as f:
            json.dump(mix, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    serve = [name for name in TOY_TRAFFIC if name != "toy-train"]
    bench = dict(real)
    bench["configs"] = [{"name": "toy", "source": "tests", "reduced": [],
                         "file": "benchmarks/configs/toy.json",
                         "why": "toy widths for the CPU rehearsal"}]
    bench["workloads"] = [{"name": n, "config": "toy", "traffic": n,
                           "chips": chips if n == "toy-train" else 1,
                           "why": "rehearsal"} for n in TOY_TRAFFIC]

    def remap(metric):
        m = dict(metric)
        if "workloads" in m:
            train = any("train" in w for w in m["workloads"])
            m["workloads"] = ["toy-train"] if train else serve
        return m

    bench["end_to_end"] = [remap(m) for m in real["end_to_end"]]
    bench["per_layer"] = [remap(m) for m in real["per_layer"]]
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp


def files_under(directory: str) -> dict:
    """Path -> content of every file under ``directory``: what a later PR
    may add to and not edit."""
    found = {}
    for d, _, files in os.walk(directory):
        for fn in files:
            with open(os.path.join(d, fn), "rb") as f:
                found[os.path.join(d, fn)] = f.read()
    return found


def assert_none_edited(before: dict) -> None:
    for path, content in before.items():
        with open(path, "rb") as f:
            assert f.read() == content, f"{path} was edited"
