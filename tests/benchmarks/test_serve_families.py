"""The serving half of the harness is driven by data (ISSUE 34): the family
comes from the recipe's ``Model.module``, the weights are made in the dtype
they are served in, and the check walks one sample — and, where the
reference asks, one layer — at a time."""

import argparse
import io
import json
import logging
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import check, manifest as manifest_mod, run, weights
from benchmarks.manifest import Manifest

from tests.benchmarks import toy
from tests.benchmarks.test_benchmark_harness import _toy_served

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = toy.ROOT


def _bits(x):
    x = np.asarray(x)
    return x.reshape(-1).view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


def _second_family_root(tmp: str) -> tuple:
    """A rehearsal root to which a second family arrives as a later PR
    would bring it: a family file, a reference, a configuration and
    entries; returns ``(root, the files that were there, by content)``."""
    root = toy.make_root(tmp)
    bench = os.path.join(root, "benchmarks")
    before = toy.files_under(bench)
    shutil.copy(os.path.join(HERE, "toy_family.py"),
                os.path.join(bench, "families/ToyLayersModule.py"))
    shutil.copy(os.path.join(HERE, "toy_streamed_ref.py"),
                os.path.join(bench, "reference/toy_streamed_ref.py"))
    cfg = toy.toy_config()
    cfg.update(name="toy-layers", reference="toy_streamed_ref",
               layout="per_layer")
    cfg["serve"]["overrides"] = cfg["serve"]["overrides"] + [
        "Model.module=ToyLayersModule"]
    gpt_paths = cfg["param_paths"]
    layers = int(cfg["num_layers"])
    cfg["param_paths"] = {"wte": "embed/word_embeddings",
                          "wpe": "embed/position_embeddings",
                          "lnf_g": "final_norm/scale",
                          "lnf_b": "final_norm/bias"}
    for name, path in gpt_paths.items():
        if path.startswith("gpt/layers/"):
            for l in range(layers):
                cfg["param_paths"][f"{name}.{l}"] = \
                    f"block_{l}/" + path[len("gpt/layers/"):]
    with open(os.path.join(bench, "configs/toy-layers.json"), "w") as f:
        json.dump(cfg, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        data = json.load(f)
    data["configs"].append({"name": "toy-layers", "source": "tests",
                            "file": "benchmarks/configs/toy-layers.json",
                            "reduced": [], "why": "a second family"})
    data["workloads"].append({"name": "toy-layers-closed",
                              "config": "toy-layers", "traffic": "toy-closed",
                              "chips": 1, "why": "added by a later PR"})
    for m in data["end_to_end"] + data["per_layer"]:
        if "workloads" in m and "toy-closed" in m["workloads"]:
            m["workloads"].append("toy-layers-closed")
    with open(path, "w") as f:
        json.dump(data, f)
    return root, before


class _Lines(logging.Handler):
    def __init__(self):
        super().__init__()
        self.lines = []

    def emit(self, record):
        self.lines.append(record.getMessage())


@pytest.mark.parametrize("cell", ["toy-closed", "toy-layers-closed"])
def test_a_family_is_served_from_the_recipe_and_handed_no_leaf_to_cast(
        tmp_path, cell):
    """The GPT toy and a second family that arrived as files and entries
    only: served ``correct``, the float8 control not, and the engine's own
    line says it cast no leaf."""
    from fleetx_tpu.utils.log import logger as program_logger

    root, before = _second_family_root(str(tmp_path))
    said = _Lines()
    program_logger.addHandler(said)
    out, err = io.StringIO(), io.StringIO()
    try:
        run.run_cell(argparse.Namespace(
            workload=cell, seed=3000000031, seconds=1.5, trace=0,
            control="float8"), root=root, platforms=("cpu",), out=out,
            err=err)
    finally:
        program_logger.removeHandler(said)
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, err.getvalue()
    limit = toy.toy_config()["check"]["serve"]["served_logit_widest_gap"]
    assert line["check"]["served_logit_widest_gap"] <= limit
    assert line["control"]["check"]["served_logit_widest_gap"] > limit
    built = [ln for ln in said.lines if "leaves cast" in ln]
    assert len(built) == 1 and " 0 leaves cast, serving tree " in built[0]
    toy.assert_none_edited(before)


def test_a_recipe_whose_family_has_no_file_is_refused(tmp_path):
    root, _ = _second_family_root(str(tmp_path))
    os.remove(os.path.join(root, "benchmarks/families/ToyLayersModule.py"))
    with pytest.raises(manifest_mod.ManifestError,
                       match="families/ToyLayersModule"):
        run.run_cell(argparse.Namespace(
            workload="toy-layers-closed", seed=1, seconds=1.0, trace=0,
            control=""), root=root, platforms=("cpu",), out=io.StringIO(),
            err=io.StringIO())


def _served_tree(seed: int):
    """``(model config, template, spec, param_paths)`` of the toy GPT
    serve part, through the shipped family file."""
    from fleetx_tpu.utils import config as config_mod

    sizes = toy.toy_config()
    part = sizes["serve"]
    cfg = config_mod.get_config(os.path.join(ROOT, part["recipe"]),
                                list(part["overrides"]), num_devices=1)
    m = Manifest(ROOT)
    family = m.family(cfg["Model"]["module"])
    model_cfg, template = family.served_template(cfg)
    ref = manifest_mod.load_module(m.reference_path("gpt_ref"))
    return model_cfg, template, ref.weight_spec(sizes), sizes["param_paths"]


def test_the_template_states_the_dtype_each_leaf_is_served_in():
    model_cfg, template, _, paths = _served_tree(5)
    named = weights.program_paths(paths, template)
    assert jnp.dtype(model_cfg.dtype) == jnp.bfloat16
    norms = {n for n in named if n.startswith("ln")}
    assert norms == {"ln1_g", "ln1_b", "ln2_g", "ln2_b", "lnf_g", "lnf_b"}
    for name, leaf in named.items():
        assert leaf.dtype == (jnp.float32 if name in norms
                              else jnp.bfloat16), name


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 7])
def test_leaves_made_in_bfloat16_are_the_engines_cast_of_float32_ones(seed):
    """``bf16(0.02 N)`` of the same stream, bit for bit: a fixed seed
    serves the tokens it served when the engine did the cast. And the
    program finds nothing to cast in the tree the harness hands it."""
    from fleetx_tpu.serving.decode import serving_params

    model_cfg, template, spec, paths = _served_tree(seed)
    dtypes = {n: l.dtype
              for n, l in weights.program_paths(paths, template).items()}
    made = weights.to_program_tree(weights.make(spec, seed, dtypes=dtypes),
                                   paths, template)
    cast = serving_params(weights.to_program_tree(
        weights.make(spec, seed), paths, template), model_cfg)
    for a, b in zip(jax.tree.leaves(made), jax.tree.leaves(cast)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert (_bits(a) == _bits(b)).all()
    again = serving_params(made, model_cfg)
    assert all(a is b for a, b in zip(jax.tree.leaves(made),
                                      jax.tree.leaves(again)))


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 9])
def test_one_layer_drawn_alone_is_that_layer_of_the_whole_leaf(seed):
    spec = {"a": ((5, 7, 33), "matrix"), "g": ((3, 64), "scale"),
            "b": ((4,), "bias"), "big": ((3, 700, 1000), "matrix")}
    source = weights.Source(spec, seed)
    tree = source.tree()
    assert tree is source.tree()            # made once
    for name, (shape, _) in spec.items():
        assert (_bits(source.leaf(name)) == _bits(tree[name])).all()
        for layer in range(shape[0]):
            got = source.leaf(name, layer)
            assert got.shape == tree[name][layer].shape
            assert (_bits(got) == _bits(tree[name][layer])).all(), \
                (name, layer)


def test_an_elements_index_carries_past_32_bits():
    """A layer deep in a leaf of more than 2**32 elements starts at a
    64-bit index: the run that crosses 2**32 continues as the run that
    starts there (high word 1, low word 0)."""
    key = jax.random.PRNGKey(3)
    u32 = jnp.uint32
    across = weights._normal_at(key, (u32(0), u32(2 ** 32 - 3)), (8,))
    after = weights._normal_at(key, (u32(1), u32(0)), (5,))
    start = weights._normal_at(key, (u32(0), u32(0)), (5,))
    assert (_bits(across[3:]) == _bits(after)).all()
    assert not (_bits(after) == _bits(start)).any()
    assert (_bits(start) == _bits(jax.random.normal(key, (5,)))).all()


@pytest.mark.parametrize("chooser", [None, "float8"])
def test_streamed_and_whole_tree_reference_read_the_same_gap(chooser):
    """The same numbers through both protocols: ``gpt_ref`` is handed the
    tree, the toy reference asks for its leaves layer by layer."""
    gpt, cfg, source, samples = _toy_served()
    streamed = manifest_mod.load_module(
        os.path.join(HERE, "toy_streamed_ref.py"))
    asked = []

    class Counting(weights.Source):
        def tree(self):
            raise AssertionError("a streamed reference takes no tree")

        def leaf(self, name, layer=None):
            asked.append((name, layer))
            return super().leaf(name, layer)

    whole = check.served_logit_gaps(gpt, cfg, source, samples, 128,
                                    chooser=chooser)
    walked = check.served_logit_gaps(
        streamed, cfg, Counting(source.spec, source.seed), samples, 128,
        chooser=chooser)
    assert walked["tokens_compared"] == whole["tokens_compared"] == 164
    assert walked["widest_gap"] == pytest.approx(whole["widest_gap"],
                                                 abs=2e-5)
    assert ("fc_w", 1) in asked and ("wte", None) in asked
    limit = cfg["check"]["serve"]["served_logit_widest_gap"]
    assert (walked["widest_gap"] > limit) == (chooser == "float8")
