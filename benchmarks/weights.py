"""Seeded weights, made on the device in one jitted call.

The benchmark makes the weights (the program's own initialiser is never
asked): each leaf's stream is the run's seed folded with the leaf's name,
so the program's copy and the reference's are the same numbers made
twice, and neither side takes anything the other has made.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

from benchmarks.traffic import seed32


def _leaf(key, shape, kind):
    noise = jax.random.normal(key, shape, jnp.float32)
    if kind == "scale":
        return 1.0 + 0.1 * noise
    return 0.02 * noise


def root_key(seed: int):
    return jax.random.PRNGKey(seed32(seed, 7) % (2 ** 31))


def build(spec: dict, root) -> dict:
    """Traceable: every leaf of ``spec`` (name -> (shape, kind)) from the
    root key; each leaf's stream is the key folded with the leaf's name."""
    return {n: _leaf(jax.random.fold_in(root, zlib.crc32(n.encode())
                                        & 0x7FFFFFFF),
                     tuple(spec[n][0]), spec[n][1]) for n in sorted(spec)}


def make(spec: dict, seed: int, shardings: dict | None = None) -> dict:
    """The weights of ``seed`` in one jitted call; ``shardings`` (name ->
    sharding) places the leaves as they are made."""
    out_sh = None if shardings is None else {n: shardings[n] for n in spec}
    return jax.jit(lambda root: build(spec, root),
                   out_shardings=out_sh)(root_key(seed))


def _named_leaves(param_paths: dict, tree) -> tuple:
    """``([(weight name, leaf), ...], treedef)`` of a program tree; a leaf's
    path is ``a/b/c`` whatever boxes (``.value``) the program wraps it in."""
    by_path = {path: name for name, path in param_paths.items()}
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    named = []
    for path, leaf in flat:
        keys = [str(getattr(p, "key", getattr(p, "name", p))) for p in path]
        named.append((by_path["/".join(k for k in keys if k != "value")],
                      leaf))
    return named, treedef


def program_paths(param_paths: dict, tree) -> dict:
    """Weight name -> the leaf of the program's tree it maps onto."""
    return dict(_named_leaves(param_paths, tree)[0])


def to_program_tree(weights: dict, param_paths: dict, template):
    """``template`` (the program's parameter tree) with each leaf replaced
    by the weight that the configuration's ``param_paths`` maps onto it."""
    named, treedef = _named_leaves(param_paths, template)
    for name, old in named:
        assert weights[name].shape == old.shape, (name, old.shape)
    return jax.tree_util.tree_unflatten(
        treedef, [weights[name] for name, _ in named])
