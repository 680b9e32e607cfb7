"""Seeded weights, made on the device in one jitted call.

The benchmark makes the weights (the program's own initialiser is never
asked): each leaf's stream is the run's seed folded with the leaf's name,
so the program's copy and the reference's are the same numbers made
twice, and neither side takes anything the other has made. A leaf is
drawn in float32 and cast, inside the call that draws it, to the dtype
it is asked for in: a tree that is served in bfloat16 never stands on the
device in float32. A stream depends on the leaf's name and shape alone
(``jax_threefry_partitionable``: an element's bits follow from its index),
so a sub-spec, or one layer of a stacked leaf, gives the same numbers as
the whole tree does.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.traffic import seed32


def _normal_at(key, first: tuple, shape: tuple):
    """``jax.random.normal(key, whole, float32)`` from its flat element
    ``first`` (a 64-bit index as two uint32 scalars, high and low) on,
    ``shape`` elements of it, bit for bit (tested): JAX's own steps for a
    partitionable threefry key — an element's bits are the two threefry
    words of its index, xor-ed; 23 of them are the mantissa of a float in
    [1, 2); that less 1 is stretched over (-1, 1); sqrt(2) erf_inv."""
    from jax.extend.random import threefry2x32_p

    assert jax.config.jax_threefry_partitionable and key.shape == (2,)
    low = first[1] + jax.lax.iota(jnp.uint32, math.prod(shape))
    high = first[0] + (low < first[1]).astype(jnp.uint32)     # the carry
    a, b = threefry2x32_p.bind(key[0], key[1], high, low)
    one_to_two = jax.lax.bitcast_convert_type(
        ((a ^ b) >> np.uint32(9)) | np.float32(1.0).view(np.uint32),
        jnp.float32)
    lo = np.nextafter(np.float32(-1.0), np.float32(0.0))
    u = jnp.maximum(lo, (one_to_two - np.float32(1.0))
                    * (np.float32(1.0) - lo) + lo)
    return (np.float32(np.sqrt(2)) * jax.lax.erf_inv(u)).reshape(shape)


def _leaf(key, shape, kind, first=None):
    """One leaf in float32; with ``first`` (see ``_normal_at``) ``shape``
    is a run of a larger leaf's elements, starting there."""
    noise = jax.random.normal(key, shape, jnp.float32) if first is None \
        else _normal_at(key, first, shape)
    if kind == "scale":
        return 1.0 + 0.1 * noise
    return 0.02 * noise


def _leaf_key(root, name: str):
    return jax.random.fold_in(root, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def root_key(seed: int):
    return jax.random.PRNGKey(seed32(seed, 7) % (2 ** 31))


def build(spec: dict, root, dtypes: dict | None = None) -> dict:
    """Traceable: every leaf of ``spec`` (name -> (shape, kind)) from the
    root key; each leaf's stream is the key folded with the leaf's name.
    ``dtypes`` (name -> dtype; float32 where a name is missing) is what
    each leaf is cast to as it is drawn."""
    dtypes = dtypes or {}
    return {n: _leaf(_leaf_key(root, n), tuple(spec[n][0]), spec[n][1]
                     ).astype(dtypes.get(n, jnp.float32))
            for n in sorted(spec)}


def make(spec: dict, seed: int, shardings: dict | None = None,
         dtypes: dict | None = None) -> dict:
    """The weights of ``seed`` in one jitted call; ``shardings`` (name ->
    sharding) places the leaves as they are made, ``dtypes`` (name ->
    dtype) is what they are made in: the dtypes of the tree they go into."""
    out_sh = None if shardings is None else {n: shardings[n] for n in spec}
    return jax.jit(lambda root: build(spec, root, dtypes),
                   out_shardings=out_sh)(root_key(seed))


_after_all_enqueued = jax.jit(lambda x: x + 1)


class Source:
    """The float32 weights of ``seed``, made when the reference asks.

    ``tree()`` is the whole spec at once, for a reference that takes it
    so (made once, kept). ``leaf(name)`` is one leaf, and ``leaf(name,
    layer)`` one index of a stacked leaf's first axis, drawn alone and
    anew at each call (one program a name): a reference that walks its
    layers holds one layer at a time, whatever the spec's total. For
    that, ``leaf`` first waits until the device has run what was
    enqueued: the host runs ahead of the device, an output is allocated
    when its program is enqueued, and without the wait the draws of
    several layers stand beside a layer that has not run yet (on the
    chip: three layers' weights where one was meant)."""

    def __init__(self, spec: dict, seed: int):
        self.spec, self.seed = spec, seed
        self._tree = None
        self._draw: dict = {}

    def tree(self) -> dict:
        if self._tree is None:
            self._tree = make(self.spec, self.seed)
        return self._tree

    def leaf(self, name: str, layer: int | None = None):
        shape, kind = tuple(self.spec[name][0]), self.spec[name][1]
        # programs run in the order they were enqueued: when this one
        # has, every earlier one has, and its dropped arguments are free
        jax.block_until_ready(_after_all_enqueued(jnp.int32(0)))
        first = None
        if layer is not None:
            assert 0 <= layer < shape[0], (name, layer, shape)
            shape = shape[1:]
            at = layer * math.prod(shape)
            first = (jnp.uint32(at >> 32), jnp.uint32(at & 0xFFFFFFFF))
        if (name, shape) not in self._draw:
            self._draw[name, shape] = jax.jit(lambda root, first: _leaf(
                _leaf_key(root, name), shape, kind, first))
        return self._draw[name, shape](root_key(self.seed), first)


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
