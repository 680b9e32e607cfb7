"""Pallas grouped matrix products over sorted rows, for sparse experts.

Rows are sorted by expert and each expert's run is padded to whole tiles
of ``tile`` rows, so a tile belongs to ONE expert (``tile_expert``, a
scalar-prefetched table the index maps read): no weight is gathered or
copied, a tile's weights are fetched by its expert's index. Tiles past
``n_tiles`` (the rows that exist) are skipped: ``moe_gmm`` writes zeros
for them, ``moe_tgmm`` adds nothing.

- ``moe_gmm``  : ``out[r] = lhs[r] @ rhs[expert(r)]`` (or ``@ rhs[..].T``);
- ``moe_tgmm`` : ``acc[e] += lhs[rows of e].T @ rhs[rows of e]`` in
  float32, into an accumulator that is aliased in and out, so the passes
  of one backward add up in place and an expert without rows keeps what
  it had.

Operands stay in their dtype on the MXU (bf16 in training) with float32
accumulation. On the CPU the kernels run in Pallas interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fleetx_tpu import ops

_VMEM_LIMIT = 64 * 1024 * 1024


def _block(dim: int, want: int) -> int:
    """``want`` when it tiles ``dim``, else the whole of ``dim``."""
    return want if dim % want == 0 else dim


def _gmm_kernel(tile_expert, n_tiles, lhs_ref, rhs_ref, out_ref, *,
                transpose_rhs):
    del tile_expert
    i = pl.program_id(0)

    @pl.when(i < n_tiles[0])
    def _compute():
        dims = (((1,), (1,)), ((), ())) if transpose_rhs else \
            (((1,), (0,)), ((), ()))
        out_ref[...] = jax.lax.dot_general(
            lhs_ref[...], rhs_ref[0], dims,
            preferred_element_type=jnp.float32).astype(out_ref.dtype)

    @pl.when(i >= n_tiles[0])
    def _skip():
        out_ref[...] = jnp.zeros_like(out_ref)


def moe_gmm(lhs: jax.Array, rhs: jax.Array, tile_expert: jax.Array,
            n_tiles: jax.Array, *, tile: int, transpose_rhs: bool = False,
            out_dtype=None, block_n: int = 2048,
            name: str | None = None) -> jax.Array:
    """``lhs`` [rows, k] times each tile's expert matrix: ``rhs`` is
    ``[experts, k, n]``, or ``[experts, n, k]`` with ``transpose_rhs``.
    ``tile_expert`` [rows / tile] int32, ``n_tiles`` the tiles that hold
    rows. Returns ``[rows, n]``, zeros in the tiles past ``n_tiles``.
    ``name``: what a device trace calls the kernel (a program that wants
    its calls told apart from another's; ``moe_gmm`` / ``moe_gmm_t``)."""
    rows, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tn = _block(n, block_n)
    n_tiles = jnp.asarray(n_tiles, jnp.int32).reshape(1)

    def live(i, nt):      # a skipped tile keeps pointing at the last live one
        return jnp.minimum(i, jnp.maximum(nt[0] - 1, 0))

    def lhs_at(i, j, te, nt):
        return (live(i, nt), 0)

    def rhs_at(i, j, te, nt):
        # ... and at one block of its matrix, so it moves no data
        e = te[live(i, nt)]
        j = jnp.where(i < nt[0], j, 0)
        return (e, j, 0) if transpose_rhs else (e, 0, j)

    rhs_block = (1, tn, k) if transpose_rhs else (1, k, tn)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows // tile, n // tn),
            in_specs=[pl.BlockSpec((tile, k), lhs_at),
                      pl.BlockSpec(rhs_block, rhs_at)],
            out_specs=pl.BlockSpec((tile, tn), lambda i, j, te, nt: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), out_dtype or lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=ops.interpret(),
        name=name or ("moe_gmm_t" if transpose_rhs else "moe_gmm"),
    )(tile_expert.astype(jnp.int32), n_tiles, lhs, rhs)


def _tgmm_kernel(tile_expert, n_tiles, lhs_ref, rhs_ref, acc_ref, out_ref,
                 scratch, *, tiles):
    i = pl.program_id(2)
    e = tile_expert[i]
    first = (i == 0) | (e != tile_expert[jnp.maximum(i - 1, 0)])
    last = (i == tiles - 1) | (e != tile_expert[jnp.minimum(i + 1,
                                                            tiles - 1)])

    @pl.when(first)
    def _start():
        scratch[...] = acc_ref[0]

    @pl.when(i < n_tiles[0])
    def _add():
        scratch[...] += jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(last)
    def _store():
        out_ref[0] = scratch[...]


def moe_tgmm(lhs: jax.Array, rhs: jax.Array, acc: jax.Array,
             tile_expert: jax.Array, n_tiles: jax.Array, *, tile: int,
             block_k: int = 1024, block_n: int = 512) -> jax.Array:
    """``acc[e] + lhs[rows of e].T @ rhs[rows of e]`` for every expert:
    ``lhs`` [rows, k], ``rhs`` [rows, n], ``acc`` float32
    ``[experts, k, n]`` (aliased to the result)."""
    rows, k = lhs.shape
    n = rhs.shape[1]
    tk, tn = _block(k, block_k), _block(n, block_n)
    tiles = rows // tile
    n_tiles = jnp.asarray(n_tiles, jnp.int32).reshape(1)

    def row_at(col):
        def at(a, b, i, te, nt):
            return (jnp.minimum(i, jnp.maximum(nt[0] - 1, 0)),
                    (a, b)[col])
        return at

    def acc_at(a, b, i, te, nt):
        return (te[i], a, b)

    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tiles=tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(k // tk, n // tn, tiles),
            in_specs=[pl.BlockSpec((tile, tk), row_at(0)),
                      pl.BlockSpec((tile, tn), row_at(1)),
                      pl.BlockSpec((1, tk, tn), acc_at)],
            out_specs=pl.BlockSpec((1, tk, tn), acc_at),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(acc.shape, jnp.float32),
        # operands: tile_expert, n_tiles, lhs, rhs, acc
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=ops.interpret(),
        name="moe_tgmm",
    )(tile_expert.astype(jnp.int32), n_tiles, lhs, rhs, acc)
