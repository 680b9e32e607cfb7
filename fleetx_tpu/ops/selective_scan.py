"""The selective scan (Mamba-1, arXiv:2312.00752): a chunk of one sequence
and the one-token step, each a Pallas kernel with a plain ``jnp`` form of
the same arithmetic.

A channel ``c`` keeps a state ``h[:, c]`` of ``N`` numbers and sees, a
token, an input ``x[c]``, a step ``Δ[c] > 0`` and — shared by all channels —
``B`` and ``C`` (``N`` numbers each); ``A[:, c] < 0`` and ``D[c]`` are the
layer's::

    h[:, c] ← exp(Δ[c] A[:, c]) ⊙ h[:, c] + Δ[c] x[c] B
    y[c]    = h[:, c] · C + D[c] x[c]

The state is held STATE-MAJOR, ``[N, channels]``: what the paper writes
``[channels, N]``, transposed — the same numbers, and the layout in which
the channels are lanes (5,120 of them: 40 whole lane tiles) and the ``N =
16`` states two sublane tiles, so the read ``h · C`` is a sum over sublanes
and nothing is padded (``[channels, 16]`` would pad every row to 128 lanes:
8× the bytes). Everything here is float32: the state accumulates over 10⁴
steps, and the exponent's argument is a product of two learned numbers.

- ``scan_chunk``: ``T`` tokens of ONE sequence from a state, state out. The
  kernel (trace name ``ssm_chunk``) keeps the state of every channel in
  VMEM for the whole chunk and loops over the tokens inside — as an XLA
  loop a chunk of 512 tokens is 512 dependent steps a layer — with the grid
  over blocks of tokens (outer) and of channels (inner), so that a token
  block's ``B`` and ``C`` are fetched once for all the channel blocks. A
  token past a ragged chunk's end carries ``Δ = 0`` and leaves the state as
  it was. ``kernel=False`` (or a geometry the kernel does not admit,
  `scan_refusal`): a ``lax.scan`` over the tokens.
- ``scan_step``: one token for every LIVE row of a batch, layer ``layer`` of
  the state buffer ``[layers, slots, N, channels]`` updated IN PLACE
  (aliased in and out; trace name ``ssm_decode``), a row a grid step, and no
  other row touched: the live rows' indices arrive compacted as scalar
  prefetch, the steps past the last live row point at the block the step
  before them used, so they move nothing (the contract of
  ``ops/gated_delta.py:gdn_decode``).

``B`` and ``C`` reach the kernels repeated along 128 lanes (``[..., N,
128]``): a state's ``B`` has to lie along SUBLANES to meet ``h``, and a
``[N, 1]`` operand would be copied into VMEM four bytes at a time. The
kernels read lane 0 and broadcast it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fleetx_tpu import ops

_LANES = 128
#: tokens a grid step of the chunk kernel holds, and the widest block of
#: channels: 10 vregs of state a step (16 × 640 float32), so the state, ``A``
#: and a step's temporaries stay in the 64 registers
_TOKEN_BLOCK = 64
_CHANNEL_BLOCKS = (640, 512, 384, 256, 128)
_VMEM_LIMIT = 64 * 1024 * 1024


def _channel_block(channels: int) -> int:
    return next((b for b in _CHANNEL_BLOCKS if channels % b == 0), 0)


def scan_refusal(*, channels: int, states: int, chunk: int) -> str:
    """Why the kernels do not take this geometry, or "": channels in whole
    lane tiles, states and the chunk's tokens in whole sublane tiles."""
    if _channel_block(channels) == 0:
        return f"{channels} channels are not whole 128-lane tiles"
    if states % 8:
        return f"{states} states a channel are not whole 8-row sublane tiles"
    if chunk % 8:
        return f"a chunk of {chunk} tokens is not whole 8-row sublane tiles"
    return ""


def _lanes(v: jax.Array) -> jax.Array:
    """``[..., N]`` -> ``[..., N, 128]``, repeated along the lanes."""
    return jnp.broadcast_to(v.astype(jnp.float32)[..., None],
                            v.shape + (_LANES,))


# ------------------------------------------------------------------ one token
def step_rule(h, x, delta, a, b, c, d):
    """The recurrence for one token a row, plain ``jnp``: ``h`` [..., N,
    ch], ``x``/``delta`` [..., ch], ``a`` [N, ch], ``b``/``c`` [..., N],
    ``d`` [ch] -> ``(y [..., ch], h)``, float32."""
    x, delta = x.astype(jnp.float32), delta.astype(jnp.float32)
    h = jnp.exp(delta[..., None, :] * a) * h \
        + b[..., :, None] * (delta * x)[..., None, :]
    return (h * c[..., :, None]).sum(-2) + d * x, h


def _step_kernel(rows_ref, n_ref, layer_ref, x_ref, dl_ref, b_ref, c_ref,
                 a_ref, d_ref, s_ref, y_ref, so_ref):
    """One live row: ``x_ref``/``dl_ref``/``y_ref`` [1, 1, ch], ``b_ref``/
    ``c_ref`` [1, N, 128], ``a_ref`` [N, ch], ``d_ref`` [1, ch],
    ``s_ref``/``so_ref`` [1, 1, N, ch]: the row's state, one buffer in and
    out."""
    del rows_ref, layer_ref

    @pl.when(pl.program_id(0) < n_ref[0])
    def _update():
        x, delta = x_ref[0], dl_ref[0]                       # [1, ch]
        h = jnp.exp(delta * a_ref[...]) * s_ref[0, 0] \
            + b_ref[0][:, 0:1] * (delta * x)
        so_ref[0, 0] = h
        y_ref[0] = (h * c_ref[0][:, 0:1]).sum(axis=0, keepdims=True) \
            + d_ref[...] * x


def scan_step(state_buf, layer, x, delta, a, b, c, d, live, *,
              kernel: bool = True):
    """One token for every LIVE row: ``state_buf`` [layers, B, N, ch]
    float32 (row *b* is slot *b*), ``x``/``delta`` [B, ch], ``a`` [N, ch],
    ``b``/``c`` [B, N], ``d`` [ch], ``live`` [B] bool -> ``(y [B, ch]
    float32, state_buf)``; a row that is not live keeps its state and gets
    zeros. ``kernel=False``: the XLA path (the layer's states read and
    written whole)."""
    _, B, n, ch = state_buf.shape
    f32 = jnp.float32
    if not kernel:
        old = state_buf[layer]
        y, new = step_rule(old, x, delta, a, b.astype(f32), c.astype(f32), d)
        state_buf = state_buf.at[layer].set(
            jnp.where(live[:, None, None], new, old))
        return jnp.where(live[:, None], y, 0.0), state_buf
    # the live rows first; a step past the last of them points at the block
    # the step before it used and moves nothing
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    n_live = live.sum().astype(jnp.int32).reshape(1)

    def row(i, rows, nl):
        return rows[jnp.minimum(i, jnp.maximum(nl[0] - 1, 0))]

    def per_row(i, rows, nl, lay):
        return row(i, rows, nl), 0, 0

    def whole(i, rows, nl, lay):
        return 0, 0

    def state_at(i, rows, nl, lay):
        return lay[0], row(i, rows, nl), 0, 0

    y, state_buf = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, 1, ch), per_row),
                      pl.BlockSpec((1, 1, ch), per_row),
                      pl.BlockSpec((1, n, _LANES), per_row),
                      pl.BlockSpec((1, n, _LANES), per_row),
                      pl.BlockSpec((n, ch), whole),
                      pl.BlockSpec((1, ch), whole),
                      pl.BlockSpec((1, 1, n, ch), state_at)],
            out_specs=[pl.BlockSpec((1, 1, ch), per_row),
                       pl.BlockSpec((1, 1, n, ch), state_at)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, 1, ch), f32),
                   jax.ShapeDtypeStruct(state_buf.shape, f32)],
        input_output_aliases={9: 1},    # 3 scalars + 6 inputs: the state
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=ops.interpret(),
        name="ssm_decode",
    )(order, n_live, jnp.reshape(layer, (1,)).astype(jnp.int32),
      x.astype(f32)[:, None], delta.astype(f32)[:, None], _lanes(b),
      _lanes(c), a.astype(f32), d.astype(f32)[None], state_buf)
    # a row no step wrote holds whatever the output buffer held
    return jnp.where(live[:, None], y[:, 0], 0.0), state_buf


# -------------------------------------------------------------------- a chunk
def scan_rule(x, delta, a, b, c, d, state):
    """``T`` tokens of one sequence, plain ``jnp``: a ``lax.scan`` of
    `step_rule`. ``x``/``delta`` [T, ch], ``a`` [N, ch], ``b``/``c`` [T,
    N], ``d`` [ch], ``state`` [N, ch] -> ``(y [T, ch] float32, state)``."""
    f32 = jnp.float32

    def step(h, xs):
        y, h = step_rule(h, *xs[:2], a, *xs[2:], d)
        return h, y

    state, y = jax.lax.scan(step, state.astype(f32), (
        x.astype(f32), delta.astype(f32), b.astype(f32), c.astype(f32)))
    return y, state


def _chunk_kernel(x_ref, dl_ref, b_ref, c_ref, a_ref, d_ref, s_ref, y_ref,
                  so_ref, h_ref, *, token_block: int):
    """Grid step (token block *t*, channel block *j*): ``x_ref``/``dl_ref``/
    ``y_ref`` [tb, cb], ``b_ref``/``c_ref`` [tb, N, 128], ``a_ref`` [N,
    cb], ``d_ref`` [1, cb], ``s_ref``/``so_ref`` [N, cb]; ``h_ref``
    [channel blocks, N, cb]: every channel's state, resident for the whole
    chunk. Eight tokens a loop step: one sublane tile of ``x``, ``Δ`` and
    ``y``."""
    t, j = pl.program_id(0), pl.program_id(1)

    @pl.when(t == 0)
    def _first_block():
        h_ref[j] = s_ref[...]

    a, d = a_ref[...], d_ref[...]
    row = jax.lax.broadcasted_iota(jnp.int32, (8, a.shape[1]), 0)

    def eight(i, h):
        at = pl.multiple_of(i * 8, 8)
        xs, ds = x_ref[pl.ds(at, 8), :], dl_ref[pl.ds(at, 8), :]
        ys = jnp.zeros_like(xs)
        for k in range(8):
            x, delta = xs[k:k + 1, :], ds[k:k + 1, :]            # [1, cb]
            h = jnp.exp(delta * a) * h \
                + b_ref[at + k][:, 0:1] * (delta * x)
            y = (h * c_ref[at + k][:, 0:1]).sum(axis=0, keepdims=True) \
                + d * x
            ys = jnp.where(row == k, y, ys)
        y_ref[pl.ds(at, 8), :] = ys
        return h

    h = jax.lax.fori_loop(0, token_block // 8, eight, h_ref[j])
    h_ref[j] = h
    so_ref[...] = h


def scan_chunk(x, delta, a, b, c, d, state, *, kernel: bool = True):
    """``T`` tokens of one sequence: ``x``/``delta`` [T, ch], ``a`` [N,
    ch], ``b``/``c`` [T, N], ``d`` [ch], ``state`` [N, ch] -> ``(y [T, ch]
    float32, state)``. A token past the sequence's end carries ``delta =
    0`` and changes nothing. ``kernel=False``, or a geometry `scan_refusal`
    names: the ``lax.scan``."""
    T, ch = x.shape
    n = a.shape[0]
    if not kernel or scan_refusal(channels=ch, states=n, chunk=T):
        return scan_rule(x, delta, a, b, c, d, state)
    f32 = jnp.float32
    cb = _channel_block(ch)
    tb = next(t for t in (_TOKEN_BLOCK, 32, 16, 8) if T % t == 0)

    def tokens(t, j):
        return t, j

    def shared(t, j):
        return t, 0, 0

    def channels(t, j):
        return 0, j

    y, state = pl.pallas_call(
        functools.partial(_chunk_kernel, token_block=tb),
        grid=(T // tb, ch // cb),
        in_specs=[pl.BlockSpec((tb, cb), tokens),
                  pl.BlockSpec((tb, cb), tokens),
                  pl.BlockSpec((tb, n, _LANES), shared),
                  pl.BlockSpec((tb, n, _LANES), shared),
                  pl.BlockSpec((n, cb), channels),
                  pl.BlockSpec((1, cb), channels),
                  pl.BlockSpec((n, cb), channels)],
        out_specs=[pl.BlockSpec((tb, cb), tokens),
                   pl.BlockSpec((n, cb), channels)],
        out_shape=[jax.ShapeDtypeStruct((T, ch), f32),
                   jax.ShapeDtypeStruct((n, ch), f32)],
        scratch_shapes=[pltpu.VMEM((ch // cb, n, cb), f32)],
        # in order: a channel block's state is carried from token block to
        # token block
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=ops.interpret(),
        name="ssm_chunk",
    )(x.astype(f32), delta.astype(f32), _lanes(b), _lanes(c), a.astype(f32),
      d.astype(f32)[None], state.astype(f32))
    return y, state
