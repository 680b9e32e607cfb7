"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464): one-token step
and chunked prefill, each a Pallas kernel with a plain form of the same
arithmetic.

A value head keeps a state ``S`` and sees, a token, a key ``k`` and a query
``q`` (L2-normalised, ``q`` scaled), a value ``v``, a decay ``α = exp(g)``
in (0, 1] and a write strength ``β`` in (0, 1)::

    S ← α S;   S ← S + k ⊗ β (v − kᵀ S);   o = qᵀ S

The state is held KEY-MAJOR, ``[key dim, value dim]``: what the paper
writes ``[value, key]``, transposed — the same numbers, and the layout in
which both reads of the state (``kᵀ S``, ``qᵀ S``) are sums over sublanes
and the write ``k ⊗ u`` broadcasts a value row, so the one-token kernel
needs no transpose and no MXU. Everything here is float32: the state
accumulates over 10⁴ steps.

- ``recurrent_step`` / ``gdn_decode``: one token for every row of a batch.
  ``gdn_decode`` updates layer ``layer`` of the state buffer ``[layers,
  slots, value heads, dk, dv]`` IN PLACE (aliased in and out) for the rows
  that are live, a row (all its value heads: 4 MB in, 4 MB out at the
  published widths) a grid step, and touches no other row: the live rows'
  indices arrive compacted as scalar prefetch, the steps past the last live
  row point at the block the step before them used, so they move nothing.
  Trace name ``gdn_decode``.
- ``chunk_rule``: ``T`` tokens of one sequence, state in, state out, in
  chunks of ``CHUNK`` tokens (the WY form of the paper, as the Qwen3-Next
  modelling code's ``torch_chunk_gated_delta_rule`` has it): within a chunk
  the rule is a unit lower-triangular system, between chunks the state is
  carried. The kernel (trace name ``gdn_chunk``) runs ALL of it, one value
  head a grid step with the state in VMEM over all the chunks, from ``q``,
  ``k``, ``v``, ``g`` and ``β`` as they come: a value head's block of
  ``q`` / ``k`` is its key head's columns of the ``[T, Hk · dk]`` view (no
  copy a value head), and nothing a chunk needs is written to HBM on the
  way. The system is solved by BLOCKED forward substitution in float32
  (`_inverse_unit_lower`): the 16-row diagonal blocks by 15 steps on the
  vector unit, two levels of joins on the MXU — the row-by-row
  substitution's solution, never a series in the matrix's powers (it
  cancels where keys repeat inside a chunk). The solves do not read the
  state, so those of `_AHEAD` chunks are made together, step by step in
  turn, ahead of the state that reaches them. The plain form
  (``kernel=False``) is the same two functions (`_chunk_operands`,
  `_carry_chunk`) over every head at once in XLA. Every product is float32
  at ``Precision.HIGHEST``. Decays enter as ``exp`` of DIFFERENCES of the
  cumulated log-decay, never as a quotient of two exponentials: a chunk of
  strong decays cannot overflow.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fleetx_tpu import ops

#: tokens a chunk of the chunked rule holds
CHUNK = 64
#: rows of a diagonal block of a chunk's triangular system
_BLOCK = 16
#: chunks of a value head whose triangular systems the kernel solves
#: together, a step of each in turn, ahead of the state that reaches them
_AHEAD = 4
_HI = jax.lax.Precision.HIGHEST
_VMEM_LIMIT = 64 * 1024 * 1024


def l2_normalise(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """``x / sqrt(sum x² + eps)`` over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + eps)


# ------------------------------------------------------------ one-token rule
def recurrent_step(state, q, k, v, alpha, beta):
    """The rule for one token a row, plain ``jnp``: ``state`` [B, Hv, dk,
    dv], ``q``/``k`` [B, Hk, dk], ``v`` [B, Hv, dv], ``alpha``/``beta`` [B,
    Hv] -> ``(o [B, Hv, dv], state)``. Value head *h* reads key head ``h //
    (Hv / Hk)``."""
    rep = v.shape[1] // k.shape[1]
    kf = jnp.repeat(k.astype(jnp.float32), rep, axis=1)[..., :, None]
    qf = jnp.repeat(q.astype(jnp.float32), rep, axis=1)[..., :, None]
    s = state * alpha[..., None, None]
    u = beta[..., None] * (v.astype(jnp.float32) - (s * kf).sum(-2))
    s = s + kf * u[..., None, :]
    return (s * qf).sum(-2), s


def _decode_kernel(rows_ref, n_ref, layer_ref, qt_ref, kt_ref, v_ref, a_ref,
                   b_ref, s_ref, o_ref, so_ref, *, rep: int):
    """One live row, all its value heads. ``qt_ref``/``kt_ref`` [1, dk,
    Hk]: a key head is a COLUMN (broadcast over the state's value lanes);
    ``v_ref``/``a_ref``/``b_ref`` [1, Hv, dv]: a value head is a row;
    ``s_ref``/``so_ref`` [1, 1, Hv, dk, dv]: the row's states, one buffer
    in and out."""
    del rows_ref, layer_ref

    @pl.when(pl.program_id(0) < n_ref[0])
    def _update():
        for h in range(s_ref.shape[2]):
            kcol = kt_ref[0, :, h // rep:h // rep + 1]      # [dk, 1]
            qcol = qt_ref[0, :, h // rep:h // rep + 1]
            s = s_ref[0, 0, h] * a_ref[0, h:h + 1, :]       # α S  [dk, dv]
            kv = (s * kcol).sum(axis=0, keepdims=True)      # kᵀ S [1, dv]
            u = b_ref[0, h:h + 1, :] * (v_ref[0, h:h + 1, :] - kv)
            s = s + kcol * u
            so_ref[0, 0, h] = s
            o_ref[0, h:h + 1, :] = (s * qcol).sum(axis=0, keepdims=True)


def gdn_decode(state_buf, layer, q, k, v, alpha, beta, live, *,
               kernel: bool = True):
    """One token for every LIVE row: ``state_buf`` [layers, B, Hv, dk, dv]
    float32 (row *b* is slot *b*), ``q``/``k`` [B, Hk, dk], ``v`` [B, Hv,
    dv], ``alpha``/``beta`` [B, Hv], ``live`` [B] bool -> ``(o [B, Hv, dv]
    float32, state_buf)``; a row that is not live keeps its state and gets
    zeros. ``kernel=False``: the XLA path (the layer's states read and
    written whole)."""
    _, B, hv, dk, dv = state_buf.shape
    hk = k.shape[1]
    if not kernel:
        old = state_buf[layer]
        o, new = recurrent_step(old, q, k, v, alpha, beta)
        keep = live[:, None, None, None]
        state_buf = state_buf.at[layer].set(jnp.where(keep, new, old))
        return jnp.where(live[:, None, None], o, 0.0), state_buf
    # the live rows first; a step past the last of them points at the block
    # the step before it used and moves nothing
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    n_live = live.sum().astype(jnp.int32).reshape(1)
    f32 = jnp.float32
    qt = jnp.swapaxes(q.astype(f32), 1, 2)                  # [B, dk, Hk]
    kt = jnp.swapaxes(k.astype(f32), 1, 2)
    a = jnp.broadcast_to(alpha.astype(f32)[..., None], (B, hv, dv))
    b = jnp.broadcast_to(beta.astype(f32)[..., None], (B, hv, dv))

    def row(i, rows, n):
        return rows[jnp.minimum(i, jnp.maximum(n[0] - 1, 0))]

    def per_row(i, rows, n, lay):
        return row(i, rows, n), 0, 0

    def state_at(i, rows, n, lay):
        return lay[0], row(i, rows, n), 0, 0, 0

    o, state_buf = pl.pallas_call(
        functools.partial(_decode_kernel, rep=hv // hk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, dk, hk), per_row),
                      pl.BlockSpec((1, dk, hk), per_row),
                      pl.BlockSpec((1, hv, dv), per_row),
                      pl.BlockSpec((1, hv, dv), per_row),
                      pl.BlockSpec((1, hv, dv), per_row),
                      pl.BlockSpec((1, 1, hv, dk, dv), state_at)],
            out_specs=[pl.BlockSpec((1, hv, dv), per_row),
                       pl.BlockSpec((1, 1, hv, dk, dv), state_at)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, hv, dv), f32),
                   jax.ShapeDtypeStruct(state_buf.shape, f32)],
        input_output_aliases={8: 1},    # 3 scalars + 5 inputs: the state
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=ops.interpret(),
        name="gdn_decode",
    )(order, n_live, jnp.reshape(layer, (1,)).astype(jnp.int32),
      qt, kt, v.astype(f32), a, b, state_buf)
    # a row no step wrote holds whatever the output buffer held
    return jnp.where(live[:, None, None], o, 0.0), state_buf


# -------------------------------------------------------------- chunked rule
def _dot(a, b, contract=((1,), (0,))):
    """A float32 product of two matrices at full precision; ``contract``
    names the summed axis of each."""
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def _inverse_unit_lower(lows):
    """``(I + low)⁻¹`` for each ``low`` [C, C] (strictly lower triangular,
    ``C`` a power of two) of a list, by blocked forward substitution. The
    diagonal blocks of `_BLOCK` rows are inverted all at once, side by side
    along the lanes, a column a step (row ``i`` of a block loses ``low[i,
    j]`` times the block's finished row ``j``: the row-by-row
    substitution's own sums); then each level joins two neighbours, ``[[A,
    0], [C, B]]⁻¹ = [[A⁻¹, 0], [−B⁻¹ C A⁻¹, B⁻¹]]``: with ``X`` the
    block-diagonal inverse so far and ``E`` the part of ``low`` that joins
    neighbours, ``X − X E X``, on the rows that change. No series in powers
    of ``low``: it cancels where a key repeats through the chunk. The
    matrices of the list take each step in turn, so their chains of
    dependent steps interleave (a kernel's schedule follows the order it
    is written in)."""
    c = lows[0].shape[0]
    b = min(_BLOCK, c)
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    diagonal = row // b == col // b
    blocks = range(0, c, b)

    def side_by_side(x):    # the diagonal blocks [b, b] along the lanes
        x = jnp.where(diagonal, x, 0.0)
        return functools.reduce(jnp.add, [x[m:m + b] for m in blocks])

    lows_b = [side_by_side(low) for low in lows]
    row_b = jax.lax.broadcasted_iota(jnp.int32, (b, c), 0)
    col_b = jax.lax.broadcasted_iota(jnp.int32, (b, c), 1)
    first = col_b // b * b              # a lane's block's first lane
    xs = [(row_b == col_b - first).astype(jnp.float32)] * len(lows)
    for j in range(b - 1):
        # row i of every block loses low[i, j] times the block's row j
        xs = [x - jnp.take_along_axis(low, first + j, axis=1) * x[j:j + 1]
              for x, low in zip(xs, lows_b)]
    xs = [jnp.where(diagonal, jnp.concatenate([x] * len(blocks), axis=0), 0.0)
          for x in xs]
    while b < c:
        # the lower neighbour of each pair alone changes: its rows only
        pairs = range(b, c, 2 * b)

        def lower_rows(x):
            return jnp.concatenate([x[m:m + b] for m in pairs], axis=0)

        def among(x):       # those rows back where they lie, zeros between
            zero = jnp.zeros((b, c), jnp.float32)
            return jnp.concatenate(
                [part for i in range(0, c // 2, b)
                 for part in (zero, x[i:i + b])], axis=0)

        join = lower_rows(col // b == row // b - 1)
        ys = [_dot(jnp.where(join, lower_rows(low), 0.0), x)
              for x, low in zip(xs, lows)]
        xs = [x - among(_dot(lower_rows(x), among(y)))
              for x, y in zip(xs, ys)]
        b *= 2
    return xs


def _chunk_operands(chunks):
    """What the carried part reads, for each chunk of a list of ``(q, k, v,
    g, beta)``: ``q``/``k`` [C, dk] (normalised, ``q`` scaled), ``v`` [C,
    dv], ``g`` (log decay ≤ 0) / ``beta`` [1, C]. Returns a list of ``(qg,
    kd, w, u, intra, d)``: queries times the decay since the chunk's start
    [C, dk], keys times the decay up to its end [C, dk], the triangular
    system's solutions against the decayed keys and the values [C, dk] /
    [C, dv], queries against the chunk's own keys, decayed and causal [C,
    C], the whole chunk's decay [1, 1]. Nothing here reads the state."""
    c = chunks[0][0].shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    lower, eye = row >= col, row == col
    nt = ((1,), (1,))
    lows, rest = [], []
    for q, k, v, g, beta in chunks:
        # the cumulated log-decay down the rows, the same numbers along
        # the lanes; write strengths down the rows
        gc = jnp.where(lower, g, 0.0).sum(1, keepdims=True)         # [C, 1]
        gr = jnp.where(eye, gc, 0.0).sum(0, keepdims=True)          # [1, C]
        b = jnp.where(eye, beta, 0.0).sum(1, keepdims=True)
        # exp of a difference, and only where it is ≤ 0
        decay = jnp.where(lower, jnp.exp(jnp.where(lower, gc - gr, 0.0)),
                          0.0)
        since = jnp.exp(gc)
        kb = k * b
        both = _dot(jnp.concatenate([kb, q], axis=0), k, nt)    # one kᵀ
        lows.append(jnp.where(row > col, both[:c] * decay, 0.0))
        rest.append((q * since, k * jnp.exp(gc[c - 1:] - gc), kb * since,
                     v * b, both[c:] * decay, since[c - 1:]))
    return [(qg, kd, _dot(t, kbg), _dot(t, vb), intra, d)
            for t, (qg, kd, kbg, vb, intra, d)
            in zip(_inverse_unit_lower(lows), rest)]


def _carry_chunk(s, qg, kd, w, u, intra, d):
    """One chunk of the carried part: state ``s`` [dk, dv] in, ``(o [C,
    dv], state)`` out."""
    c = w.shape[0]
    both = _dot(jnp.concatenate([w, qg], axis=0), s)            # one state
    v_new = u - both[:c]
    o = both[c:] + _dot(intra, v_new)
    return o, s * d + _dot(kd, v_new, ((0,), (0,)))


def _chunk_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, o_ref, so_ref, *,
                  c: int):
    """One value head, all its chunks, the state in VMEM throughout:
    ``q_ref``/``k_ref`` [T, dk] are the columns of the head's KEY head,
    ``v_ref`` [T, dv] / ``o_ref`` [1, T, dv] the value head's,
    ``g_ref``/``b_ref`` [1, chunks, C] a chunk a row. The operands of
    `_AHEAD` chunks are made together, then carried one by one."""
    s = s_ref[0]
    n = g_ref.shape[1]
    for first in range(0, n, _AHEAD):
        group = range(first, min(first + _AHEAD, n))
        operands = _chunk_operands([
            (q_ref[i * c:(i + 1) * c, :], k_ref[i * c:(i + 1) * c, :],
             v_ref[i * c:(i + 1) * c, :], g_ref[0, i:i + 1, :],
             b_ref[0, i:i + 1, :]) for i in group])
        for i, xs in zip(group, operands):
            o_ref[0, i * c:(i + 1) * c, :], s = _carry_chunk(s, *xs)
    so_ref[0] = s


def chunk_rule(q, k, v, g, beta, state, *, kernel: bool = True):
    """``T`` tokens of one sequence: ``q``/``k`` [T, Hk, dk], ``v`` [T, Hv,
    dv], ``g`` (log decay) / ``beta`` [T, Hv], ``state`` [Hv, dk, dv] ->
    ``(o [T, Hv, dv] float32, state)``, in chunks of `CHUNK` tokens (of
    their greatest common divisor with ``T``, where that is smaller); a
    token past the sequence's end carries ``g = 0, beta = 0`` and changes
    nothing. Value head *h* reads key head ``h // (Hv / Hk)`` where it
    lies. ``kernel=False``: the same chunks, every head at once in XLA."""
    T, hv, dv = v.shape
    hk, dk = k.shape[1:]
    rep = hv // hk
    c = math.gcd(T, CHUNK)
    n = T // c
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    state = state.astype(f32)
    # a chunk a row, a head's rows together
    g, beta = (jnp.transpose(x.astype(f32)).reshape(hv, n, c)
               for x in (g, beta))
    if kernel:
        def key_head(h):
            return 0, h // rep

        def value_head(h):
            return 0, h

        def head(h):
            return h, 0, 0

        o, state = pl.pallas_call(
            functools.partial(_chunk_kernel, c=c),
            grid=(hv,),
            in_specs=[pl.BlockSpec((T, dk), key_head),
                      pl.BlockSpec((T, dk), key_head),
                      pl.BlockSpec((T, dv), value_head),
                      pl.BlockSpec((1, n, c), head),
                      pl.BlockSpec((1, n, c), head),
                      pl.BlockSpec((1, dk, dv), head)],
            out_specs=[pl.BlockSpec((1, T, dv), head),
                       pl.BlockSpec((1, dk, dv), head)],
            out_shape=[jax.ShapeDtypeStruct((hv, T, dv), f32),
                       jax.ShapeDtypeStruct((hv, dk, dv), f32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=ops.interpret(),
            name="gdn_chunk",
        )(q.reshape(T, hk * dk), k.reshape(T, hk * dk),
          v.reshape(T, hv * dv), g, beta, state)
        return jnp.swapaxes(o, 0, 1), state
    # every head's chunks at once; a key head's q and k serve its value heads
    per_head = jax.vmap(
        lambda q, k, v, g, b: _chunk_operands([(q, k, v, g, b)])[0],
        in_axes=(None, None, 1, 0, 0))
    per_key_head = jax.vmap(per_head, in_axes=(1, 1, 1, 0, 0))
    state = state.reshape(hk, rep, dk, dv)
    carry = jax.vmap(jax.vmap(_carry_chunk))
    outs = []
    for i in range(n):
        rows = slice(i * c, (i + 1) * c)
        o, state = carry(state, *per_key_head(
            q[rows], k[rows], v[rows].reshape(c, hk, rep, dv),
            g[:, i:i + 1].reshape(hk, rep, 1, c),
            beta[:, i:i + 1].reshape(hk, rep, 1, c)))
        outs.append(jnp.moveaxis(o.reshape(hv, c, dv), 0, 1))
    return jnp.concatenate(outs), state.reshape(hv, dk, dv)
