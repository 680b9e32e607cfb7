"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464): one-token step
and chunked prefill, each a Pallas kernel with an XLA path of the same
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
  the rule is a unit lower-triangular system, solved by forward
  substitution in float32; between chunks the state is carried. The
  kernel (trace name ``gdn_chunk``) runs the carried part, one value head a
  grid step with the state in VMEM over all the chunks; what has no
  sequential dependence (the triangular system, the products with the
  decays) is batched XLA in front of it. Decays enter as ``exp`` of
  DIFFERENCES of the cumulated log-decay, never as a quotient of two
  exponentials: a chunk of strong decays cannot overflow.
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
def _chunk_operands(q, k, v, g, beta, c: int):
    """What the carried part of the chunked rule reads, every chunk and
    value head at once. ``q``/``k`` [T, Hk, dk] (normalised, ``q`` scaled),
    ``v`` [T, Hv, dv], ``g`` (log decay ≤ 0) / ``beta`` [T, Hv], ``T`` a
    multiple of the chunk ``c``. Returns, each ``[Hv, chunks, ...]`` float32:
    ``qg`` (queries times the decay since the chunk's start) [C, dk],
    ``kdt`` (keys times the decay up to the chunk's end, TRANSPOSED) [dk,
    C], ``w`` and ``u`` (the triangular system's solutions against the
    decayed keys and the values) [C, dk] / [C, dv], ``intra`` (queries
    against the chunk's own keys, decayed, causal) [C, C], ``d`` (the whole
    chunk's decay, along the value lanes) [1, dv]."""
    T, hk, dk = k.shape
    hv, dv = v.shape[1], v.shape[2]
    n, rep = T // c, v.shape[1] // k.shape[1]
    f32 = jnp.float32

    def heads_first(x, per_key_head=False):     # [T, H, d] -> [Hv, n, C, d]
        x = x.astype(f32).reshape(n, c, x.shape[1], -1)
        x = jnp.transpose(x, (2, 0, 1, 3))
        return jnp.repeat(x, rep, axis=0) if per_key_head else x

    qh, kh = heads_first(q, True), heads_first(k, True)
    vh = heads_first(v)
    gh = jnp.cumsum(heads_first(g[..., None])[..., 0], axis=-1)  # [Hv, n, C]
    bh = heads_first(beta[..., None])                           # [.., C, 1]
    lower = jnp.tril(jnp.ones((c, c), bool))
    # exp of a difference, and only where it is ≤ 0
    decay = jnp.where(lower, jnp.exp(jnp.where(
        lower, gh[..., :, None] - gh[..., None, :], 0.0)), 0.0)
    kb = kh * bh
    a = -jnp.einsum("hncd,hned->hnce", kb, kh, precision=_HI) * decay
    a = jnp.where(jnp.tril(jnp.ones((c, c), bool), -1), a, 0.0)

    def substitute(i, a):
        # row i of (I + A)⁻¹ − I from the rows above it: exact float32
        row = jax.lax.dynamic_index_in_dim(a, i, axis=2, keepdims=False)
        row = row + jnp.einsum("hnj,hnjk->hnk", row, a, precision=_HI)
        return jax.lax.dynamic_update_index_in_dim(a, row, i, axis=2)

    t = jax.lax.fori_loop(1, c, substitute, a) + jnp.eye(c, dtype=f32)
    u = jnp.einsum("hnce,hned->hncd", t, vh * bh, precision=_HI)
    w = jnp.einsum("hnce,hned->hncd", t, kb * jnp.exp(gh)[..., None],
                   precision=_HI)
    intra = jnp.einsum("hncd,hned->hnce", qh, kh, precision=_HI) * decay
    qg = qh * jnp.exp(gh)[..., None]
    kdt = jnp.swapaxes(kh * jnp.exp(gh[..., -1:] - gh)[..., None], -1, -2)
    d = jnp.broadcast_to(jnp.exp(gh[..., -1])[..., None, None],
                         (hv, n, 1, dv))
    return qg, kdt, w, u, intra, d


def _carry_chunk(s, qg, kdt, w, u, intra, d):
    """One chunk of the carried part: state ``s`` [dk, dv] in, ``(o [C,
    dv], state)`` out."""
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                            precision=_HI)
    v_new = u - dot(w, s)
    o = dot(qg, s) + dot(intra, v_new)
    return o, s * d + dot(kdt, v_new)


def _chunk_kernel(qg_ref, kdt_ref, w_ref, u_ref, intra_ref, d_ref, s_ref,
                  o_ref, so_ref, *, chunks: int):
    s = s_ref[0]
    for c in range(chunks):
        o, s = _carry_chunk(s, qg_ref[0, c], kdt_ref[0, c], w_ref[0, c],
                            u_ref[0, c], intra_ref[0, c], d_ref[0, c])
        o_ref[0, c] = o
    so_ref[0] = s


def chunk_rule(q, k, v, g, beta, state, *, kernel: bool = True):
    """``T`` tokens of one sequence: ``q``/``k`` [T, Hk, dk], ``v`` [T, Hv,
    dv], ``g`` (log decay) / ``beta`` [T, Hv], ``state`` [Hv, dk, dv] ->
    ``(o [T, Hv, dv] float32, state)``, in chunks of `CHUNK` tokens (of
    their greatest common divisor with ``T``, where that is smaller); a
    token past the sequence's end carries ``g = 0, beta = 0`` and changes
    nothing. ``kernel=False``: the carried part as a ``lax.scan``."""
    T, hv, dv = v.shape
    dk = k.shape[2]
    c = math.gcd(T, CHUNK)
    n = T // c
    operands = _chunk_operands(q, k, v, g, beta, c)
    state = state.astype(jnp.float32)
    if kernel:
        def head(h):
            return h, 0, 0, 0

        blocks = [(1, n) + x.shape[2:] for x in operands]
        o, state = pl.pallas_call(
            functools.partial(_chunk_kernel, chunks=n),
            grid=(hv,),
            in_specs=[pl.BlockSpec(b, head) for b in blocks]
            + [pl.BlockSpec((1, dk, dv), lambda h: (h, 0, 0))],
            out_specs=[pl.BlockSpec((1, n, c, dv), head),
                       pl.BlockSpec((1, dk, dv), lambda h: (h, 0, 0))],
            out_shape=[jax.ShapeDtypeStruct((hv, n, c, dv), jnp.float32),
                       jax.ShapeDtypeStruct((hv, dk, dv), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=ops.interpret(),
            name="gdn_chunk",
        )(*operands, state)
    else:
        def step(s, xs):
            o, s = jax.vmap(_carry_chunk)(s, *xs)
            return s, o

        state, o = jax.lax.scan(
            step, state, tuple(jnp.swapaxes(x, 0, 1) for x in operands))
        o = jnp.swapaxes(o, 0, 1)                       # [Hv, n, C, dv]
    return jnp.transpose(o, (1, 2, 0, 3)).reshape(T, hv, dv), state
