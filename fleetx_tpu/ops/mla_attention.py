"""Pallas flash attention for latent (low-rank) attention heads on TPU.

A latent-attention score is two products: a ``nope`` part with per-head
keys (128 wide) and a rotary part whose key is shared by every head (64
wide); values are 128 wide. ``ops/flash_attention.py`` takes one
``head_dim`` for q, k and v, so this module has its own kernels:

- no width is padded in HBM. Two heads' rotary queries lie side by side
  in one 128-lane row (``qr2`` ``[batch, heads/2, seq, 128]``, which the
  query projection produces directly), and the shared rotary key is held
  twice in a row (``[batch, seq, 128]``, 1/32 of a per-head tensor);
- one grid step serves the two heads of a pair: each head's score is
  ``qn . kn + (qr2 masked to its half) . kr2``, so the rotary product
  runs 128 deep on the MXU where the mathematics needs 64 — the padding
  is inside the kernel and the roofline count (``benchmarks/kernels``)
  charges the published widths;
- operands stay bf16 on the MXU with float32 accumulation; softmax
  statistics and accumulators are float32;
- backward is the FlashAttention-2 split: ``mla_flash_bwd_dq`` and
  ``mla_flash_bwd_dkv`` recompute P from the saved log-sum-exp. The
  shared key's gradient leaves the kernel per head pair and is summed
  over pairs and lane halves outside;
- causal blocks above the diagonal are skipped and their index maps are
  clamped to the last block needed, so a skipped step moves no data.

Layout contract: heads-major ``[batch, heads, seq, dim]`` — what the
projections' einsums write directly; no transpose around the call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fleetx_tpu import ops
from fleetx_tpu.ops.flash_attention import pick_block

_NEG_INF = -1e30
_LANES = 128
_PAIR = 2          # heads a grid step serves: 128 lanes / 64 rotary dims
#: scoped VMEM for one kernel instance: the dkv kernel holds ~12 MiB of
#: blocks and float32 score tiles at 512 x 512
_VMEM_LIMIT = 64 * 1024 * 1024


def supported(qn: jax.Array, qr2: jax.Array, v: jax.Array) -> bool:
    """True when the kernels take these shapes: 128-wide nope and value
    parts, 64-wide rotary parts packed two to a row, an even head count
    and a sequence that tiles into 128-row blocks."""
    if qn.ndim != 4 or qr2.ndim != 4:
        return False
    _, heads, seq, dn = qn.shape
    return (dn == _LANES and v.shape[-1] == _LANES and
            qr2.shape[-1] == _LANES and heads % _PAIR == 0 and
            qr2.shape[1] * _PAIR == heads and seq >= 128 and seq % 128 == 0)


def _causal_branches(compute, q_start, k_start, block_q, block_k):
    """Run ``compute(masked)`` for the blocks a causal mask keeps: with the
    mask where the block crosses the diagonal, without where it lies wholly
    below (most blocks: the mask's compares and selects are VPU work)."""
    crosses = k_start + block_k - 1 > q_start
    pl.when(crosses & (k_start <= q_start + block_q - 1))(
        functools.partial(compute, True))
    pl.when(jnp.logical_not(crosses))(functools.partial(compute, False))


def _halves(q2):
    """The pair's two rotary queries, each in its own half of the lanes
    and zero in the other."""
    low = jax.lax.broadcasted_iota(jnp.int32, q2.shape, 1) < _LANES // _PAIR
    zero = jnp.zeros_like(q2)
    return jnp.where(low, q2, zero), jnp.where(low, zero, q2)


def _scores(qn, qr, kn, k2, scale, q_start, k_start, masked):
    """Float32 scores of one head for one (q block, k block), causally
    masked where the block crosses the diagonal (``masked``)."""
    nt = (((1,), (1,)), ((), ()))
    s = jax.lax.dot_general(qn, kn, nt, preferred_element_type=jnp.float32)
    s = s + jax.lax.dot_general(qr, k2, nt,
                                preferred_element_type=jnp.float32)
    s = s * scale
    if not masked:
        return s
    rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(rows >= cols, s, _NEG_INF)


# ------------------------------------------------------------------ forward
def _fwd_kernel(qn_ref, qr2_ref, kn_ref, k2_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, block_q, block_k):
    qi, kj = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start, k_start = qi * block_q, kj * block_k

    def _compute(masked):
        qr = _halves(qr2_ref[0, 0])
        k2 = k2_ref[0]
        for h in range(_PAIR):
            s = _scores(qn_ref[0, h], qr[h], kn_ref[0, h], k2, scale,
                        q_start, k_start, masked)
            # the row statistics fill all 128 lanes of their scratch (the
            # accumulator is 128 wide too), so every update is a dense
            # elementwise op and none a strided column store
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - pltpu.repeat(m_new, block_k // _LANES, axis=1))
            l_ref[h] = l_ref[h] * alpha + p.sum(axis=1, keepdims=True)
            m_ref[h] = m_new
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot(
                p.astype(v_ref.dtype), v_ref[0, h],
                preferred_element_type=jnp.float32)

    _causal_branches(_compute, q_start, k_start, block_q, block_k)

    @pl.when(kj == nk - 1)
    def _finish():
        for h in range(_PAIR):
            l = l_ref[h]
            o_ref[0, h] = (acc_ref[h] / l).astype(o_ref.dtype)
            lse_ref[0, h] = (m_ref[h] + jnp.log(l))[:, :1]


def _params():
    """Batch, head pair and the outer block are independent; the inner
    block axis carries the accumulators."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * 3 + ("arbitrary",),
        vmem_limit_bytes=_VMEM_LIMIT)


def _fwd(qn, qr2, kn, k2, v, *, scale, block_q, block_k):
    b, heads, s, _ = qn.shape
    bq, bk = min(block_q, s), min(block_k, s)

    def last_k(i):          # the last key block a query block needs
        return ((i + 1) * bq - 1) // bk

    def q_at(b_, h, i, j):
        return (b_, h, i, 0)

    def k_at(b_, h, i, j):
        return (b_, h, jnp.minimum(j, last_k(i)), 0)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, block_q=bq, block_k=bk),
        grid=(b, heads // _PAIR, s // bq, s // bk),
        in_specs=[
            pl.BlockSpec((1, _PAIR, bq, _LANES), q_at),
            pl.BlockSpec((1, 1, bq, _LANES), q_at),
            pl.BlockSpec((1, _PAIR, bk, _LANES), k_at),
            pl.BlockSpec((1, bk, _LANES),
                         lambda b_, h, i, j: (b_, jnp.minimum(j, last_k(i)),
                                              0)),
            pl.BlockSpec((1, _PAIR, bk, _LANES), k_at),
        ],
        out_specs=[
            pl.BlockSpec((1, _PAIR, bq, _LANES), q_at),
            pl.BlockSpec((1, _PAIR, bq, 1), q_at),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(qn.shape, qn.dtype),
            jax.ShapeDtypeStruct((b, heads, s, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((_PAIR, bq, _LANES), jnp.float32),
            pltpu.VMEM((_PAIR, bq, _LANES), jnp.float32),
            pltpu.VMEM((_PAIR, bq, _LANES), jnp.float32),
        ],
        compiler_params=_params(),
        interpret=ops.interpret(),
        name="mla_flash_fwd",
    )(qn, qr2, kn, k2, v)
    return out, lse


# ----------------------------------------------------------------- backward
def _tile_grads(qn, qr, kn, k2, v, do, lse, delta, scale, q_start, k_start,
                masked):
    """P and dS of one head's tile, in the operands' dtype for the MXU."""
    s = _scores(qn, qr, kn, k2, scale, q_start, k_start, masked)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    return p.astype(do.dtype), ds.astype(do.dtype)


def _bwd_dq_kernel(qn_ref, qr2_ref, kn_ref, k2_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dqn_ref, dqr2_ref, accn_ref, accr_ref, *,
                   scale, block_q, block_k):
    qi, kj = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        accn_ref[...] = jnp.zeros_like(accn_ref)
        accr_ref[...] = jnp.zeros_like(accr_ref)

    q_start, k_start = qi * block_q, kj * block_k

    def _compute(masked):
        qr = _halves(qr2_ref[0, 0])
        k2 = k2_ref[0]
        low = jax.lax.broadcasted_iota(
            jnp.int32, accr_ref.shape, 1) < _LANES // _PAIR
        for h in range(_PAIR):
            _, ds = _tile_grads(qn_ref[0, h], qr[h], kn_ref[0, h], k2,
                                v_ref[0, h], do_ref[0, h], lse_ref[0, h],
                                delta_ref[0, h], scale, q_start, k_start,
                                masked)
            accn_ref[h] += jax.lax.dot(ds, kn_ref[0, h],
                                       preferred_element_type=jnp.float32)
            r = jax.lax.dot(ds, k2, preferred_element_type=jnp.float32)
            accr_ref[...] += jnp.where(low == (h == 0), r, 0.0)

    _causal_branches(_compute, q_start, k_start, block_q, block_k)

    @pl.when(kj == nk - 1)
    def _finish():
        dqn_ref[0] = accn_ref[...].astype(dqn_ref.dtype)
        dqr2_ref[0, 0] = accr_ref[...].astype(dqr2_ref.dtype)


def _bwd_dkv_kernel(qn_ref, qr2_ref, kn_ref, k2_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dkn_ref, dk2_ref, dv_ref, dkn_acc, dk2_acc,
                    dv_acc, *, scale, block_q, block_k):
    kj, qi = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dkn_acc[...] = jnp.zeros_like(dkn_acc)
        dk2_acc[...] = jnp.zeros_like(dk2_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start, k_start = qi * block_q, kj * block_k

    def _compute(masked):
        qr = _halves(qr2_ref[0, 0])
        k2 = k2_ref[0]
        tn = (((0,), (0,)), ((), ()))
        for h in range(_PAIR):
            p, ds = _tile_grads(qn_ref[0, h], qr[h], kn_ref[0, h], k2,
                                v_ref[0, h], do_ref[0, h], lse_ref[0, h],
                                delta_ref[0, h], scale, q_start, k_start,
                                masked)
            dv_acc[h] += jax.lax.dot_general(
                p, do_ref[0, h], tn, preferred_element_type=jnp.float32)
            dkn_acc[h] += jax.lax.dot_general(
                ds, qn_ref[0, h], tn, preferred_element_type=jnp.float32)
            # each head's rotary query is zero in the other head's lanes,
            # so the pair's two contributions land in their own halves
            dk2_acc[...] += jax.lax.dot_general(
                ds, qr[h], tn, preferred_element_type=jnp.float32)

    _causal_branches(_compute, q_start, k_start, block_q, block_k)

    @pl.when(qi == nq - 1)
    def _finish():
        dkn_ref[0] = dkn_acc[...].astype(dkn_ref.dtype)
        dk2_ref[0, 0] = dk2_acc[...]
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_calls(qn, qr2, kn, k2, v, do, lse, delta, *, scale, block_q,
               block_k):
    b, heads, s, _ = qn.shape
    bq, bk = min(block_q, s), min(block_k, s)
    pairs = heads // _PAIR
    operands = (qn, qr2, kn, k2, v, do, lse, delta)

    def specs(q_at, k_at, k2_at):
        return [
            pl.BlockSpec((1, _PAIR, bq, _LANES), q_at),
            pl.BlockSpec((1, 1, bq, _LANES), q_at),
            pl.BlockSpec((1, _PAIR, bk, _LANES), k_at),
            pl.BlockSpec((1, bk, _LANES), k2_at),
            pl.BlockSpec((1, _PAIR, bk, _LANES), k_at),
            pl.BlockSpec((1, _PAIR, bq, _LANES), q_at),
            pl.BlockSpec((1, _PAIR, bq, 1), q_at),
            pl.BlockSpec((1, _PAIR, bq, 1), q_at),
        ]

    # dq: query blocks outer, key blocks inner (clamped past the diagonal)
    def last_k(i):
        return ((i + 1) * bq - 1) // bk

    def q_at(b_, h, i, j):
        return (b_, h, i, 0)

    dqn, dqr2 = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, block_q=bq,
                          block_k=bk),
        grid=(b, pairs, s // bq, s // bk),
        in_specs=specs(
            q_at,
            lambda b_, h, i, j: (b_, h, jnp.minimum(j, last_k(i)), 0),
            lambda b_, h, i, j: (b_, jnp.minimum(j, last_k(i)), 0)),
        out_specs=[pl.BlockSpec((1, _PAIR, bq, _LANES), q_at),
                   pl.BlockSpec((1, 1, bq, _LANES), q_at)],
        out_shape=[jax.ShapeDtypeStruct(qn.shape, qn.dtype),
                   jax.ShapeDtypeStruct(qr2.shape, qr2.dtype)],
        scratch_shapes=[pltpu.VMEM((_PAIR, bq, _LANES), jnp.float32),
                        pltpu.VMEM((bq, _LANES), jnp.float32)],
        compiler_params=_params(),
        interpret=ops.interpret(),
        name="mla_flash_bwd_dq",
    )(*operands)

    # dk, dv: key blocks outer, query blocks inner (clamped before the
    # first query block that sees this key block)
    def first_q(j):
        return (j * bk) // bq

    def k_at(b_, h, j, i):
        return (b_, h, j, 0)

    def q_in(b_, h, j, i):
        return (b_, h, jnp.maximum(i, first_q(j)), 0)

    dkn, dk2, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, block_q=bq,
                          block_k=bk),
        grid=(b, pairs, s // bk, s // bq),
        in_specs=specs(q_in, k_at, lambda b_, h, j, i: (b_, j, 0)),
        out_specs=[pl.BlockSpec((1, _PAIR, bk, _LANES), k_at),
                   pl.BlockSpec((1, 1, bk, _LANES), k_at),
                   pl.BlockSpec((1, _PAIR, bk, _LANES), k_at)],
        out_shape=[jax.ShapeDtypeStruct(kn.shape, kn.dtype),
                   jax.ShapeDtypeStruct((b, pairs, s, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((_PAIR, bk, _LANES), jnp.float32),
                        pltpu.VMEM((bk, _LANES), jnp.float32),
                        pltpu.VMEM((_PAIR, bk, _LANES), jnp.float32)],
        compiler_params=_params(),
        interpret=ops.interpret(),
        name="mla_flash_bwd_dkv",
    )(*operands)
    return dqn, dqr2, dkn, dk2, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _mla(qn, qr2, kn, kr, v, scale, block_q, block_k):
    return _mla_fwd(qn, qr2, kn, kr, v, scale, block_q, block_k)[0]


def _mla_fwd(qn, qr2, kn, kr, v, scale, block_q, block_k):
    k2 = jnp.concatenate([kr] * _PAIR, axis=-1)
    out, lse = _fwd(qn, qr2, kn, k2, v, scale=scale, block_q=block_q,
                    block_k=block_k)
    return out, (qn, qr2, kn, kr, v, out, lse)


def _mla_bwd(scale, block_q, block_k, residuals, do):
    qn, qr2, kn, kr, v, out, lse = residuals
    k2 = jnp.concatenate([kr] * _PAIR, axis=-1)
    delta = (out.astype(jnp.float32) * do.astype(jnp.float32)).sum(
        axis=-1, keepdims=True)
    dqn, dqr2, dkn, dk2, dv = _bwd_calls(
        qn, qr2, kn, k2, v, do, lse, delta, scale=scale, block_q=block_q,
        block_k=block_k)
    b, pairs, s, _ = dk2.shape
    # the shared key: every pair and both lane halves add up
    dkr = dk2.reshape(b, pairs, s, _PAIR, _LANES // _PAIR).sum(axis=(1, 3))
    return dqn, dqr2, dkn, dkr.astype(kr.dtype), dv


_mla.defvjp(_mla_fwd, _mla_bwd)


def unpack_rope_queries(qr2: jax.Array) -> jax.Array:
    """``[batch, heads/2, seq, 128]`` -> ``[batch, heads, seq, 64]``."""
    b, pairs, s, _ = qr2.shape
    return qr2.reshape(b, pairs, s, _PAIR, _LANES // _PAIR).transpose(
        0, 1, 3, 2, 4).reshape(b, pairs * _PAIR, s, _LANES // _PAIR)


def reference_attention(qn, qr2, kn, kr, v, *, scale: float) -> jax.Array:
    """The same mathematics in plain ``jax.numpy`` (O(S^2) memory): the
    path for shapes the kernels do not take, and the tests' yardstick."""
    qr = unpack_rope_queries(qr2) if qr2.shape[1] != qn.shape[1] else qr2
    s = jnp.einsum("bnqd,bnkd->bnqk", qn, kn,
                   preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bnqd,bkd->bnqk", qr, kr,
                       preferred_element_type=jnp.float32)
    seq = qn.shape[2]
    s = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), s * scale, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bnqk,bnkd->bnqd", p, v,
                      preferred_element_type=jnp.float32).astype(v.dtype)


def mla_flash_attention(qn: jax.Array, qr2: jax.Array, kn: jax.Array,
                        kr: jax.Array, v: jax.Array, *, scale: float,
                        block_q: int | None = None,
                        block_k: int | None = None) -> jax.Array:
    """Causal latent attention. ``qn``, ``kn``, ``v``: ``[batch, heads,
    seq, 128]``; ``qr2``: ``[batch, heads/2, seq, 128]`` (two heads'
    rotary queries to a row); ``kr``: ``[batch, seq, 64]`` (the rotary key
    all heads share). Returns ``[batch, heads, seq, 128]``. Callers gate
    on ``supported`` and take ``reference_attention`` otherwise."""
    seq = qn.shape[2]
    block_q = pick_block(seq, _LANES) if block_q is None else block_q
    block_k = pick_block(seq, _LANES) if block_k is None else block_k
    if seq % min(seq, block_q) or seq % min(seq, block_k):
        raise ValueError(f"blocks {block_q}/{block_k} do not tile seq {seq}")
    return _mla(qn, qr2, kn, kr, v, float(scale), int(block_q),
                int(block_k))
