"""Pallas flash attention (FlashAttention-2) for TPU.

Replaces the reference's fused attention core — ``core_attn`` with
``incubate.softmax_mask_fuse_upper_triangle``
(``hybrid_model.py:268-298``) — with a blockwise online-softmax kernel that
never materialises the [S, S] score matrix in HBM:

- forward: one pass over K/V blocks per Q block, f32 accumulators in VMEM,
  causal blocks skipped entirely (2x FLOP saving);
- backward, fused (default where ``fused_backward_supported``): ONE kernel
  sweeps the (k-block, q-block) tile grid once, recomputes P once per tile,
  and emits dq, dk and dv together — dq accumulates in its full-sequence
  f32 output window (VMEM-resident per head, one HBM writeback), dk/dv in
  per-block scratch over the minor (q) dimension. The split pair re-reads
  q/k/v/do and recomputes P in each of its two kernels; the fused sweep
  does both once. ``flash_bwd_roofline`` 21.7 % (GPT-345M, 64-wide heads) /
  43.6 % (GPT-1.3B, 128-wide) of the compute floor (ledger, PR 30); fused
  against split: not measured on the chip (ROADMAP S10).
- backward, split (fallback): FlashAttention-2 style — a dq kernel and a
  dk/dv kernel that recompute P from the saved logsumexp, so residual memory
  is O(S) not O(S^2). Selected when the fused predicate rejects the shape
  (wide heads, non-tiling or very long sequences) or via
  ``fused_bwd=False`` (``Model.flash_fused_bwd``).

Layout contract: q, k, v are [batch, seq, heads, head_dim] (the model's
``bsnd``); internally reshaped to [batch*heads, seq, head_dim].

Falls back automatically (``supported()``) when shapes don't tile; on CPU the
kernel runs in interpreter mode so the same code path is unit-testable without
hardware.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fleetx_tpu import ops
from fleetx_tpu.parallel.rules import activation_spec

_VMEM = pltpu.VMEM

# Block sizes default to the largest of these that tiles the sequence:
# 512x512 measured 3.6x faster than 128x128 on v5e (fwd, seq 1024, d 64) —
# bigger blocks amortise the per-block epilogue and keep the MXU busy, and
# VMEM still fits comfortably (f32 scores block = 1MB). Callers can override
# with explicit block_q/block_k.
_BLOCK_CANDIDATES = (512, 256, 128)


def pick_block(seq: int, head_dim: int = 64) -> int:
    """Largest candidate block that tiles ``seq``; when none divides it the
    whole sequence becomes one block (grid 1 — always correct; absurdly long
    non-tiling sequences then fail loudly in Mosaic on VMEM rather than
    silently leaving output rows unwritten). Wide heads (256) cap at 256 to
    keep the backward kernels' live VMEM (q/k/v/do blocks + f32 scores +
    accumulators, double-buffered) well under the ~16MB budget."""
    cap = 256 if head_dim > 128 else _BLOCK_CANDIDATES[0]
    for b in _BLOCK_CANDIDATES:
        if b <= cap and seq >= b and seq % b == 0:
            return b
    return seq
_NEG_INF = -1e30


def dropout_supported() -> bool:
    """In-kernel dropout needs the TPU PRNG (``pltpu.prng_seed``), which has
    no interpret-mode lowering — so it's available exactly when we're NOT
    interpreting. CPU callers fall back to the naive-attention dropout path."""
    return not ops.interpret()


def supported(q: jax.Array, k: jax.Array | None = None,
              block_q: int | None = None,
              block_k: int | None = None, causal: bool = True) -> bool:
    """True when the pallas path applies: seq tiles into blocks and head_dim
    is MXU-friendly. When ``k`` is given, its seq length must also tile — and
    must equal q's under ``causal`` (see flash_attention), so gating on this
    predicate never selects a call that then raises. ``block_q``/``block_k``
    default to ``pick_block`` of the respective seq length, matching
    ``flash_attention``'s own defaulting."""
    if q.ndim != 4:
        return False
    seq, head_dim = q.shape[1], q.shape[3]
    block_q = pick_block(seq, head_dim) if block_q is None else block_q
    # q's seq only needs to tile into q blocks; k's seq into k blocks
    if seq % min(seq, block_q):
        return False
    if seq < 128 or seq % 128:
        return False
    if k is not None:
        if k.ndim != 4 or k.shape[3] != head_dim:
            return False
        sk = k.shape[1]
        if causal and sk != seq:
            return False
        block_k = pick_block(sk, head_dim) if block_k is None else block_k
        if sk < 128 or sk % 128 or sk % min(sk, block_k):
            return False
    elif block_k is not None and seq % min(seq, block_k):
        return False
    return head_dim in (64, 128, 256)


#: VMEM budget for the fused backward's full-sequence f32 dq accumulator
#: window (plus the two per-block dk/dv scratches), counted at the 128-lane
#: width a narrower head pads to. Mosaic double-buffers the window, so
#: 4 MiB here is 8 MiB of the core's 16 MiB scoped limit, and the q/k/v/do
#: blocks with the f32 score tiles take ~2.3 MiB more (v5e compile, PERF.md):
#: seq 7168 at any head_dim <= 128.
_FUSED_DQ_SCRATCH_BYTES = 4 * 1024 * 1024


def fused_backward_supported(q: jax.Array, k: jax.Array | None = None,
                             block_q: int | None = None,
                             block_k: int | None = None,
                             causal: bool = True) -> bool:
    """True when the single-pass fused backward kernel applies: the base
    ``supported`` contract, a non-wide head (>128 degrades to the split
    kernels — their per-block scratch stays bounded where the fused dq
    accumulator would not), and the full-sequence f32 dq window within
    ``_FUSED_DQ_SCRATCH_BYTES``. Shapes this rejects fall back to the
    split dq + dkv kernels — today's behavior, never silence."""
    if not supported(q, k, block_q=block_q, block_k=block_k, causal=causal):
        return False
    seq, head_dim = q.shape[1], q.shape[3]
    if head_dim > 128:
        return False
    sk = k.shape[1] if k is not None else seq
    bk = pick_block(sk, head_dim) if block_k is None else min(block_k, sk)
    scratch = (seq + 2 * bk) * max(head_dim, 128) * 4
    return scratch <= _FUSED_DQ_SCRATCH_BYTES


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _dropout_mask(seed_ref, h, qi, kj, nq_blocks, nk_blocks, shape, rate: float):
    """Regenerable per-block dropout mask (same seeding in fwd and bwd).

    Seeded by (step seed, flat block coordinates) so the backward kernels
    reproduce the identical mask when they recompute P from the logsumexp —
    this is what lets attention dropout run inside the flash kernel instead
    of materialising [S, S] probability/mask tensors (the reference applies
    dropout to full probs, ``single_model.py:214``).

    ``nq_blocks``/``nk_blocks`` are STATIC so the flat id is identical across
    the fwd/dq/dkv kernels, whose grid orders differ; Mosaic accepts at most
    two seed words.
    """
    flat = (h * nq_blocks * nk_blocks + qi * nk_blocks + kj).astype(jnp.int32)
    pltpu.prng_seed(seed_ref[0], flat)
    bits = pltpu.prng_random_bits(shape)
    threshold = min(int(rate * 2.0 ** 32), 2 ** 32 - 1)
    keep = bits.astype(jnp.uint32) >= jnp.uint32(threshold)
    return keep


def _fwd_kernel(q_ref, k_ref, v_ref, seed_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale: float, causal: bool,
                block_q: int, block_k: int, dropout_rate: float):
    h = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = kj * block_k
    run = True
    if causal:
        # skip blocks fully above the diagonal
        run = k_start <= q_start + block_q - 1

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        # the softmax normaliser uses UNdropped p; the mask scales only the
        # weighted sum, so out = mask .* softmax(s) / keep_prob @ v
        l_ref[:, 0] = l_ref[:, 0] * alpha + p.sum(axis=1)
        m_ref[:, 0] = m_new
        if dropout_rate > 0.0:
            keep = _dropout_mask(seed_ref, h, qi, kj, pl.num_programs(1),
                                 pl.num_programs(2), p.shape, dropout_rate)
            p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
        v = v_ref[0].astype(jnp.float32)
        acc_ref[:] = acc_ref[:] * alpha[:, None] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _finish():
        l = l_ref[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe[:, None]).astype(o_ref.dtype)
        # lse laid out [bn, sq, 1]: Mosaic needs the last two block dims
        # (8k, 128m-or-full); a (block_q, 1) store satisfies that where a
        # 2D (1, block_q) block does not.
        lse_ref[0] = (m_ref[:, 0] + jnp.log(l_safe))[:, None]


def _fwd(q3, k3, v3, seed, *, scale, causal, block_q, block_k, dropout_rate):
    bn, sq, d = q3.shape
    sk = k3.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    grid = (bn, sq // block_q, sk // block_k)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          dropout_rate=dropout_rate),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda h, i, j: (h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bn, sq, d), q3.dtype),
            jax.ShapeDtypeStruct((bn, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            _VMEM((block_q, d), jnp.float32),
            _VMEM((block_q, 128), jnp.float32),
            _VMEM((block_q, 128), jnp.float32),
        ],
        interpret=ops.interpret(),
        name="flash_fwd",
    )(q3, k3, v3, seed)
    return out, lse[..., 0]


# ---------------------------------------------------------------------------
# backward (FlashAttention-2: recompute P per block from saved logsumexp)
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, seed_ref,
                   dq_ref, acc_ref, *, scale, causal, block_q, block_k,
                   dropout_rate):
    h = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = kj * block_k
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0])          # lse block [bq, 1] broadcasts
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            keep = _dropout_mask(seed_ref, h, qi, kj, pl.num_programs(1),
                                 pl.num_programs(2), p.shape, dropout_rate)
            dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
        ds = p * (dp - delta_ref[0]) * scale
        acc_ref[:] += jax.lax.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _finish():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, seed_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                    block_q, block_k, dropout_rate):
    h = pl.program_id(0)
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = qi * block_q
    k_start = kj * block_k
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0])  # [bq, bk]; lse block [bq, 1] broadcasts
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            # identical (h, qi, kj) seeding as the forward mask; this kernel's
            # grid is (h, kj, qi) so the q/k block counts swap positions
            keep = _dropout_mask(seed_ref, h, qi, kj, pl.num_programs(2),
                                 pl.num_programs(1), p.shape, dropout_rate)
            inv = 1.0 / (1.0 - dropout_rate)
            dv_acc[:] += jax.lax.dot_general(
                jnp.where(keep, p * inv, 0.0), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jnp.where(keep, dp * inv, 0.0)
        else:
            dv_acc[:] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * scale
        dk_acc[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq(q3, k3, v3, do, lse3, delta3, seed, *, scale, causal,
            block_q, block_k, dropout_rate: float = 0.0):
    """dq kernel entry: lse3/delta3 as ``[bn, sq, 1]`` (any lse works — the
    ring backward feeds the GLOBAL logsumexp to get exact per-block grads)."""
    bn, sq, d = q3.shape
    sk = k3.shape[1]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, dropout_rate=dropout_rate),
        grid=(bn, sq // bq, sk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, d), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, bk, d), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bn, sq, d), q3.dtype),
        scratch_shapes=[_VMEM((bq, d), jnp.float32)],
        interpret=ops.interpret(),
        name="flash_bwd_dq",
    )(q3, k3, v3, do, lse3, delta3, seed)


def _bwd_dkv(q3, k3, v3, do, lse3, delta3, seed, *, scale, causal,
             block_q, block_k, dropout_rate: float = 0.0):
    """dk/dv kernel entry (same lse3/delta3 contract as ``_bwd_dq``)."""
    bn, sq, d = q3.shape
    sk = k3.shape[1]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    return pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, dropout_rate=dropout_rate),
        grid=(bn, sk // bk, sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, j, i: (h, i, 0)),
            pl.BlockSpec((1, bk, d), lambda h, j, i: (h, j, 0)),
            pl.BlockSpec((1, bk, d), lambda h, j, i: (h, j, 0)),
            pl.BlockSpec((1, bq, d), lambda h, j, i: (h, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda h, j, i: (h, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda h, j, i: (h, i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda h, j, i: (h, j, 0)),
            pl.BlockSpec((1, bk, d), lambda h, j, i: (h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bn, sk, d), k3.dtype),
            jax.ShapeDtypeStruct((bn, sk, d), v3.dtype),
        ],
        scratch_shapes=[_VMEM((bk, d), jnp.float32), _VMEM((bk, d), jnp.float32)],
        interpret=ops.interpret(),
        name="flash_bwd_dkv",
    )(q3, k3, v3, do, lse3, delta3, seed)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      seed_ref, dq_ref, dk_ref, dv_ref,
                      dk_acc, dv_acc, *, scale, causal,
                      block_q, block_k, dropout_rate):
    """Single-pass fused backward: grid (head, k-block, q-block).

    Each tile recomputes P exactly once and contributes to all three
    grads. dk/dv accumulate in per-block f32 scratch across the minor
    (q) dimension — the split dkv kernel's proven shape, one HBM
    writeback per k-block — and dq accumulates DIRECTLY in its
    full-sequence f32 output window, whose index map depends only on the
    head: Mosaic keeps the window VMEM-resident across the entire
    (k-block, q-block) sweep (the standard reduction idiom — out index
    invariant over the reduction dims) and flushes it to HBM exactly
    once, at the head transition. No per-step garbage flushes, no
    cross-step read-modify-write of HBM-backed blocks.
    """
    h = pl.program_id(0)
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    nk = pl.num_programs(1)
    nq = pl.num_programs(2)

    @pl.when((kj == 0) & (qi == 0))
    def _init_dq():  # fresh head: zero the resident full-seq dq window
        dq_ref[...] = jnp.zeros_like(dq_ref)

    @pl.when(qi == 0)
    def _init_dkv():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = qi * block_q
    k_start = kj * block_k
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0])  # [bq, bk]; lse block [bq, 1] broadcasts
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            # identical (h, qi, kj) seeding as the forward mask; this
            # kernel's grid is (h, kj, qi) so the q/k block counts swap
            keep = _dropout_mask(seed_ref, h, qi, kj, nq, nk, p.shape,
                                 dropout_rate)
            inv = 1.0 / (1.0 - dropout_rate)
            dv_acc[:] += jax.lax.dot_general(
                jnp.where(keep, p * inv, 0.0), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jnp.where(keep, dp * inv, 0.0)
        else:
            dv_acc[:] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * scale
        dk_acc[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dq_ref[0, pl.ds(q_start, block_q), :] += jax.lax.dot(
            ds, k, preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _flush_dkv():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_fused(q3, k3, v3, do, lse3, delta3, seed, *, scale, causal,
               block_q, block_k, dropout_rate: float = 0.0):
    """Fused dq/dk/dv kernel entry (same lse3/delta3 contract as the split
    kernels: ``[bn, sq, 1]``). dq comes back f32 — it IS the in-kernel
    accumulator (see ``_bwd_fused_kernel``) — and is cast to the operand
    dtype outside the kernel."""
    bn, sq, d = q3.shape
    sk = k3.shape[1]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    dq32, dk, dv = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, dropout_rate=dropout_rate),
        grid=(bn, sk // bk, sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, j, i: (h, i, 0)),
            pl.BlockSpec((1, bk, d), lambda h, j, i: (h, j, 0)),
            pl.BlockSpec((1, bk, d), lambda h, j, i: (h, j, 0)),
            pl.BlockSpec((1, bq, d), lambda h, j, i: (h, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda h, j, i: (h, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda h, j, i: (h, i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            # dq: the whole head's [sq, d] as ONE window, index map
            # invariant over both sweep dims — resident in VMEM for the
            # head's entire tile sweep, flushed once at the head change
            pl.BlockSpec((1, sq, d), lambda h, j, i: (h, 0, 0)),
            pl.BlockSpec((1, bk, d), lambda h, j, i: (h, j, 0)),
            pl.BlockSpec((1, bk, d), lambda h, j, i: (h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bn, sq, d), jnp.float32),
            jax.ShapeDtypeStruct((bn, sk, d), k3.dtype),
            jax.ShapeDtypeStruct((bn, sk, d), v3.dtype),
        ],
        scratch_shapes=[
            _VMEM((bk, d), jnp.float32),
            _VMEM((bk, d), jnp.float32),
        ],
        interpret=ops.interpret(),
        name="flash_bwd_fused",
    )(q3, k3, v3, do, lse3, delta3, seed)
    return dq32.astype(q3.dtype), dk, dv


def _bwd(scale, causal, block_q, block_k, dropout_rate, fused_bwd,
         residuals, g):
    q3, k3, v3, seed, out, lse = residuals
    do = g
    delta = (out.astype(jnp.float32) * do.astype(jnp.float32)).sum(axis=-1)
    # lse/delta travel as [bn, sq, 1] so their blocks tile on TPU (see _fwd)
    lse3 = lse[..., None]
    delta3 = delta[..., None]
    kw = dict(scale=scale, causal=causal, block_q=block_q, block_k=block_k,
              dropout_rate=dropout_rate)
    if fused_bwd:
        dq, dk, dv = _bwd_fused(q3, k3, v3, do, lse3, delta3, seed, **kw)
    else:
        dq = _bwd_dq(q3, k3, v3, do, lse3, delta3, seed, **kw)
        dk, dv = _bwd_dkv(q3, k3, v3, do, lse3, delta3, seed, **kw)
    return dq, dk, dv, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash3(q3, k3, v3, seed, scale, causal, block_q, block_k, dropout_rate,
            fused_bwd):
    out, _ = _fwd(q3, k3, v3, seed, scale=scale, causal=causal,
                  block_q=block_q, block_k=block_k, dropout_rate=dropout_rate)
    return out


def _flash3_fwd(q3, k3, v3, seed, scale, causal, block_q, block_k,
                dropout_rate, fused_bwd):
    out, lse = _fwd(q3, k3, v3, seed, scale=scale, causal=causal,
                    block_q=block_q, block_k=block_k, dropout_rate=dropout_rate)
    return out, (q3, k3, v3, seed, out, lse)


_flash3.defvjp(_flash3_fwd, _bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: float | None = None,
                    block_q: int | None = None,
                    block_k: int | None = None,
                    dropout_rate: float = 0.0,
                    dropout_seed: jax.Array | None = None,
                    fused_bwd: bool = True) -> jax.Array:
    """Blockwise causal attention. q/k/v: [batch, seq, heads, head_dim].

    ``dropout_rate`` > 0 applies attention-probability dropout INSIDE the
    kernel (regenerable per-block masks; see ``_dropout_mask``) so training
    configs with attention dropout keep the O(S) memory profile.
    ``dropout_seed``: int32 scalar/[1] array; vary per step.
    ``fused_bwd`` selects the single-pass fused backward kernel where
    ``fused_backward_supported`` admits the shape (``Model.flash_fused_bwd``
    upstream); other shapes — and ``fused_bwd=False`` — take the split
    dq + dkv kernels.
    """
    b, sq, n, d = q.shape
    sk = k.shape[1]
    if block_q is None:
        block_q = pick_block(sq, d)
    if block_k is None:
        block_k = pick_block(sk, d)
    # a non-dividing explicit block would floor away whole grid rows and
    # return unwritten output — refuse loudly (defaults always divide)
    if sq % min(sq, block_q) or sk % min(sk, block_k):
        raise ValueError(
            f"block sizes must tile the sequence: seq {sq}/{sk} vs "
            f"block_q={block_q}, block_k={block_k}")
    if causal and sq != sk:
        # The kernel's causal mask compares absolute row/col positions with no
        # offset, which is only meaningful for self-attention (sq == sk).
        raise ValueError(
            f"flash_attention(causal=True) requires q and k to share a seq "
            f"length; got sq={sq}, sk={sk}")
    scale = scale if scale is not None else d ** -0.5
    if dropout_seed is None:
        seed = jnp.zeros((1,), jnp.int32)
    else:
        seed = jnp.asarray(dropout_seed, jnp.int32).reshape((1,))

    def to3(x, s):
        return x.transpose(0, 2, 1, 3).reshape(b * n, s, d)

    use_fused = bool(fused_bwd) and fused_backward_supported(
        q, k, block_q=block_q, block_k=block_k, causal=causal)
    out3 = _flash3(to3(q, sq), to3(k, sk), to3(v, sk), seed, scale, causal,
                   block_q, block_k, float(dropout_rate), use_fused)
    return out3.reshape(b, n, sq, d).transpose(0, 2, 1, 3)


def reference_attention(q, k, v, *, causal: bool = True,
                        scale: float | None = None) -> jax.Array:
    """Naive O(S^2)-memory attention, used for numerics tests."""
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    s = jnp.einsum("bqnd,bknd->bnqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bnqk,bknd->bqnd", p.astype(q.dtype), v)


#: how attention operands ``[batch, seq, heads, head_dim]`` lie over the
#: mesh: batch over ``(data, fsdp)``, heads over ``tensor``; ``seq`` stays
#: whole here — a context-sharded sequence is ring attention's case
_QKV_SPEC = activation_spec("batch", None, "act_heads", "act_kv")


def sharded_supported(q: jax.Array, mesh) -> bool:
    """True when ``flash_attention_sharded`` can run ``q`` under ``mesh``:
    no mesh or one device (the plain call), else batch divides the data
    axes, heads divide the tensor axis, and the seq axis is not
    context-sharded (ring attention owns that case)."""
    if mesh is None or mesh.size == 1:
        return True
    if q.ndim != 4 or mesh.shape.get("seq", 1) != 1:
        return False
    return ops.local_shape(q.shape, _QKV_SPEC, mesh) is not None


def flash_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array, *,
                            mesh=None, causal: bool = True,
                            **kwargs) -> jax.Array:
    """Mesh-aware flash attention: the kernel is a Mosaic custom call GSPMD
    cannot partition, so under a multi-device mesh it runs per-device —
    batch sharded over ``(data, fsdp)``, heads over ``tensor`` — inside a
    ``shard_map`` that is manual over EVERY mesh axis (the only context in
    which a Mosaic call lowers under a mesh; attention is embarrassingly
    parallel over both dims). Callers gate on ``sharded_supported``.

    Under pipeline parallelism this wrapper is reached through the stage
    ``nn.vmap(spmd_axis_name="pipe")`` (parallel/pipeline.py): ``pipe`` being
    manual here lets the vmap batching rule shard the stage dim over it.

    The in-kernel dropout seed is folded with the device's linear index so
    shards draw independent masks.
    """
    if mesh is None:
        from fleetx_tpu.parallel.mesh import current_mesh

        mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return flash_attention(q, k, v, causal=causal, **kwargs)
    if not sharded_supported(q, mesh):
        raise ValueError(
            f"flash_attention_sharded: q {q.shape} does not lay out over "
            f"mesh {dict(mesh.shape)} as {_QKV_SPEC}")

    def body(q, k, v):
        kw = dict(kwargs)
        if kw.get("dropout_seed") is not None:
            ix = jnp.int32(0)
            for a in ("data", "fsdp", "tensor", "pipe"):
                ix = ix * mesh.shape[a] + jax.lax.axis_index(a)
            kw["dropout_seed"] = kw["dropout_seed"] + ix
        return flash_attention(q, k, v, causal=causal, **kw)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(_QKV_SPEC,) * 3,
                       out_specs=_QKV_SPEC, check_vma=False)
    return fn(q, k, v)
