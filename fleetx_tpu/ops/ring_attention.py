"""Ring attention over the ``seq`` mesh axis — long-context parallelism.

The reference tops out at Megatron-SP over the TP group (activations
scattered 1/mp along sequence between blocks,
``ppfleetx/models/language_model/gpt/dygraph/sequence_parallel_utils.py:150-326``)
and trains seq_len 1024; it has NO ring/context/blockwise attention anywhere
(SURVEY.md §5). This module is the idiomatic TPU superset: sequence-sharded
attention where K/V blocks rotate around the ``seq`` ring via
``lax.ppermute`` (one ICI hop per step) while each device folds the incoming
block into an online-softmax accumulator — flash attention's streaming
update, distributed.

Written as a ``jax.shard_map`` manual over EVERY mesh axis — the per-block
Pallas kernels are Mosaic calls, which lower under a mesh in no other
context — with batch over ``(data, fsdp)``, the sequence over ``seq`` and
heads over ``tensor``; only ``seq`` carries collectives. Causality with
contiguous block sharding means block ``j`` contributes to queries of block
``i`` only when ``j <= i``; later blocks are masked (the compute is uniform
across ring steps — the standard ring-attention bubble).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from fleetx_tpu import ops
from fleetx_tpu.parallel.rules import activation_spec

__all__ = ["ring_attention", "ring_attention_local", "ring_flash_local",
           "flash_ring_supported"]

_NEG_INF = -1e30


def ring_attention_local(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         axis_name: str = "seq", causal: bool = True,
                         kv_chunk: int | None = None) -> jax.Array:
    """Per-device body; call inside ``shard_map`` with ``axis_name`` manual.

    q/k/v: [batch, s_local, heads, head_dim] — the local sequence block.
    Returns the exact softmax(QK^T)V rows for the local queries.

    ``kv_chunk`` streams each incoming K/V block through the online-softmax
    accumulator in chunks, bounding the live score tensor to
    ``[b, n, s_local, kv_chunk]`` instead of ``[b, n, s_local, s_local]`` —
    at 8k tokens over seq4 that is the difference between ~270MB and ~2.1GB
    of f32 scores per ring step. Exact (online softmax), differentiable
    (plain ``lax.scan``); must divide the local block length.
    """
    ring = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    b, s_loc, n, d = q.shape
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    chunk = int(kv_chunk) if kv_chunk else s_loc
    if s_loc % chunk:
        raise ValueError(f"kv_chunk {chunk} must divide the local block "
                         f"length {s_loc}")
    n_chunks = s_loc // chunk

    q32 = q.astype(jnp.float32)
    qpos = me * s_loc + jnp.arange(s_loc)

    def fold(acc, xs):
        """One K/V chunk through the streaming softmax update."""
        m, l, o = acc
        k_c, v_c, kpos_c = xs
        s = jnp.einsum("bqnd,bknd->bnqk", q32, k_c.astype(jnp.float32)) * scale
        if causal:
            mask = kpos_c[None, :] <= qpos[:, None]  # [q, k]
            s = jnp.where(mask[None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * corr + p.sum(axis=-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bnqk,bknd->bnqd", p, v_c.astype(jnp.float32))
        return (m_new, l_new, o_new), None

    def step(carry, t):
        k_cur, v_cur, m, l, o = carry
        j = (me - t) % ring  # whose block we hold at step t
        kpos = j * s_loc + jnp.arange(s_loc)
        k_ch = jnp.moveaxis(k_cur.reshape(b, n_chunks, chunk, n, d), 1, 0)
        v_ch = jnp.moveaxis(v_cur.reshape(b, n_chunks, chunk, n, d), 1, 0)
        # remat the fold: without it lax.scan stacks each chunk's p
        # residuals across iterations and backward peaks at the full
        # [s_loc, s_loc] score tensor anyway — recompute per chunk instead
        (m, l, o), _ = lax.scan(jax.checkpoint(fold), (m, l, o),
                                (k_ch, v_ch, kpos.reshape(n_chunks, chunk)))
        perm = [(r, (r + 1) % ring) for r in range(ring)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, m, l, o), None

    m0 = jnp.full((b, n, s_loc), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, n, s_loc), jnp.float32)
    o0 = jnp.zeros((b, n, s_loc, d), jnp.float32)
    (_, _, _, l, o), _ = lax.scan(step, (k, v, m0, l0, o0),
                                  jnp.arange(ring))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


# ---------------------------------------------------------------------------
# flash-composed ring (VERDICT r3 #9): the per-ring-step block attention runs
# on the Pallas MXU kernels instead of einsums-in-HBM
# ---------------------------------------------------------------------------


def _qkv_spec(shape: tuple, mesh) -> P:
    """Operand layout ``[batch, seq, heads, head_dim]`` over the mesh: batch
    over ``(data, fsdp)``, the sequence on the ring axis, heads over
    ``tensor``. A batch or head count that does not divide its axes stays
    whole — every device then holds all of it (the init-time dummy batch
    of 1)."""
    batch, seq, heads = activation_spec("batch", "act_seq", "act_heads")
    if ops.local_shape(shape[:1], P(batch), mesh) is None:
        batch = None
    if ops.local_shape(shape[2:3], P(heads), mesh) is None:
        heads = None
    return P(batch, seq, heads)


def flash_ring_supported(q: jax.Array, mesh) -> bool:
    """True when each device's local block (seq / ring) satisfies the Pallas
    kernel contract."""
    if q.ndim != 4:
        return False
    local = ops.local_shape(q.shape, _qkv_spec(q.shape, mesh), mesh)
    if local is None:
        return False
    s_loc, d = local[1], local[3]
    return s_loc >= 128 and s_loc % 128 == 0 and d in (64, 128, 256)


def _to3(x):
    b, s, n, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * n, s, d)


def _ring_perm(axis_name):
    ring = lax.axis_size(axis_name)
    return [(r, (r + 1) % ring) for r in range(ring)]


def _ring_flash_fwd_pass(q3, k3, v3, axis_name, block):
    """Ring forward on the Pallas kernel: per-step (out, lse) folded through
    the online-logsumexp merge. Block structure per device ``me`` at step
    ``t`` (holding block ``j = (me - t) % ring``): ``t == 0`` → causal
    self-block; ``t <= me`` → fully-visible earlier block; else skipped."""
    from fleetx_tpu.ops import flash_attention as fa

    ring = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    bn, s, d = q3.shape
    scale = d ** -0.5
    seed = jnp.zeros((1,), jnp.int32)

    def block_fwd(k_b, v_b, causal):
        return fa._fwd(q3, k_b, v_b, seed, scale=scale, causal=causal,
                       block_q=block, block_k=block, dropout_rate=0.0)

    out, lse = block_fwd(k3, v3, True)  # t = 0: the causal diagonal
    out = out.astype(jnp.float32)
    k_cur, v_cur = k3, v3
    for t in range(1, ring):
        k_cur = lax.ppermute(k_cur, axis_name, _ring_perm(axis_name))
        v_cur = lax.ppermute(v_cur, axis_name, _ring_perm(axis_name))

        def visible(args):
            o_acc, l_acc, k_b, v_b = args
            o_t, l_t = block_fwd(k_b, v_b, False)
            l_new = jnp.logaddexp(l_acc, l_t)
            o_new = (o_acc * jnp.exp(l_acc - l_new)[..., None]
                     + o_t.astype(jnp.float32)
                     * jnp.exp(l_t - l_new)[..., None])
            return o_new, l_new

        out, lse = lax.cond(t <= me, visible,
                            lambda args: (args[0], args[1]),
                            (out, lse, k_cur, v_cur))
    return out.astype(q3.dtype), lse


def ring_flash_local(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     axis_name: str = "seq") -> jax.Array:
    """Causal ring attention whose per-block math runs on the Pallas flash
    kernels (``ops/flash_attention.py``) — forward merges per-block
    (out, lse) pairs; backward re-rotates K/V and runs the dq/dkv kernels
    against the GLOBAL logsumexp. Exact, differentiable, O(s_local) memory.

    Same contract as ``ring_attention_local`` (call inside ``shard_map``
    with ``axis_name`` manual; q/k/v ``[b, s_local, n, d]``), restricted to
    causal self-attention without dropout.
    """
    from fleetx_tpu.ops import flash_attention as fa

    b, s_loc, n, d = q.shape
    block = fa.pick_block(s_loc, d)
    q3, k3, v3 = _to3(q), _to3(k), _to3(v)
    out3 = _ring_flash3(q3, k3, v3, axis_name, block)
    return out3.reshape(b, n, s_loc, d).transpose(0, 2, 1, 3)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ring_flash3(q3, k3, v3, axis_name, block):
    out, _ = _ring_flash_fwd_pass(q3, k3, v3, axis_name, block)
    return out


def _ring_flash3_fwd(q3, k3, v3, axis_name, block):
    out, lse = _ring_flash_fwd_pass(q3, k3, v3, axis_name, block)
    return out, (q3, k3, v3, out, lse)


def _ring_flash3_bwd(axis_name, block, residuals, g):
    from fleetx_tpu.ops import flash_attention as fa

    q3, k3, v3, out, lse = residuals
    do = g
    ring = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    bn, s, d = q3.shape
    scale = d ** -0.5
    seed = jnp.zeros((1,), jnp.int32)
    # p = exp(s - GLOBAL lse) makes the per-block backward exact
    delta = (out.astype(jnp.float32) * do.astype(jnp.float32)).sum(axis=-1)
    lse3, delta3 = lse[..., None], delta[..., None]

    def block_bwd(k_b, v_b, causal):
        dq_b = fa._bwd_dq(q3, k_b, v_b, do, lse3, delta3, seed, scale=scale,
                          causal=causal, block_q=block, block_k=block)
        dk_b, dv_b = fa._bwd_dkv(q3, k_b, v_b, do, lse3, delta3, seed,
                                 scale=scale, causal=causal, block_q=block,
                                 block_k=block)
        return dq_b, dk_b, dv_b

    dq_d, dk_d, dv_d = block_bwd(k3, v3, True)  # diagonal
    dq = dq_d.astype(jnp.float32)
    k_cur, v_cur = k3, v3
    dk_cur = dk_d.astype(jnp.float32)
    dv_cur = dv_d.astype(jnp.float32)
    for t in range(1, ring):
        # dk/dv accumulators travel WITH their k/v block around the ring
        k_cur = lax.ppermute(k_cur, axis_name, _ring_perm(axis_name))
        v_cur = lax.ppermute(v_cur, axis_name, _ring_perm(axis_name))
        dk_cur = lax.ppermute(dk_cur, axis_name, _ring_perm(axis_name))
        dv_cur = lax.ppermute(dv_cur, axis_name, _ring_perm(axis_name))

        def visible(args):
            dq_acc, dk_acc, dv_acc, k_b, v_b = args
            dq_b, dk_b, dv_b = block_bwd(k_b, v_b, False)
            return (dq_acc + dq_b.astype(jnp.float32),
                    dk_acc + dk_b.astype(jnp.float32),
                    dv_acc + dv_b.astype(jnp.float32))

        dq, dk_cur, dv_cur = lax.cond(
            t <= me, visible, lambda args: (args[0], args[1], args[2]),
            (dq, dk_cur, dv_cur, k_cur, v_cur))
    # after ring-1 hops the accumulators sit one hop short of home
    dk_cur = lax.ppermute(dk_cur, axis_name, _ring_perm(axis_name))
    dv_cur = lax.ppermute(dv_cur, axis_name, _ring_perm(axis_name))
    return (dq.astype(q3.dtype), dk_cur.astype(k3.dtype),
            dv_cur.astype(v3.dtype))


_ring_flash3.defvjp(_ring_flash3_fwd, _ring_flash3_bwd)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   causal: bool = True,
                   kv_chunk: int | None = None, mesh=None,
                   use_flash: bool | None = None) -> jax.Array:
    """Sequence-parallel attention: q/k/v ``[b, s, n, d]`` with ``s`` sharded
    over ``seq``, batch over ``(data, fsdp)`` and heads over ``tensor``.
    Must run inside jit under the mesh context (the engine's ``_ctx``).
    ``kv_chunk`` bounds per-ring-step score memory on the einsum path (see
    ``ring_attention_local``).

    ``use_flash`` None (auto) routes causal calls whose local block fits the
    Pallas contract through ``ring_flash_local`` — per-block attention on
    the MXU kernels, the einsum path kept as fallback/reference.
    """
    if mesh is None:
        from fleetx_tpu.parallel.mesh import current_mesh

        mesh = current_mesh()
    assert mesh is not None, "ring_attention needs an ambient or explicit mesh"
    spec = _qkv_spec(q.shape, mesh)
    if use_flash is None:
        use_flash = causal and flash_ring_supported(q, mesh)
    body = (partial(ring_flash_local, axis_name=spec[1]) if use_flash
            else partial(ring_attention_local, axis_name=spec[1],
                         causal=causal, kv_chunk=kv_chunk))
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)
