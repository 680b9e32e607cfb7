"""Fused residual-add + f32 LayerNorm + output-cast Pallas kernel.

XLA materialises an op chain around every pre-norm `LayerNorm` call in
`models/gpt/model.py`: the block residual add, the f32 upcast, the
mean/variance reductions, the normalise/affine elementwise line, and the
cast back to the compute dtype — each a separate HBM round-trip when XLA
declines to fuse across the reduction. This kernel runs the whole chain
in one VMEM-resident pass per row block (`fused_norm_ms` 3.2 of the 211 ms
GPT-345M step, 13.1 of the 648 ms GPT-1.3B step: ledger, PR 30; against
the unfused chain: not measured on the chip, ROADMAP S10):

- forward: ``s = residual + x`` (optional), f32 mean/var over the hidden
  dim, normalise + affine, cast to ``out_dtype`` — one read of ``x`` (and
  ``residual``), one write each of ``out``/``s``/the two stat rows.
- backward (``custom_vjp``): recomputes ``rsqrt``/centred rows from the
  **saved f32 stats** ``(mean, var)`` plus the saved compute-dtype ``s``
  instead of re-running the forward reductions, and emits ``dx``;
  ``dscale``/``dbias`` reduce outside the kernel from the same saved
  stats so XLA sees the identical elementwise-then-reduce subgraph the
  unfused backward has (bitwise, and no extra f32 row buffer to spill).

Numerics contract: the kernel body transcribes the *exact* op sequence
JAX autodiff derives for the unfused `LayerNorm` (operand order, the
per-branch ``dmean`` accumulation, the ``-0.5 * rstd / u`` residual) so
f32 loss AND grads are bitwise identical fused vs unfused under jit —
pinned by `tests/test_zz_fusednorm.py`. bf16 compute stays drift-bounded
by the same cast points the unfused path has.

Fallback contract (the PR 13 playbook): `fused_norm_supported` gates on
lane-aligned hidden dims, sublane-aligned row counts and the VMEM budget;
rejected shapes — and ``Model.fused_residual_norm: False`` — keep today's
unfused jnp path, never silence. On CPU the kernel runs in interpreter
mode, so every path is unit-testable without a TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from fleetx_tpu import ops

#: VMEM budget for one row-block's live buffers (x/residual/s/out blocks,
#: the f32 upcast + centred-row temps, stats, double buffering). 4 MiB
#: leaves the ~16 MB core budget comfortable headroom; with the f32 worst
#: case (~28 bytes/element live) an 8-row block admits hidden dims up to
#: ~18k — wider hidden sizes fall back to the unfused path.
_FUSED_NORM_VMEM_BYTES = 4 * 1024 * 1024

#: Live bytes per block element, worst case (f32 in/out): x + residual +
#: s + out blocks plus three f32 temporaries (upcast, centred, product).
_BYTES_PER_ELEMENT = 28

_ROW_BLOCK_CANDIDATES = (512, 256, 128, 64, 32, 16, 8)


def _pick_rows_block(rows: int, hidden: int) -> int:
    """Largest sublane-aligned candidate that tiles ``rows`` and keeps the
    block's live VMEM under `_FUSED_NORM_VMEM_BYTES`. (8-row blocks hold
    for bf16 too: the v5e compiles and computes them right, PERF.md.)"""
    for b in _ROW_BLOCK_CANDIDATES:
        if rows % b == 0 and b * hidden * _BYTES_PER_ELEMENT <= \
                _FUSED_NORM_VMEM_BYTES:
            return b
    return 0


def _whole_array(shape) -> bool:
    """True when the array is one block under the VMEM budget."""
    total = 1
    for d in shape:
        total *= d
    return total * _BYTES_PER_ELEMENT <= _FUSED_NORM_VMEM_BYTES


def fused_norm_supported(x: jax.Array, residual: jax.Array | None = None,
                         *, mesh=None, spec: P | None = None) -> bool:
    """True when the fused kernel applies to this activation shape: hidden
    dim lane-aligned (multiple of 128), the second-minor (seq) dim tiling
    into a sublane-aligned block that fits the VMEM budget, and a float
    compute dtype. Under a multi-device ``mesh`` the shape judged is the
    per-device one ``spec`` leaves (`fused_residual_norm` runs the kernel
    per shard); a dim that does not divide its mesh axes is rejected.
    Shapes this rejects keep the unfused jnp path — today's behavior,
    never silence.

    The kernel blocks the *native-rank* array over its ``-2`` axis
    (leading dims become grid dims) rather than flattening to
    ``[rows, hidden]``: a rank change perturbs XLA's reduce codegen by an
    ulp, which would break the bitwise-f32 contract with the fallback.
    """
    if x.ndim < 2:
        return False
    if residual is not None and (residual.shape != x.shape
                                 or residual.dtype != x.dtype):
        return False
    if x.dtype not in (jnp.float32, jnp.bfloat16, jnp.float16):
        return False
    shape = x.shape
    if mesh is not None and mesh.size > 1:
        spec = spec or P()
        if len(spec) == x.ndim and spec[-1] is not None:
            return False  # the norm reduces over hidden: it stays whole
        shape = ops.local_shape(shape, spec, mesh)
        if shape is None:
            return False
    hidden = shape[-1]
    if hidden < 128 or hidden % 128:
        return False
    if _whole_array(shape):
        return True  # whole array in one block (also the bitwise-pin path)
    return _pick_rows_block(shape[-2], hidden) > 0


def _fwd_kernel(*refs, eps: float, have_residual: bool):
    """One row block: (optional) residual add, f32 LayerNorm, affine, cast.

    Op-for-op the unfused `models/gpt/model.py:LayerNorm` body, so the
    forward is bitwise identical to the fallback in f32.
    """
    if have_residual:
        (x_ref, r_ref, scale_ref, bias_ref,
         out_ref, s_ref, mean_ref, var_ref) = refs
        s = r_ref[...] + x_ref[...]
        s_ref[...] = s
    else:
        x_ref, scale_ref, bias_ref, out_ref, mean_ref, var_ref = refs
        s = x_ref[...]
    x32 = s.astype(jnp.float32)
    mean = x32.mean(-1, keepdims=True)
    var = ((x32 - mean) ** 2).mean(-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    out_ref[...] = (y * scale_ref[...] + bias_ref[...]).astype(out_ref.dtype)
    mean_ref[...] = mean
    var_ref[...] = var


def _bwd_kernel(*refs, eps: float, hidden: int, have_dsin: bool):
    """One row block of the LayerNorm backward from saved ``(mean, var)``.

    Transcribes the exact op sequence JAX autodiff derives for the unfused
    forward (see module docstring): ``rstd``/``u`` recomputed from the
    saved stats reproduce the forward values bitwise, the two ``dxc``
    branches accumulate var-branch-first, the downstream residual-stream
    cotangent ``ds_in`` (when present) joins the accumulation FIRST —
    ``((ds_in + dxc_b) + dxc_a) + dmean_term``, autodiff's ``add_any``
    chain at the residual-sum node — and ``dmean`` sums each branch
    separately before combining. These orderings make f32 grads bitwise
    equal to the fallback. ``dscale``/``dbias`` are *not* computed here:
    the caller re-derives ``y`` from the saved stats with plain jnp ops
    so their reduce sees the same fusion context the unfused graph has.
    """
    if have_dsin:
        (s_ref, scale_ref, mean_ref, var_ref, do_ref, dsin_ref,
         dx_ref) = refs
    else:
        s_ref, scale_ref, mean_ref, var_ref, do_ref, dx_ref = refs
    s32 = s_ref[...].astype(jnp.float32)
    mean = mean_ref[...]
    var = var_ref[...]
    u = var + eps
    rstd = jax.lax.rsqrt(u)
    xc = s32 - mean
    dout = do_ref[...].astype(jnp.float32)
    dy = dout * scale_ref[...].astype(jnp.float32)
    dxc_a = dy * rstd
    drstd = (xc * dy).sum(-1, keepdims=True)
    e_res = -0.5 * (rstd / u)
    f_res = 2.0 * xc
    dxc_b = ((drstd * e_res) / hidden) * f_res
    if have_dsin:
        acc = (dsin_ref[...].astype(jnp.float32) + dxc_b) + dxc_a
    else:
        acc = dxc_b + dxc_a
    dmean = (jnp.negative(dxc_b).sum(-1, keepdims=True)
             + jnp.negative(dxc_a).sum(-1, keepdims=True))
    dx_ref[...] = (acc + dmean / hidden).astype(dx_ref.dtype)


def _specs(shape, hidden):
    """Native-rank BlockSpecs. Keeping the operands at their original
    rank keeps the interpret-mode lowering's op shapes identical to the
    unfused graph's — a flatten-to-``[rows, hidden]`` reshape perturbs
    XLA's reduce codegen by an ulp and breaks the bitwise-f32 contract.

    When the whole array fits the VMEM budget, a single whole-array
    block (grid of one) is used: the kernel body then runs at exactly
    the unfused graph's shapes, which pins every internal reduce's
    codegen too. Larger arrays block the ``-2`` (seq) axis into
    sublane-aligned rows with the leading dims as grid dims."""
    nd = len(shape)
    if _whole_array(shape):
        grid = (1,)
        row_spec = pl.BlockSpec(shape, lambda i: (0,) * nd)
        stat_spec = pl.BlockSpec(shape[:-1] + (1,), lambda i: (0,) * nd)
        vec_spec = pl.BlockSpec((1,) * (nd - 1) + (hidden,),
                                lambda i: (0,) * nd)
        return grid, row_spec, stat_spec, vec_spec
    br = _pick_rows_block(shape[-2], hidden)
    lead = shape[:-2]
    ones = (1,) * len(lead)
    grid = lead + (shape[-2] // br,)
    row_spec = pl.BlockSpec(ones + (br, hidden), lambda *i: (*i, 0))
    stat_spec = pl.BlockSpec(ones + (br, 1), lambda *i: (*i, 0))
    vec_spec = pl.BlockSpec(ones + (1, hidden), lambda *i: (0,) * nd)
    return grid, row_spec, stat_spec, vec_spec


def _fwd_call(x, r, scale_v, bias_v, eps, out_dtype):
    """Dispatch the forward kernel on native-rank operands."""
    shape = x.shape
    hidden = shape[-1]
    stat_shape = shape[:-1] + (1,)
    vec_shape = (1,) * (len(shape) - 1) + (hidden,)
    grid, row_spec, stat_spec, vec_spec = _specs(shape, hidden)
    scale_v = scale_v.astype(jnp.float32).reshape(vec_shape)
    bias_v = bias_v.astype(jnp.float32).reshape(vec_shape)
    have_residual = r is not None
    in_specs = [row_spec] + ([row_spec] if have_residual else []) + \
        [vec_spec, vec_spec]
    out_specs = [row_spec] + ([row_spec] if have_residual else []) + \
        [stat_spec, stat_spec]
    out_shape = [jax.ShapeDtypeStruct(shape, out_dtype)] + \
        ([jax.ShapeDtypeStruct(shape, x.dtype)] if have_residual else []) + \
        [jax.ShapeDtypeStruct(stat_shape, jnp.float32),
         jax.ShapeDtypeStruct(stat_shape, jnp.float32)]
    operands = (x, r, scale_v, bias_v) if have_residual else \
        (x, scale_v, bias_v)
    outs = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, have_residual=have_residual),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=ops.interpret(),
        name="fused_norm_fwd",
    )(*operands)
    if have_residual:
        out, s, mean, var = outs
    else:
        out, mean, var = outs
        s = x
    return out, s, mean, var


def _bwd_call(s, scale_v, mean, var, do, eps, ds_in=None):
    """Dispatch the backward kernel on native-rank operands."""
    shape = s.shape
    hidden = shape[-1]
    vec_shape = (1,) * (len(shape) - 1) + (hidden,)
    grid, row_spec, stat_spec, vec_spec = _specs(shape, hidden)
    scale_v = scale_v.astype(jnp.float32).reshape(vec_shape)
    have_dsin = ds_in is not None
    in_specs = [row_spec, vec_spec, stat_spec, stat_spec, row_spec] + \
        ([row_spec] if have_dsin else [])
    operands = (s, scale_v, mean, var, do) + \
        ((ds_in,) if have_dsin else ())
    dx = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps, hidden=hidden,
                          have_dsin=have_dsin),
        grid=grid,
        in_specs=in_specs,
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct(shape, s.dtype),
        interpret=ops.interpret(),
        name="fused_norm_bwd",
    )(*operands)
    return dx


def _param_grads(s, mean, var, dout, eps, scale_dtype):
    """``dscale``/``dbias`` via the unfused backward's exact subgraph.

    Re-derives ``y`` from the saved ``(s, mean, var)`` with plain jnp ops
    at the cotangent's original shape, so the elementwise-then-reduce
    chain compiles identically to the unfused backward's and stays
    bitwise in f32 (a pallas-emitted ``y`` lands in a different fusion
    context and drifts by an ulp). It is also cheaper: no extra f32 row
    buffer round-trips HBM — the recompute fuses into the reduce.
    """
    lead = tuple(range(dout.ndim - 1))
    y = (s.astype(jnp.float32) - mean) * jax.lax.rsqrt(var + eps)
    dout32 = dout.astype(jnp.float32)
    dscale = (y * dout32).sum(axis=lead).astype(scale_dtype)
    dbias = dout32.sum(axis=lead).astype(scale_dtype)
    return dscale, dbias


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fused_add_norm(x, residual, scale, bias, eps, out_dtype):
    """Primal: ``s = residual + x``; return ``(LN(s).astype(out_dtype), s)``."""
    primal, _ = _fused_add_norm_fwd(x, residual, scale, bias, eps, out_dtype)
    return primal


def _fused_add_norm_fwd(x, residual, scale, bias, eps, out_dtype):
    out, s, mean, var = _fwd_call(x, residual, scale, bias, eps, out_dtype)
    return (out, s), (s, scale, mean, var)


def _fused_add_norm_bwd(eps, out_dtype, res, cts):
    s, scale, mean, var = res
    dout, ds_in = cts
    ds = _bwd_call(s, scale, mean, var, dout, eps, ds_in=ds_in)
    dscale, dbias = _param_grads(s, mean, var, dout, eps, scale.dtype)
    return ds, ds, dscale, dbias


_fused_add_norm.defvjp(_fused_add_norm_fwd, _fused_add_norm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_norm(x, scale, bias, eps, out_dtype):
    """Primal: ``LN(x).astype(out_dtype)`` with no residual add."""
    return _fused_norm_fwd(x, scale, bias, eps, out_dtype)[0]


def _fused_norm_fwd(x, scale, bias, eps, out_dtype):
    out, s, mean, var = _fwd_call(x, None, scale, bias, eps, out_dtype)
    return out, (s, scale, mean, var)


def _fused_norm_bwd(eps, out_dtype, res, cts):
    s, scale, mean, var = res
    dx = _bwd_call(s, scale, mean, var, cts, eps)
    dscale, dbias = _param_grads(s, mean, var, cts, eps, scale.dtype)
    return dx, dscale, dbias


_fused_norm.defvjp(_fused_norm_fwd, _fused_norm_bwd)


def fused_residual_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
                        residual: jax.Array | None = None, *,
                        eps: float = 1e-5,
                        out_dtype=jnp.float32,
                        mesh=None, spec: P | None = None):
    """Fused (residual-add +) f32 LayerNorm + cast; the public entry point.

    Returns ``(out, s)`` where ``s = residual + x`` (or ``x`` when
    ``residual`` is None — the norm-only sites ``ln1``/``ln_f``) and
    ``out = LayerNorm_f32(s).astype(out_dtype)``. Callers must gate on
    `fused_norm_supported` first; this function assumes the shape was
    admitted.

    Under a multi-device ``mesh`` the kernel runs per shard inside a
    ``shard_map`` manual over every mesh axis (GSPMD cannot partition a
    Mosaic call): ``spec`` says how the activations lie over the mesh; the
    op is row-wise, so any sharding of the leading dims is legal, and
    ``scale``/``bias`` enter replicated (their cotangents are summed over
    the mesh by the ``shard_map`` transpose).
    """
    eps = float(eps)
    if residual is None:
        def norm(x, scale, bias):
            return _fused_norm(x, scale, bias, eps, out_dtype)
    else:
        def norm(x, residual, scale, bias):
            return _fused_add_norm(x, residual, scale, bias, eps, out_dtype)
    rows = (x,) if residual is None else (x, residual)
    if mesh is not None and mesh.size > 1:
        spec = spec or P()
        out_specs = spec if residual is None else (spec, spec)
        norm = jax.shard_map(
            norm, mesh=mesh, in_specs=(spec,) * len(rows) + (P(), P()),
            out_specs=out_specs, check_vma=False)
    out = norm(*rows, scale, bias)
    return (out, x) if residual is None else out
