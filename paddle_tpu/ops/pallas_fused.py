"""Fused BN->ReLU->1x1-conv GEMM kernels with stats epilogues (Mosaic).

The ResNet-50 MFU lever (PERF.md round-3 plan; reference CUDA analogue:
the hand-fused kernels under cuda/src/hl_cuda_cnn.cu). Round-3 profiling
showed convolutions are only 18% of the train step on v5e — the rest is
elementwise BN/ReLU/residual chains (42%) and BN-stats reductions (34%)
that XLA cannot fold into the conv kernels and does not multi-output-fuse.
A 1x1 convolution over NHWC is a GEMM over [N=B*H*W, Cin]; Mosaic lets
us put the whole bottleneck-glue chain inside that GEMM:

  input side:   z = act(u * scale + shift [+ residual])   (the PREVIOUS
                BatchNorm's normalize/affine + ReLU, and optionally the
                residual add) — u is read ONCE, z is never materialized
  matmul:       y = z @ w                                  (MXU, f32 acc)
  output side:  ssum = sum_n y, ssq = sum_n y*y            (the NEXT
                BatchNorm's statistics — no separate passes over y)

plus the custom VJP (two more pass-efficient GEMM kernels: dz/du/dscale/
dshift and dw, both recomputing z from u in registers instead of saving
it).

All shapes are padded row-wise to the block size; a row mask keeps
padding out of y and the statistics. Everything runs in interpret mode
on CPU (tests) and compiled Mosaic on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from paddle_tpu import ops as _ops


def _block_rows(n: int, cin: int, cout: int, itemsize: int = 2) -> int:
    """Row-block size. Two failure modes bound it: too small and the
    grid's per-step fixed cost dominates (measured: bn=512 at
    N=802816/Cin=64 was grid-overhead-bound); too big and the kernel
    blows the 16 MiB scoped-VMEM stack (double-buffered in/out DMA
    blocks plus f32 compute temporaries). `itemsize` is the activation/
    weight dtype width — f32 inputs (non-AMP) double both the resident
    weight block and the row DMA buffers, so the budget shrinks."""
    # resident weight block (double-buffered) comes off the top
    budget = (8 << 20) - 2 * cin * cout * itemsize
    # per-row: in/out DMA blocks (u, du, y, dy at itemsize, x2 double
    # buffering) + f32 temporaries (z/dz/dy_eff)
    per_row = (4 * cin + 4 * cout) * itemsize + 8 * cin + 8 * cout
    for bn in (4096, 2048, 1024, 512, 256, 128, 64, 32, 8):
        if budget <= 0 or bn * per_row > budget or bn > max(n, 8):
            continue
        return bn
    return 8


def _pad_rows(x, bn):
    n = x.shape[0]
    pad = (-n) % bn
    if pad:
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    return x, n + pad


# --------------------------------------------------------------- fwd
def _fwd_kernel(n_valid, relu, has_res):
    def kernel(*refs):
        if has_res:
            u_ref, s_ref, t_ref, w_ref, r_ref, y_ref, s1_ref, s2_ref = refs
        else:
            u_ref, s_ref, t_ref, w_ref, y_ref, s1_ref, s2_ref = refs
        i = pl.program_id(0)
        bn = u_ref.shape[0]
        z = u_ref[...].astype(jnp.float32) * s_ref[...] + t_ref[...]
        if has_res:
            z = z + r_ref[...].astype(jnp.float32)
        if relu:
            z = jnp.maximum(z, 0.0)
        # mask padded rows out of the matmul AND the stats
        rows = lax.broadcasted_iota(jnp.int32, (bn, 1), 0) + i * bn
        z = jnp.where(rows < n_valid, z, 0.0)
        y = jnp.dot(
            z.astype(w_ref.dtype), w_ref[...],
            preferred_element_type=jnp.float32,
        )
        y_ref[...] = y.astype(y_ref.dtype)
        s1 = jnp.sum(y, axis=0, keepdims=True)
        s2 = jnp.sum(y * y, axis=0, keepdims=True)

        @pl.when(i == 0)
        def _init():
            s1_ref[...] = jnp.zeros_like(s1_ref)
            s2_ref[...] = jnp.zeros_like(s2_ref)

        s1_ref[...] += s1
        s2_ref[...] += s2

    return kernel


def _fwd_call(n, n_pad, bn, cin, cout, dtype, relu, has_res, interpret):
    grid = (n_pad // bn,)
    row_spec = pl.BlockSpec((bn, cin), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, cin), lambda i: (0, 0))
    w_spec = pl.BlockSpec((cin, cout), lambda i: (0, 0))
    out_specs = [
        pl.BlockSpec((bn, cout), lambda i: (i, 0)),
        pl.BlockSpec((1, cout), lambda i: (0, 0)),
        pl.BlockSpec((1, cout), lambda i: (0, 0)),
    ]
    in_specs = [row_spec, vec_spec, vec_spec, w_spec]
    if has_res:
        in_specs.append(row_spec)
    return pl.pallas_call(
        _fwd_kernel(n, relu, has_res),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, cout), dtype),
            jax.ShapeDtypeStruct((1, cout), jnp.float32),
            jax.ShapeDtypeStruct((1, cout), jnp.float32),
        ],
        interpret=interpret,
    )


def _fused_fwd_impl(u, scale, shift, w, res, relu):
    n, cin = u.shape
    cout = w.shape[1]
    bn = _block_rows(n, cin, cout, u.dtype.itemsize)
    u_p, n_pad = _pad_rows(u, bn)
    args = [
        u_p,
        scale.reshape(1, cin).astype(jnp.float32),
        shift.reshape(1, cin).astype(jnp.float32),
        # MXU in bf16 under AMP; full precision otherwise (tests)
        w.astype(u.dtype),
    ]
    if res is not None:
        args.append(_pad_rows(res, bn)[0])
    y, s1, s2 = _fwd_call(
        n, n_pad, bn, cin, cout, u.dtype, relu, res is not None,
        _ops.pallas_interpret(),
    )(*args)
    return y[:n], s1[0], s2[0]


# --------------------------------------------------------------- bwd
def _bwd_dx_kernel(n_valid, relu, has_res):
    """du (+dres) + dscale/dshift: reads u, y, dy (+res); recomputes z's
    preactivation sign; dz = dy_eff @ w^T on the MXU."""

    def kernel(*refs):
        if has_res:
            (u_ref, s_ref, t_ref, w_ref, r_ref, y_ref, dy_ref, d1_ref,
             d2_ref, du_ref, dr_ref, ds_ref, dt_ref) = refs
        else:
            (u_ref, s_ref, t_ref, w_ref, y_ref, dy_ref, d1_ref, d2_ref,
             du_ref, ds_ref, dt_ref) = refs
        i = pl.program_id(0)
        bn = u_ref.shape[0]
        y = y_ref[...].astype(jnp.float32)
        dy_eff = dy_ref[...].astype(jnp.float32) + d1_ref[...] \
            + 2.0 * y * d2_ref[...]
        rows = lax.broadcasted_iota(jnp.int32, (bn, 1), 0) + i * bn
        dy_eff = jnp.where(rows < n_valid, dy_eff, 0.0)
        # dz = dy_eff @ w^T — contract over cout without materializing
        # the transpose
        dz = lax.dot_general(
            dy_eff.astype(w_ref.dtype), w_ref[...],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        u = u_ref[...].astype(jnp.float32)
        pre = u * s_ref[...] + t_ref[...]
        if has_res:
            pre = pre + r_ref[...].astype(jnp.float32)
        if relu:
            dz = dz * (pre > 0.0)
        du_ref[...] = (dz * s_ref[...]).astype(du_ref.dtype)
        if has_res:
            dr_ref[...] = dz.astype(dr_ref.dtype)
        ds = jnp.sum(dz * u, axis=0, keepdims=True)
        dt = jnp.sum(dz, axis=0, keepdims=True)

        @pl.when(i == 0)
        def _init():
            ds_ref[...] = jnp.zeros_like(ds_ref)
            dt_ref[...] = jnp.zeros_like(dt_ref)

        ds_ref[...] += ds
        dt_ref[...] += dt

    return kernel


def _bwd_dw_kernel(n_valid, relu, has_res):
    """dw += z^T @ dy_eff, z recomputed from u (never stored)."""

    def kernel(*refs):
        if has_res:
            (u_ref, s_ref, t_ref, r_ref, y_ref, dy_ref, d1_ref, d2_ref,
             dw_ref) = refs
        else:
            (u_ref, s_ref, t_ref, y_ref, dy_ref, d1_ref, d2_ref,
             dw_ref) = refs
        i = pl.program_id(0)
        bn = u_ref.shape[0]
        z = u_ref[...].astype(jnp.float32) * s_ref[...] + t_ref[...]
        if has_res:
            z = z + r_ref[...].astype(jnp.float32)
        if relu:
            z = jnp.maximum(z, 0.0)
        rows = lax.broadcasted_iota(jnp.int32, (bn, 1), 0) + i * bn
        z = jnp.where(rows < n_valid, z, 0.0)
        y = y_ref[...].astype(jnp.float32)
        dy_eff = dy_ref[...].astype(jnp.float32) + d1_ref[...] \
            + 2.0 * y * d2_ref[...]
        mxu = u_ref.dtype
        dw = lax.dot_general(
            z.astype(mxu), dy_eff.astype(mxu),
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

        @pl.when(i == 0)
        def _init():
            dw_ref[...] = jnp.zeros_like(dw_ref)

        dw_ref[...] += dw

    return kernel


def _bwd_impl(relu, has_res, residuals, cotangents):
    u, scale, shift, w, res, y = residuals
    dy, d1, d2 = cotangents
    n, cin = u.shape
    cout = w.shape[1]
    bn = _block_rows(n, cin, cout, u.dtype.itemsize)
    u_p, n_pad = _pad_rows(u, bn)
    y_p, _ = _pad_rows(y, bn)
    dy_p, _ = _pad_rows(dy, bn)
    grid = (n_pad // bn,)
    interpret = _ops.pallas_interpret()
    s2d = scale.reshape(1, cin).astype(jnp.float32)
    t2d = shift.reshape(1, cin).astype(jnp.float32)
    d1_2d = d1.reshape(1, cout).astype(jnp.float32)
    d2_2d = d2.reshape(1, cout).astype(jnp.float32)

    urow = pl.BlockSpec((bn, cin), lambda i: (i, 0))
    yrow = pl.BlockSpec((bn, cout), lambda i: (i, 0))
    cvec = pl.BlockSpec((1, cin), lambda i: (0, 0))
    ovec = pl.BlockSpec((1, cout), lambda i: (0, 0))
    wspec = pl.BlockSpec((cin, cout), lambda i: (0, 0))

    in_specs = [urow, cvec, cvec, wspec]
    args = [u_p, s2d, t2d, w.astype(u.dtype)]
    if has_res:
        in_specs.append(urow)
        args.append(_pad_rows(res, bn)[0])
    in_specs += [yrow, yrow, ovec, ovec]
    args += [y_p, dy_p, d1_2d, d2_2d]

    out_specs = [urow]
    out_shape = [jax.ShapeDtypeStruct((n_pad, cin), u.dtype)]
    if has_res:
        out_specs.append(urow)
        out_shape.append(jax.ShapeDtypeStruct((n_pad, cin), u.dtype))
    out_specs += [cvec, cvec]
    out_shape += [
        jax.ShapeDtypeStruct((1, cin), jnp.float32),
        jax.ShapeDtypeStruct((1, cin), jnp.float32),
    ]
    outs = pl.pallas_call(
        _bwd_dx_kernel(n, relu, has_res),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*args)
    if has_res:
        du, dres, ds, dt = outs
        dres = dres[:n]
    else:
        du, ds, dt = outs
        dres = None
    du = du[:n]

    dw_in_specs = [urow, cvec, cvec]
    dw_args = [u_p, s2d, t2d]
    if has_res:
        dw_in_specs.append(urow)
        dw_args.append(_pad_rows(res, bn)[0])
    dw_in_specs += [yrow, yrow, ovec, ovec]
    dw_args += [y_p, dy_p, d1_2d, d2_2d]
    (dw,) = pl.pallas_call(
        _bwd_dw_kernel(n, relu, has_res),
        grid=grid,
        in_specs=dw_in_specs,
        out_specs=[pl.BlockSpec((cin, cout), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((cin, cout), jnp.float32)],
        interpret=interpret,
    )(*dw_args)
    return (
        du,
        ds[0].astype(scale.dtype),
        dt[0].astype(shift.dtype),
        dw.astype(w.dtype),
        dres,
    )


# ------------------------------------------------------- public op
@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _fused_core(u, scale, shift, w, res, relu):
    y, s1, s2 = _fused_fwd_impl(u, scale, shift, w, res, relu)
    return y, s1, s2


def _fused_core_fwd(u, scale, shift, w, res, relu):
    y, s1, s2 = _fused_fwd_impl(u, scale, shift, w, res, relu)
    return (y, s1, s2), (u, scale, shift, w, res, y)


def _fused_core_bwd(relu, residuals, cts):
    res = residuals[4]
    du, ds, dt, dw, dres = _bwd_impl(
        relu, res is not None, residuals, cts
    )
    return du, ds, dt, dw, dres


_fused_core.defvjp(_fused_core_fwd, _fused_core_bwd)


def bn_act_conv1x1(u, scale, shift, w, residual=None, act="relu"):
    """y, ssum, ssq = act(u*scale + shift [+ residual]) @ w with the
    output statistics accumulated in the kernel's epilogue.

    u: [N, Cin] (bf16 or f32); scale/shift: [Cin] (the previous BN's
    folded affine — pass ones/zeros for a plain conv+stats);
    w: [Cin, Cout]; residual: optional [N, Cin] added before the
    activation. act: "relu" or "" (linear). Differentiable in
    u/scale/shift/w/residual (custom VJP — two fused backward GEMMs).
    Returns y [N, Cout] in u's dtype, ssum/ssq [Cout] f32."""
    assert act in ("relu", ""), act
    return _fused_core(u, scale, shift, w, residual, act == "relu")
