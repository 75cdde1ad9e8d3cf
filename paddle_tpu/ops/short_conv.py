"""The gated short convolution's mixing (the `short_conv` layer of LFM2):

    [B | C | x] = bcx;  z = B * x
    s_t = sum_j w[:, j] z_{t - (L - 1) + j}, zeros before a row's start
    y = C * s

`mix(bcx, conv_w)` takes `bcx` [B, T, 3 D] as the layer's input product
gives it and returns y [B, T, D] in bcx's dtype beside the float32 max |y|
(the gauge `conv.mix_absmax`: a max is exact, so both ways read the same
number), two ways (`impl`):

- "pass": ONE Pallas pass, bcx's dtype in and out and float32 inside. A grid
  step takes a block of positions of one row across all of bcx's lanes (B,
  C and x are lane slices of it at 0, D and 2 D), and through second
  BlockSpecs the `HALO` positions of B and x before the block (zeros at a
  row's start), so that both grid axes stay parallel. A `fori_loop` goes
  over pieces of lanes; the taps are sublane rotations in VMEM. It writes y
  and, lane by lane, max |y| over the block. Its backward (a `custom_vjp`
  whose residuals are bcx and conv_w, what the plain backward reads) is one
  pass too: the cotangent dy, bcx's block, the positions before it (B, x)
  and after it (C and dy; zeros past a row's end); g = C * dy, dC = dy * s
  (s computed again), dz_t = sum_j w[:, j] g_{t + (L - 1) - j}, dB = dz * x,
  dx = dz * B, written as ONE [B, T, 3 D] array in bcx's dtype, and conv_w's
  gradient as float32 partial sums a block, summed outside.
- "plain": that composition in float32 XLA operations (`causal_depthwise`,
  the helper the `mamba` layer's convolution calls too), which the compiler
  writes to HBM piece by piece in float32: the portable path and the
  reference.

The two round alike (bcx as given, y and the gradient of bcx rounded to
bcx's dtype, float32 between) and differ only in the order of float32 sums.
None picks "pass" on a TPU where the shapes fit its blocks (`pass_fits`),
else "plain". Every call counts into `conv.mix_calls` by `path`.

What tracing and lowering a step costs is held down on purpose: the pass's
two Pallas calls sit in module-level `jax.jit` functions of hashable static
arguments, so that every layer of one shape, and a recomputed forward, share
ONE trace and ONE lowering of each kernel; and a kernel body loops over its
pieces instead of unrolling them, so that its size does not grow with the
block or the width.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu import ops as _ops

LANES = 128
# positions read before a block (after it, backward): the taps reach L - 1
# back, so the pass takes L up to HALO + 1
HALO = 8
# positions a grid step: the largest lane multiple up to PASS_ROWS that
# divides T and keeps a block of bcx within PASS_BLOCK_BYTES; lanes computed
# at once, a piece (on a v5e at 2 x 8,192 x 3 x 2,048 in bfloat16, blocks of
# 256 and 512 positions by pieces of 256 to 1,024 lanes ran within 1.5% of
# each other: the forward 0.410 to 0.418 ms, 643 to 654 GB/s, the backward
# 0.742 to 0.752 ms, 625 to 633 GB/s, of the HBM's 819)
PASS_ROWS = 512
PASS_BLOCK_BYTES = 3 << 20
LANE_PIECE = 512
VMEM_LIMIT = 48 << 20


def causal_depthwise(x, w, b=None):
    """A causal depthwise convolution over time, float32: x [B, T, C], w
    [C, K] -> s_t = b + sum_j w[:, j] x_{t - (K - 1) + j}, zeros before a
    row's start."""
    k, t = w.shape[-1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0))).astype(jnp.float32)
    w = w.astype(jnp.float32)
    acc = sum(w[:, j] * padded[:, j: j + t] for j in range(k))
    return acc if b is None else b + acc


def pass_fits(t: int, d: int, taps: int) -> bool:
    """The pass's blocks: positions and the width D in lane multiples, and
    taps that reach no further back than `HALO`."""
    return d % LANES == 0 and t % LANES == 0 and 1 <= taps <= HALO + 1


def picks_pass(t: int, d: int, taps: int) -> bool:
    """What a call that names no `impl` takes: the pass on a TPU where its
    blocks fit."""
    return jax.default_backend() == "tpu" and pass_fits(t, d, taps)


def note_path(path: str) -> None:
    """`conv.mix_calls` by `path` ("pass" or "plain"), raised once a TRACED
    call, read by nobody on the step's path."""
    from paddle_tpu import obs

    obs.get_registry().counter("conv.mix_calls").inc(1, path=path)


def _rows(t: int, d: int, itemsize: int) -> int:
    """Positions a grid step (see `PASS_ROWS`)."""
    fit = max(LANES, PASS_BLOCK_BYTES // (3 * d * itemsize))
    rows = min(PASS_ROWS, t, fit) // LANES * LANES
    while t % rows:
        rows -= LANES
    return rows


def _piece(d: int) -> int:
    """Lanes a piece: the largest lane multiple up to `LANE_PIECE` that
    divides d."""
    width = min(LANE_PIECE, d)
    while d % width:
        width -= LANES
    return width


def _each_piece(d: int, body) -> None:
    """body(first lane, lanes) over the pieces of d lanes, as a loop."""
    width = _piece(d)

    def one(i, carry):
        body(pl.multiple_of(i * width, LANES), width)
        return carry

    lax.fori_loop(0, d // width, one, 0)


def _read(ref, start, width):
    """ref[0, :, start: start + width] (start a lane multiple), float32."""
    return ref[0, :, pl.ds(pl.multiple_of(start, LANES), width)].astype(
        jnp.float32)


def _later(v, before, taps):
    """What each tap reads, j = 0 .. L - 1: v moved L - 1 - j positions
    later (row r holds v[r - k]), the first rows from `before` [HALO, n],
    the positions just before v's."""
    both = jnp.concatenate([before, v], 0)
    return [pltpu.roll(both, k, 0)[HALO:] if k else v
            for k in range(taps - 1, -1, -1)]


def _earlier(v, after, taps):
    """What each tap's transpose reads, j = 0 .. L - 1: v moved L - 1 - j
    positions earlier (row r holds v[r + k]), the last rows from `after`
    [HALO, n], the positions just after v's."""
    n = v.shape[0]
    both = jnp.concatenate([v, after], 0)
    return [pltpu.roll(both, n + HALO - k, 0)[:n] if k else v
            for k in range(taps - 1, -1, -1)]


def _weighted(w_ref, lanes, seen):
    """sum_j w[j] seen[j], summed from j = 0 as the plain composition sums."""
    s = w_ref[0:1, lanes] * seen[0]
    for j in range(1, len(seen)):
        s = s + w_ref[j: j + 1, lanes] * seen[j]
    return s


def _mix_kernel(bcx_ref, b_before_ref, x_before_ref, w_ref, y_ref, peak_ref):
    """One block of positions of one row: y = C * s, and max |y| over the
    block's positions, lane by lane."""
    d = y_ref.shape[-1]
    first = pl.program_id(1) == 0

    def piece(at, width):
        lanes = pl.ds(at, width)
        before = jnp.where(first, 0.0, _read(b_before_ref, at, width)
                           * _read(x_before_ref, at, width))
        z = _read(bcx_ref, at, width) * _read(bcx_ref, 2 * d + at, width)
        s = _weighted(w_ref, lanes, _later(z, before, w_ref.shape[0]))
        y = _read(bcx_ref, d + at, width) * s
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)
        peak_ref[0, 0, :, lanes] = jnp.max(jnp.abs(y), axis=0, keepdims=True)

    _each_piece(d, piece)


def _mix_bwd_kernel(bcx_ref, b_before_ref, x_before_ref, c_after_ref, g_ref,
                    g_after_ref, w_ref, dbcx_ref, dw_ref):
    """One block of positions of one row: the gradients of B, C and x into
    the block of ONE [B, T, 3 D] array, and the block's share of conv_w's."""
    d, taps = g_ref.shape[-1], w_ref.shape[0]
    i = pl.program_id(1)
    first, last = i == 0, i == pl.num_programs(1) - 1
    out = dbcx_ref.dtype

    def piece(at, width):
        lanes = pl.ds(at, width)
        b = _read(bcx_ref, at, width)
        x = _read(bcx_ref, 2 * d + at, width)
        before = jnp.where(first, 0.0, _read(b_before_ref, at, width)
                           * _read(x_before_ref, at, width))
        seen = _later(b * x, before, taps)
        dy = _read(g_ref, at, width)
        g = dy * _read(bcx_ref, d + at, width)
        after = jnp.where(last, 0.0, _read(c_after_ref, at, width)
                          * _read(g_after_ref, at, width))
        dz = _weighted(w_ref, lanes, _earlier(g, after, taps))
        for p, grad in enumerate((dz * x, dy * _weighted(w_ref, lanes, seen),
                                  dz * b)):
            dbcx_ref[0, :, pl.ds(pl.multiple_of(p * d + at, LANES),
                                 width)] = grad.astype(out)
        for j in range(taps):
            dw_ref[0, 0, j: j + 1, lanes] = jnp.sum(seen[j] * g, axis=0,
                                                    keepdims=True)

    _each_piece(d, piece)


def _blockspecs(t, d, rows):
    """On the grid (row, block of positions): a block of an array's
    positions across all its lanes; and slabs of HALO positions of lane
    block p (D wide) just before and just after it, clamped at a row's ends,
    where the kernel reads zeros instead."""
    slabs, last = rows // HALO, t // HALO - 1

    def block(width):
        return pl.BlockSpec((1, rows, width), lambda b, i: (b, i, 0))

    def before(p):
        return pl.BlockSpec((1, HALO, d), lambda b, i: (
            b, jnp.maximum(i * slabs - 1, 0), p))

    def after(p):
        return pl.BlockSpec((1, HALO, d), lambda b, i: (
            b, jnp.minimum((i + 1) * slabs, last), p))

    return block, before, after


def _params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"),
                                vmem_limit_bytes=VMEM_LIMIT)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _forward(bcx, conv_w, rows, interpret):
    """The forward pass: bcx [B, T, 3 D], conv_w [D, L] -> y [B, T, D] in
    bcx's dtype, and the float32 max |y|."""
    b, t, width = bcx.shape
    d, taps = width // 3, conv_w.shape[-1]
    block, before, _ = _blockspecs(t, d, rows)
    y, peaks = pl.pallas_call(
        _mix_kernel,
        grid=(b, t // rows),
        in_specs=[block(3 * d), before(0), before(2),
                  pl.BlockSpec((taps, d), lambda b, i: (0, 0))],
        out_specs=[block(d),
                   pl.BlockSpec((1, 1, 1, d), lambda b, i: (b, i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, t, d), bcx.dtype),
                   jax.ShapeDtypeStruct((b, t // rows, 1, d), jnp.float32)],
        compiler_params=_params(),
        cost_estimate=pl.CostEstimate(
            flops=(2 * taps + 2) * b * t * d, transcendentals=0,
            bytes_accessed=4 * b * t * d * bcx.dtype.itemsize),
        interpret=interpret,
        name="short_conv_mix",
    )(bcx, bcx, bcx, conv_w.astype(jnp.float32).T)
    return y, jnp.max(peaks)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _backward(bcx, conv_w, dy, rows, interpret):
    """The backward pass: -> the gradient of bcx [B, T, 3 D] in bcx's dtype
    and conv_w's in its own."""
    b, t, width = bcx.shape
    d, taps = width // 3, conv_w.shape[-1]
    block, before, after = _blockspecs(t, d, rows)
    dbcx, dw = pl.pallas_call(
        _mix_bwd_kernel,
        grid=(b, t // rows),
        in_specs=[block(3 * d), before(0), before(2), after(1), block(d),
                  after(0), pl.BlockSpec((taps, d), lambda b, i: (0, 0))],
        out_specs=[block(3 * d),
                   pl.BlockSpec((1, 1, taps, d), lambda b, i: (b, i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(bcx.shape, bcx.dtype),
                   jax.ShapeDtypeStruct((b, t // rows, taps, d),
                                        jnp.float32)],
        compiler_params=_params(),
        cost_estimate=pl.CostEstimate(
            flops=(6 * taps + 6) * b * t * d, transcendentals=0,
            bytes_accessed=7 * b * t * d * bcx.dtype.itemsize),
        interpret=interpret,
        name="short_conv_mix_bwd",
    )(bcx, bcx, bcx, bcx, dy, dy, conv_w.astype(jnp.float32).T)
    return dbcx, jnp.sum(dw, axis=(0, 1)).T.astype(conv_w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _pass(bcx, conv_w, rows, interpret):
    return _forward(bcx, conv_w, rows, interpret)


def _pass_fwd(bcx, conv_w, rows, interpret):
    return _forward(bcx, conv_w, rows, interpret), (bcx, conv_w)


def _pass_bwd(rows, interpret, residuals, cotangents):
    bcx, conv_w = residuals
    dy, _ = cotangents      # the gauge's: it is read stop-gradient
    return _backward(bcx, conv_w, dy, rows, interpret)


_pass.defvjp(_pass_fwd, _pass_bwd)


def mix(bcx, conv_w, *, impl=None):
    """bcx [B, T, 3 D] (the input product [B | C | x]), conv_w [D, L] ->
    (y = C * s [B, T, D] in bcx's dtype, the float32 max |y|). See the
    module's docstring for `impl`."""
    _, t, width = bcx.shape
    d, taps = width // 3, conv_w.shape[-1]
    if impl is None:
        impl = "pass" if picks_pass(t, d, taps) else "plain"
    note_path(impl)
    if impl == "pass":
        if not pass_fits(t, d, taps):
            raise ValueError(
                f"the short convolution's pass needs T and D in multiples of "
                f"{LANES} and at most {HALO + 1} taps; got T={t}, D={d}, "
                f"L={taps}")
        return _pass(bcx, conv_w, _rows(t, d, bcx.dtype.itemsize),
                     _ops.pallas_interpret())
    if impl == "plain":
        f32 = bcx.astype(jnp.float32)
        s = causal_depthwise(f32[..., :d] * f32[..., 2 * d:], conv_w)
        y = f32[..., d: 2 * d] * s
        return y.astype(bcx.dtype), jnp.max(jnp.abs(y))
    raise ValueError(f"unknown short convolution impl {impl!r}")
