"""The selective scan of a Mamba layer: a linear recurrence over time whose
decay and input depend on the position.

    x, dt [B, T, C]; A [C, N]; Bm, Cm [B, T, N]; D [C]  ->  s [B, T, C]

Per row and channel c, a state h [N] in float32, zero at the row's start:

    h_t = exp(dt_t[c] A[c]) * h_{t-1} + (dt_t[c] x_t[c]) Bm_t
    s_t[c] = <h_t, Cm_t> + D[c] x_t[c]

Two lowerings (`impl`):

- "pallas": ONE kernel call forward and ONE backward (a `custom_vjp`), grid
  (row, channel block, time chunk) with time the sequential axis. A channel
  block is 1,024 channels as one (8, 128) tile a state, so the N states of a
  block are N registers, every product is a whole-tile product, and Bm_t[n],
  Cm_t[n] are scalars read from SMEM: no broadcast along lanes, no
  reduction in the forward pass. The state is carried in VMEM scratch from
  chunk to chunk; the forward saves it at every chunk's end, the backward
  recomputes a chunk's states from the end before it and runs the reverse
  recurrence for dx, d dt, dA, dBm, dCm. dBm and dCm sum over channels: the
  kernel sums a tile's sublanes and leaves the 128 lanes and the channel
  blocks to a plain sum outside. `D x` and its gradients are plain jnp
  around the kernel.
- "chunked": portable. A `lax.scan` over chunks, inside it an associative
  scan over the chunk's positions, each chunk rematerialised in the backward
  pass; plain autodiff. The CPU tests run it, and the kernel is held to it.

None picks "pallas" on a TPU when the shapes fit its tiles (T a multiple of
the chunk, C of 128 in blocks of 8 tiles or as one block), else "chunked".
`with_state_absmax` returns beside s the largest |h| at a chunk's end (no
gradient): the overflow watch of a float32 recurrence.

The kernel tags what it produces and its backward reads (y and the
chunk-end states) with the name `KEPT`: a recompute group keeps values of
that name (`network._KEEP`) and so does not run the scan forward a second
time. Outside such a group the tag is the identity. The portable scan tags
nothing: its backward reads no output of its forward.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu import ops as _ops

KEPT = "ssm.scan"
LANES = 128
SUBLANES = 8
CHUNK = 128                    # positions a grid step, and between saved states
VMEM_LIMIT = 48 * 1024 * 1024  # the backward holds a chunk's states: 8 MiB


def _tiles(c: int):
    """Tiles of 128 channels a block, or None where C does not fit."""
    if c % LANES:
        return None
    tiles = c // LANES
    if tiles % SUBLANES == 0:
        return SUBLANES
    return tiles if tiles < SUBLANES else None


def pallas_fits(t: int, c: int, chunk: int = CHUNK) -> bool:
    return t % chunk == 0 and _tiles(c) is not None


# ---- portable ----

def _chunked(x, dt, a, bm, cm, chunk):
    """-> (y [B, T, C] without the D x term, largest |h| at a chunk's
    end)."""
    b, t, c = x.shape
    chunk = min(chunk, t)
    pad = -t % chunk
    if pad:
        # dt = 0 past the end: decay 1, input 0, the state stands
        x, dt, bm, cm = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                         for v in (x, dt, bm, cm))
    at = a.T[None, None]                              # [1, 1, N, C]

    def combine(left, right):
        (al, bl), (ar, br) = left, right
        return al * ar, ar * bl + br

    @jax.checkpoint
    def one(h, xs):
        xc, dtc, bc, cc = xs                          # [chunk, B, C | N]
        decay = jnp.exp(dtc[:, :, None, :] * at)      # [chunk, B, N, C]
        inp = (dtc * xc)[:, :, None, :] * bc[..., None]
        acum, bcum = lax.associative_scan(combine, (decay, inp), axis=0)
        hs = acum * h[None] + bcum
        y = jnp.sum(hs * cc[..., None], axis=2)
        return hs[-1], (y, jnp.max(jnp.abs(hs[-1])))

    def chunks(v):
        return jnp.moveaxis(v, 1, 0).reshape((t + pad) // chunk, chunk, b, -1)

    _, (y, hmax) = lax.scan(one, jnp.zeros((b, a.shape[1], c), jnp.float32),
                            tuple(chunks(v) for v in (x, dt, bm, cm)))
    y = jnp.moveaxis(y.reshape(t + pad, b, c), 0, 1)[:, :t]
    return y, jnp.max(hmax)


# ---- the kernel ----

def _forward_kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, y_ref, hend_ref,
                    h_scr, *, chunk, n):
    @pl.when(pl.program_id(2) == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = [a_ref[i] for i in range(n)]

    def step(t, h):
        dt_t = dt_ref[t]
        u = dt_t * x_ref[t]
        y = jnp.zeros_like(u)
        new = []
        for i in range(n):
            hi = jnp.exp(dt_t * a[i]) * h[i] + u * b_ref[0, t * n + i]
            y = y + hi * c_ref[0, t * n + i]
            new.append(hi)
        y_ref[t] = y
        return tuple(new)

    h = lax.fori_loop(0, chunk, step, tuple(h_scr[i] for i in range(n)))
    for i in range(n):
        h_scr[i] = h[i]
        hend_ref[i] = h[i]


def _backward_kernel(b_ref, c_ref, x_ref, dt_ref, dy_ref, a_ref, hend_ref,
                     dx_ref, ddt_ref, da_ref, db_ref, dc_ref,
                     hb_scr, g_scr, *, chunk, n):
    """Grid step k works the row's chunk `last - k`; `hend_ref` is the state
    the chunk before it ended in (of the first chunk: any, taken as 0)."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        g_scr[...] = jnp.zeros_like(g_scr)
        da_ref[...] = jnp.zeros_like(da_ref)

    a = [a_ref[i] for i in range(n)]

    def forward(t, h):
        """The chunk's states again: hb_scr[t] is the state BEFORE t."""
        dt_t = dt_ref[t]
        u = dt_t * x_ref[t]
        new = []
        for i in range(n):
            hb_scr[t, i] = h[i]
            new.append(jnp.exp(dt_t * a[i]) * h[i] + u * b_ref[0, t * n + i])
        return tuple(new)

    first = pl.program_id(2) == pl.num_programs(2) - 1
    lax.fori_loop(0, chunk, forward, tuple(
        jnp.where(first, 0.0, hend_ref[i]) for i in range(n)))

    def reverse(j, carry):
        g, da = carry            # dL/dh_t from the positions after t | dA
        t = chunk - 1 - j
        dt_t, x_t, dy_t = dt_ref[t], x_ref[t], dy_ref[t]
        u = dt_t * x_t
        du = jnp.zeros_like(u)
        ddt = jnp.zeros_like(u)
        new_g, new_da = [], []
        for i in range(n):
            bi, ci = b_ref[0, t * n + i], c_ref[0, t * n + i]
            decay = jnp.exp(dt_t * a[i])
            before = hb_scr[t, i]
            gh = g[i] + dy_t * ci
            dc_ref[t, pl.ds(i, 1), :] = jnp.sum(
                dy_t * (decay * before + u * bi), axis=0, keepdims=True)
            db_ref[t, pl.ds(i, 1), :] = jnp.sum(gh * u, axis=0,
                                                keepdims=True)
            du = du + gh * bi
            ddecay = gh * before * decay
            ddt = ddt + ddecay * a[i]
            new_da.append(da[i] + ddecay * dt_t)
            new_g.append(gh * decay)
        dx_ref[t] = du * dt_t
        ddt_ref[t] = ddt + du * x_t
        return tuple(new_g), tuple(new_da)

    g, da = lax.fori_loop(
        0, chunk, reverse,
        (tuple(g_scr[i] for i in range(n)),
         tuple(jnp.zeros_like(a[0]) for _ in range(n))))
    for i in range(n):
        g_scr[i] = g[i]
        da_ref[i] += da[i]


def _grid(b, t, c, chunk):
    """-> (the grid (rows, channel blocks, time chunks), tiles a block)."""
    cs = _tiles(c)
    return (b, c // (cs * LANES), t // chunk), cs


def _tiled(v, b, t, c):
    return v.reshape(b, t, c // LANES, LANES)


def _scalars(v, b, t, n, chunk):
    return v.reshape(b, t // chunk, 1, chunk * n)


def _forward_call(x, dt, a, bm, cm, chunk, interpret):
    """-> (y [B, T, C] float32, the state at every chunk's end
    [B, T / chunk, N, C / 128, 128])."""
    b, t, c = x.shape
    n = a.shape[1]
    grid, cs = _grid(b, t, c, chunk)
    seq = pl.BlockSpec((None, chunk, cs, LANES),
                       lambda i, j, k: (i, k, j, 0))
    smem = pl.BlockSpec((None, None, 1, chunk * n),
                        lambda i, j, k: (i, k, 0, 0),
                        memory_space=pltpu.SMEM)
    y, hend = pl.pallas_call(
        functools.partial(_forward_kernel, chunk=chunk, n=n),
        grid=grid,
        in_specs=[smem, smem, seq, seq,
                  pl.BlockSpec((n, cs, LANES), lambda i, j, k: (0, j, 0))],
        out_specs=[seq, pl.BlockSpec((None, None, n, cs, LANES),
                                     lambda i, j, k: (i, k, 0, j, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, c // LANES, LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, t // chunk, n, c // LANES, LANES),
                                 jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, cs, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="selective_scan_forward",
    )(_scalars(bm, b, t, n, chunk), _scalars(cm, b, t, n, chunk),
      _tiled(x, b, t, c), _tiled(dt, b, t, c),
      a.T.reshape(n, c // LANES, LANES))
    return y.reshape(b, t, c), hend


def _backward_call(x, dt, a, bm, cm, hend, dy, chunk, interpret):
    b, t, c = x.shape
    n = a.shape[1]
    grid, cs = _grid(b, t, c, chunk)
    last = grid[2] - 1
    seq = pl.BlockSpec((None, chunk, cs, LANES),
                       lambda i, j, k: (i, last - k, j, 0))
    smem = pl.BlockSpec((None, None, 1, chunk * n),
                        lambda i, j, k: (i, last - k, 0, 0),
                        memory_space=pltpu.SMEM)
    states = pl.BlockSpec((n, cs, LANES), lambda i, j, k: (0, j, 0))
    partial = pl.BlockSpec((None, None, chunk, n, LANES),
                           lambda i, j, k: (i, j, last - k, 0, 0))
    tiled = jax.ShapeDtypeStruct((b, t, c // LANES, LANES), jnp.float32)
    lanes = jax.ShapeDtypeStruct((b, grid[1], t, n, LANES), jnp.float32)
    dx, ddt, da, db, dc = pl.pallas_call(
        functools.partial(_backward_kernel, chunk=chunk, n=n),
        grid=grid,
        in_specs=[smem, smem, seq, seq, seq, states,
                  pl.BlockSpec((None, None, n, cs, LANES),
                               lambda i, j, k: (
                                   i, jnp.maximum(last - k - 1, 0), 0, j, 0))],
        out_specs=[seq, seq,
                   pl.BlockSpec((None, n, cs, LANES),
                                lambda i, j, k: (i, 0, j, 0)),
                   partial, partial],
        out_shape=[tiled, tiled,
                   jax.ShapeDtypeStruct((b, n, c // LANES, LANES),
                                        jnp.float32),
                   lanes, lanes],
        scratch_shapes=[pltpu.VMEM((chunk, n, cs, LANES), jnp.float32),
                        pltpu.VMEM((n, cs, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name="selective_scan_backward",
    )(_scalars(bm, b, t, n, chunk), _scalars(cm, b, t, n, chunk),
      _tiled(x, b, t, c), _tiled(dt, b, t, c), _tiled(dy, b, t, c),
      a.T.reshape(n, c // LANES, LANES), hend)
    return (dx.reshape(b, t, c), ddt.reshape(b, t, c),
            jnp.sum(da, axis=0).reshape(n, c).T,
            jnp.sum(db, axis=(1, 4)), jnp.sum(dc, axis=(1, 4)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _pallas(x, dt, a, bm, cm, chunk, interpret):
    """Float32 in and out. -> (y without the D x term, largest |h| at a
    chunk's end)."""
    out, saved = _pallas_fwd(x, dt, a, bm, cm, chunk, interpret)
    _ops.note_kept(out[0], saved[-1])           # y and the chunk-end states
    return out


def _pallas_fwd(x, dt, a, bm, cm, chunk, interpret):
    y, hend = (checkpoint_name(v, KEPT) for v in _forward_call(
        x, dt, a, bm, cm, chunk, interpret))
    return (y, jnp.max(jnp.abs(hend))), (x, dt, a, bm, cm, hend)


def _pallas_bwd(chunk, interpret, saved, cotangents):
    return _backward_call(*saved, cotangents[0], chunk, interpret)


_pallas.defvjp(_pallas_fwd, _pallas_bwd)


def selective_scan(x, dt, a, bm, cm, d, *, impl=None, chunk=None,
                   interpret=None, with_state_absmax=False):
    """See the module's docstring. Float32 out whatever x's dtype; `chunk`:
    positions a chunk (default 128)."""
    b, t, c = x.shape
    chunk = chunk or CHUNK
    if impl is None:
        on_tpu = jax.default_backend() == "tpu"
        impl = "pallas" if on_tpu and pallas_fits(t, c, chunk) else "chunked"
    x32 = x.astype(jnp.float32)
    args = (x32, dt.astype(jnp.float32), a.astype(jnp.float32),
            bm.astype(jnp.float32), cm.astype(jnp.float32))
    if impl == "pallas":
        if not pallas_fits(t, c, chunk):
            raise ValueError(
                f"the scan kernel needs T in multiples of the chunk {chunk} "
                f"and C in multiples of {LANES}, as {SUBLANES} tiles a block "
                f"or fewer than {SUBLANES} in all; got T={t}, C={c}")
        y, hmax = _pallas(*args, chunk, _ops.pallas_interpret(interpret))
    elif impl == "chunked":
        y, hmax = _chunked(*args, chunk)
    else:
        raise ValueError(f"unknown scan impl {impl!r}")
    s = y + d.astype(jnp.float32) * x32
    return (s, lax.stop_gradient(hmax)) if with_state_absmax else s
