"""Fused LSTM/GRU sequence kernels in Pallas.

TPU-native equivalent of the reference's fused recurrent CUDA cells
(cuda/src/hl_cuda_lstm.cu, cuda/include/hl_gpu_gru.cuh): the whole time
loop runs inside ONE kernel, with hidden/cell state pinned in VMEM and the
per-step recurrent matmul on the MXU — no HBM round-trip of h/c/gate
intermediates between steps, which is what the XLA `lax.scan` lowering
pays for.

Numerics match the `lax.scan` reference implementations (`lstm_ref`,
`gru_ref`) exactly — masked-carry semantics included: at padded timesteps
the state carries through unchanged and the output is zeroed (the
SequenceToBatch contract, gserver/layers/SequenceToBatch.h).

Layout: the grid is (batch blocks, time blocks); time blocks stream
through VMEM (double-buffered by the Pallas pipeline) while the h/c
carry lives in VMEM scratch across the whole time sweep, so VMEM usage
is O(bb·tb·h) regardless of sequence length. Batch and time are padded
to multiples of 8 (Mosaic's sublane constraint); padded rows/steps are
masked out by the length mask, so padding is numerically free.

Backward (LSTM): a REVERSE-time Pallas kernel (`_lstm_bwd_kernel`) —
time blocks visited back-to-front via the index map, gates recomputed
from the saved y/c sequences (one extra matmul per step, the standard
memory/FLOP trade), dW/db accumulated across the whole grid in resident
output blocks. GRU backward still recomputes through the scan reference.

When the plan does not fit VMEM (forward: w alone is h·4h floats;
backward keeps w AND the dW accumulator resident, so it falls back
earlier, around h~512-700) the
fused path falls back to `lax.scan` — at that size the per-step matmul
is MXU-bound anyway, which is exactly when the fusion win vanishes.

Gate orders match the layer/bias layouts in layers/recurrent.py:
LSTM [i, f, g, o] with peepholes (wci, wcf, wco); GRU [u, r | c].
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu import ops as _ops

logger = logging.getLogger(__name__)

_VMEM_BUDGET = 10 * 1024 * 1024  # soft planning budget (VMEM is ~16MB)
# the backward keeps BOTH w and the resident dW accumulator in VMEM
# (h=512: 2 x 4MB) — give it a larger share so h=512 training stays on
# the kernel path; Mosaic still owns the hard limit
_VMEM_BUDGET_BWD = 13 * 1024 * 1024


def _round8(n: int) -> int:
    return -(-n // 8) * 8


_FALLBACKS_SAID: set = set()


def _fell_back(kernel: str, why: str, x) -> None:
    """A caller asked for `kernel` and gets the lax.scan reference.
    Counted every time in obs (`pallas_rnn.fallbacks`, label
    `kernel`) and logged once per (kernel, shape). It runs while the
    program is traced, so the count is of traced programs, not of
    steps — a run whose counter moved did not run that kernel."""
    from paddle_tpu import obs

    obs.get_registry().counter("pallas_rnn.fallbacks").inc(kernel=kernel)
    key = (kernel, tuple(x.shape), str(x.dtype))
    if key not in _FALLBACKS_SAID:
        _FALLBACKS_SAID.add(key)
        logger.warning(
            "pallas_rnn: %s was asked for at x=%s %s and is NOT used "
            "(%s); the lax.scan reference runs instead",
            kernel, tuple(x.shape), x.dtype, why,
        )


def _ref_like_kernel(ref, x, *rest):
    """The scan reference under the kernels' dtype rule: compute in
    float32, return x's dtype. The bare references need one dtype
    throughout (bf16 activations against f32 weights break the scan
    carry), which the kernels never did."""
    *ws, lens = rest
    f32 = jnp.float32
    y = ref(x.astype(f32), *(w.astype(f32) for w in ws), lens)
    return y.astype(x.dtype)


def _plan(b: int, t: int, h: int, tok_bytes: int, fixed_bytes: int,
          budget: int = None):
    """Choose (bb, tb, Bp, Tp): batch block, time block, padded dims.

    Constraints (Mosaic): bb and tb multiples of 8 (or the full padded
    dim). Preference: the largest bb (per-step recurrent matmul is
    [bb, h] @ [h, 4h] — more rows, better MXU utilization), then the
    largest tb (fewer grid steps). Returns None if even the minimal
    block overflows the budget (weights too big for VMEM -> caller
    falls back to the scan path)."""
    if budget is None:  # resolved at call time (tests patch the global)
        budget = _VMEM_BUDGET
    bp = _round8(b)
    t8 = _round8(t)
    tb_options = [t8] + [x for x in (256, 128, 64, 32, 16, 8) if x < t8]
    bb_options = [bb for bb in range(bp, 7, -8) if bp % bb == 0]
    for bb in bb_options:
        for tb in tb_options:
            if fixed_bytes + bb * tb * tok_bytes <= budget:
                tp = -(-t // tb) * tb
                return bb, tb, bp, tp
    return None


def _pad_bt(x, bp, tp):
    """Zero-pad [B, T, ...] to [Bp, Tp, ...]."""
    pads = [(0, bp - x.shape[0]), (0, tp - x.shape[1])]
    pads += [(0, 0)] * (x.ndim - 2)
    if bp == x.shape[0] and tp == x.shape[1]:
        return x
    return jnp.pad(x, pads)


# ---------------------------------------------------------------- LSTM

def lstm_ref(x, w, gb, wci, wcf, wco, lens):
    """Reference scan. x: [B,T,4h] pre-projected input; w: [h,4h];
    gb: [4h]; peepholes [h] each; lens: [B] int32. Returns y [B,T,h]."""
    h = w.shape[0]
    t_max = x.shape[1]
    mask = (
        jnp.arange(t_max, dtype=jnp.int32)[None, :] < lens[:, None]
    ).astype(x.dtype)

    def step(carry, inp):
        h_prev, c_prev = carry
        x_t, m_t = inp
        g = x_t + jnp.dot(h_prev, w) + gb
        gi, gf, gg, go = jnp.split(g, 4, axis=-1)
        i = jax.nn.sigmoid(gi + wci * c_prev)
        f = jax.nn.sigmoid(gf + wcf * c_prev)
        cand = jnp.tanh(gg)
        c = f * c_prev + i * cand
        o = jax.nn.sigmoid(go + wco * c)
        out = o * jnp.tanh(c)
        m = m_t[:, None]
        h_new = m * out + (1 - m) * h_prev
        c_new = m * c + (1 - m) * c_prev
        return (h_new, c_new), out * m

    bsz = x.shape[0]
    z = jnp.zeros((bsz, h), x.dtype)
    _, ys = lax.scan(
        step, (z, z), (x.swapaxes(0, 1), mask.swapaxes(0, 1))
    )
    return ys.swapaxes(0, 1)


def _make_lstm_fwd_kernel(emit_c: bool):
    """One (batch block, time block) step. Carries h/c in VMEM scratch
    across the time sweep; emits the masked output y and (training
    only) the carried cell sequence c for the backward kernel —
    inference skips the c store to halve output HBM traffic."""

    def kernel(x_ref, w_ref, b_ref, lens_ref, y_ref, *rest):
        if emit_c:
            c_ref, h_scr, c_scr = rest
        else:
            h_scr, c_scr = rest
        bb, tb, h4 = x_ref.shape
        h = h4 // 4
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            h_scr[:] = jnp.zeros_like(h_scr)
            c_scr[:] = jnp.zeros_like(c_scr)

        gb = b_ref[0, : 4 * h]
        wci = b_ref[0, 4 * h : 5 * h]
        wcf = b_ref[0, 5 * h : 6 * h]
        wco = b_ref[0, 6 * h : 7 * h]
        lens = lens_ref[:, 0]
        t0 = j * tb

        def body(tt, _):
            x_t = x_ref[:, tt, :]
            h_prev = h_scr[:]
            c_prev = c_scr[:]
            g = (
                x_t
                + jnp.dot(
                    h_prev, w_ref[:], preferred_element_type=jnp.float32
                )
                + gb
            )
            i = jax.nn.sigmoid(g[:, :h] + wci * c_prev)
            f = jax.nn.sigmoid(g[:, h : 2 * h] + wcf * c_prev)
            cand = jnp.tanh(g[:, 2 * h : 3 * h])
            c = f * c_prev + i * cand
            o = jax.nn.sigmoid(g[:, 3 * h :] + wco * c)
            out = o * jnp.tanh(c)
            m = (t0 + tt < lens).astype(jnp.float32)[:, None]
            h_scr[:] = m * out + (1 - m) * h_prev
            c_scr[:] = m * c + (1 - m) * c_prev
            y_ref[:, tt, :] = (out * m).astype(y_ref.dtype)
            if emit_c:
                c_ref[:, tt, :] = c_scr[:].astype(c_ref.dtype)
            return 0

        lax.fori_loop(0, tb, body, 0)

    return kernel


_lstm_fwd_kernel = _make_lstm_fwd_kernel(emit_c=True)
_lstm_fwd_kernel_noc = _make_lstm_fwd_kernel(emit_c=False)


def _lstm_bwd_kernel(
    x_ref, w_ref, b_ref, lens_ref, y_ref, yp_ref, c_ref, cp_ref, dy_ref,
    dx_ref, dw_ref, db_ref, dh_scr, dc_scr, dg_scr, hp_scr, db_scr,
):
    """Reverse-time LSTM backward. Grid blocks arrive back-to-front in
    time (see the reversed index maps); within a block, steps run in
    reverse. Gates are recomputed from x and the saved y/c sequences.
    yp/cp are the PREVIOUS time block of y/c (their last row supplies
    h_{t-1}/c_{t-1} at the block boundary). dW/db accumulate into
    resident output blocks across the whole grid."""
    bb, tb, h4 = x_ref.shape
    h = h4 // 4
    i_blk = pl.program_id(0)
    j = pl.program_id(1)
    nt = pl.num_programs(1)
    # reversed sweep: this grid step handles time block k = nt-1-j
    k = nt - 1 - j
    t0 = k * tb

    @pl.when(j == 0)
    def _init_carry():
        dh_scr[:] = jnp.zeros_like(dh_scr)
        dc_scr[:] = jnp.zeros_like(dc_scr)

    @pl.when((i_blk == 0) & (j == 0))
    def _init_outs():
        dw_ref[:] = jnp.zeros_like(dw_ref)
        db_ref[:] = jnp.zeros_like(db_ref)

    db_scr[:] = jnp.zeros_like(db_scr)

    gb = b_ref[0, : 4 * h]
    wci = b_ref[0, 4 * h : 5 * h]
    wcf = b_ref[0, 5 * h : 6 * h]
    wco = b_ref[0, 6 * h : 7 * h]
    lens = lens_ref[:, 0]
    w = w_ref[:]

    def body(s, _):
        tt = tb - 1 - s
        t = t0 + tt
        m = (t < lens).astype(jnp.float32)[:, None]
        first = t == 0
        # h_{t-1}, c_{t-1}: previous row of this block, or the last row
        # of the previous time block at the boundary, or zeros at t=0
        tt_prev = jnp.maximum(tt - 1, 0)
        in_blk = (tt > 0).astype(jnp.float32)
        h_prev_blk = y_ref[:, tt_prev, :]
        c_prev_blk = c_ref[:, tt_prev, :]
        h_prev_edge = yp_ref[:, tb - 1, :]
        c_prev_edge = cp_ref[:, tb - 1, :]
        zero = jnp.float32(0.0)
        live = jnp.where(first, zero, 1.0)
        h_prev = live * (
            in_blk * h_prev_blk + (1 - in_blk) * h_prev_edge
        )
        c_prev = live * (
            in_blk * c_prev_blk + (1 - in_blk) * c_prev_edge
        )
        # recompute the forward cell (valid wherever m = 1)
        g = (
            x_ref[:, tt, :]
            + jnp.dot(h_prev, w, preferred_element_type=jnp.float32)
            + gb
        )
        ig = jax.nn.sigmoid(g[:, :h] + wci * c_prev)
        fg = jax.nn.sigmoid(g[:, h : 2 * h] + wcf * c_prev)
        cand = jnp.tanh(g[:, 2 * h : 3 * h])
        c_t = fg * c_prev + ig * cand
        og = jax.nn.sigmoid(g[:, 3 * h :] + wco * c_t)
        tanh_c = jnp.tanh(c_t)
        # backward through the step
        dh_in = dh_scr[:]
        dc_in = dc_scr[:]
        dout = m * (dh_in + dy_ref[:, tt, :])
        dg_o = dout * tanh_c * og * (1 - og)
        dc_tot = m * dc_in + dout * og * (1 - tanh_c * tanh_c) + dg_o * wco
        dg_i = dc_tot * cand * ig * (1 - ig)
        dg_f = dc_tot * c_prev * fg * (1 - fg)
        dg_g = dc_tot * ig * (1 - cand * cand)
        dg = jnp.concatenate([dg_i, dg_f, dg_g, dg_o], axis=-1)
        dx_ref[:, tt, :] = dg.astype(dx_ref.dtype)
        dg_scr[:, tt, :] = dg
        hp_scr[:, tt, :] = h_prev
        # carries for step t-1
        dh_scr[:] = (1 - m) * dh_in + lax.dot_general(
            dg, w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dc_scr[:] = dc_tot * fg + dg_i * wci + dg_f * wcf + (1 - m) * dc_in
        # bias + peephole partials for this step
        db_scr[0, : 4 * h] += jnp.sum(dg, axis=0)
        db_scr[0, 4 * h : 5 * h] += jnp.sum(dg_i * c_prev, axis=0)
        db_scr[0, 5 * h : 6 * h] += jnp.sum(dg_f * c_prev, axis=0)
        db_scr[0, 6 * h : 7 * h] += jnp.sum(dg_o * c_t, axis=0)
        return 0

    lax.fori_loop(0, tb, body, 0)
    # block-level reductions into the resident outputs
    hp2 = hp_scr[:].reshape(bb * tb, h)
    dg2 = dg_scr[:].reshape(bb * tb, h4)
    dw_ref[:] += lax.dot_general(
        hp2, dg2, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    db_ref[:] += db_scr[:]


def _lstm_plan(bsz, t_max, h):
    # fwd tokens: x 4h in + (y, c) 2h out, double-buffered
    tok = 2 * 4 * (4 * h + 2 * h)
    fixed = 4 * (h * 4 * h + 7 * h) + 8 * 8 * h  # w + b7 + h/c scratch
    return _plan(bsz, t_max, h, tok, fixed)


def _lstm_bwd_plan(bsz, t_max, h):
    # in: x 4h, y h, yp h, c h, cp h, dy h; out: dx 4h -> 13h tokens,
    # double-buffered; plus dg/hp scratch 5h tokens (single)
    tok = 2 * 4 * 13 * h + 4 * 5 * h
    fixed = 4 * (2 * h * 4 * h + 2 * 7 * h) + 8 * 8 * h
    return _plan(bsz, t_max, h, tok, fixed, budget=_VMEM_BUDGET_BWD)


def _lstm_fwd_pallas(x, w, b7, lens, *, interpret, want_c):
    """Returns (y, c_seq) — c_seq None unless `want_c` (training path
    saving the cell sequence for the backward kernel) — or None if
    infeasible."""
    orig = x.dtype
    bsz, t_max, h4 = x.shape
    h = h4 // 4
    plan = _lstm_plan(bsz, t_max, h)
    if plan is None:
        _fell_back("lstm_fwd", "no block plan fits VMEM", x)
        return None
    bb, tb, bp, tp = plan
    if orig == jnp.bfloat16:
        x, w, b7 = (a.astype(jnp.float32) for a in (x, w, b7))
    xp = _pad_bt(x, bp, tp)
    lensp = jnp.pad(lens, ((0, bp - bsz), (0, 0)))
    grid = (bp // bb, tp // tb)
    blk = pl.BlockSpec((bb, tb, h), lambda i, j: (i, j, 0))
    out = pl.pallas_call(
        _lstm_fwd_kernel if want_c else _lstm_fwd_kernel_noc,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, tb, h4), lambda i, j: (i, j, 0)),
            pl.BlockSpec((h, h4), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 7 * h), lambda i, j: (0, 0)),
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[blk, blk] if want_c else blk,
        out_shape=(
            [
                jax.ShapeDtypeStruct((bp, tp, h), jnp.float32),
                jax.ShapeDtypeStruct((bp, tp, h), jnp.float32),
            ]
            if want_c
            else jax.ShapeDtypeStruct((bp, tp, h), jnp.float32)
        ),
        scratch_shapes=[
            pltpu.VMEM((bb, h), jnp.float32),
            pltpu.VMEM((bb, h), jnp.float32),
        ],
        interpret=_ops.pallas_interpret(interpret),
    )(xp, w, b7, lensp)
    if want_c:
        y, c = out
        return y[:bsz, :t_max].astype(orig), c[:bsz, :t_max]
    return out[:bsz, :t_max].astype(orig), None


def _lstm_bwd_pallas(x, w, b7, lens, y, c_seq, dy, *, interpret):
    """Returns (dx, dw, db7) or None if infeasible."""
    orig = x.dtype
    bsz, t_max, h4 = x.shape
    h = h4 // 4
    plan = _lstm_bwd_plan(bsz, t_max, h)
    if plan is None:
        _fell_back("lstm_bwd", "no block plan fits VMEM", x)
        return None
    bb, tb, bp, tp = plan
    # measured on v5e: with bb < 32 the per-step [bb,h]@[h,4h] matmul
    # under-fills the MXU and the kernel loses to the scan-recompute
    # backward (h=512/bb=16: 19.9ms vs 13.8ms scan; h=256/bb>=32 the
    # kernel wins 1.56x) — fall back unless the batch block is wide.
    # interpret mode (CPU tests) keeps the kernel path regardless.
    if bb < 32 and not interpret:
        _fell_back("lstm_bwd", f"batch block {bb} < 32", x)
        return None
    f32 = jnp.float32
    # everything in f32 inside the kernel — including w/b7, matching
    # the forward's bf16-AMP upcast
    w = w.astype(f32)
    b7 = b7.astype(f32)
    xp = _pad_bt(x.astype(f32), bp, tp)
    yp_ = _pad_bt(y.astype(f32), bp, tp)
    cp_ = _pad_bt(c_seq.astype(f32), bp, tp)
    dyp = _pad_bt(dy.astype(f32), bp, tp)
    lensp = jnp.pad(lens, ((0, bp - bsz), (0, 0)))
    nt = tp // tb
    rev = lambda i, j: (i, nt - 1 - j, 0)  # noqa: E731
    # previous time block (one earlier in real time); clamped at 0 —
    # its stale values are masked inside the kernel at t == 0
    prev = lambda i, j: (i, jnp.maximum(nt - 2 - j, 0), 0)  # noqa: E731
    grid = (bp // bb, nt)
    dx, dw, db7 = pl.pallas_call(
        _lstm_bwd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, tb, h4), rev),
            pl.BlockSpec((h, h4), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 7 * h), lambda i, j: (0, 0)),
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, tb, h), rev),
            pl.BlockSpec((bb, tb, h), prev),
            pl.BlockSpec((bb, tb, h), rev),
            pl.BlockSpec((bb, tb, h), prev),
            pl.BlockSpec((bb, tb, h), rev),
        ],
        out_specs=[
            pl.BlockSpec((bb, tb, h4), rev),
            pl.BlockSpec((h, h4), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 7 * h), lambda i, j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, tp, h4), jnp.float32),
            jax.ShapeDtypeStruct((h, h4), jnp.float32),
            jax.ShapeDtypeStruct((1, 7 * h), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bb, h), f32),
            pltpu.VMEM((bb, h), f32),
            pltpu.VMEM((bb, tb, h4), f32),
            pltpu.VMEM((bb, tb, h), f32),
            pltpu.VMEM((1, 7 * h), f32),
        ],
        interpret=_ops.pallas_interpret(interpret),
    )(xp, w, b7, lensp, yp_, yp_, cp_, cp_, dyp)
    return dx[:bsz, :t_max].astype(orig), dw, db7


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def lstm_fused(x, w, gb, wci, wcf, wco, lens, interpret=False):
    b7 = jnp.concatenate([gb, wci, wcf, wco])[None, :]
    out = _lstm_fwd_pallas(
        x, w, b7, lens[:, None].astype(jnp.int32), interpret=interpret,
        want_c=False,
    )
    if out is None:  # weights too large for VMEM: scan is MXU-bound
        return _ref_like_kernel(lstm_ref, x, w, gb, wci, wcf, wco, lens)
    return out[0]


def _lstm_fused_fwd(x, w, gb, wci, wcf, wco, lens, interpret):
    b7 = jnp.concatenate([gb, wci, wcf, wco])[None, :]
    out = _lstm_fwd_pallas(
        x, w, b7, lens[:, None].astype(jnp.int32), interpret=interpret,
        want_c=True,
    )
    if out is None:
        y = _ref_like_kernel(lstm_ref, x, w, gb, wci, wcf, wco, lens)
        return y, (x, w, gb, wci, wcf, wco, lens, None, None)
    y, c_seq = out
    return y, (x, w, gb, wci, wcf, wco, lens, y, c_seq)


def _lstm_fused_bwd(interpret, res, dy):
    x, w, gb, wci, wcf, wco, lens, y, c_seq = res
    h = w.shape[0]
    if y is not None:
        b7 = jnp.concatenate([gb, wci, wcf, wco])[None, :]
        out = _lstm_bwd_pallas(
            x, w, b7, lens[:, None].astype(jnp.int32), y, c_seq, dy,
            interpret=interpret,
        )
        if out is not None:
            dx, dw, db7 = out
            dgb = db7[0, : 4 * h].astype(gb.dtype)
            dwci = db7[0, 4 * h : 5 * h].astype(wci.dtype)
            dwcf = db7[0, 5 * h : 6 * h].astype(wcf.dtype)
            dwco = db7[0, 6 * h : 7 * h].astype(wco.dtype)
            return (dx, dw.astype(w.dtype), dgb, dwci, dwcf, dwco, None)
    _, vjp = jax.vjp(
        lambda *a: _ref_like_kernel(lstm_ref, *a, lens),
        x, w, gb, wci, wcf, wco,
    )
    return (*vjp(dy), None)


lstm_fused.defvjp(_lstm_fused_fwd, _lstm_fused_bwd)


# ---------------------------------------------------------------- GRU

def gru_ref(x, w_g, w_c, b, lens):
    """Reference scan. x: [B,T,3h] as [u,r,c]; w_g: [h,2h]; w_c: [h,h];
    b: [3h]; lens [B]. Returns y [B,T,h]."""
    h = w_c.shape[0]
    t_max = x.shape[1]
    mask = (
        jnp.arange(t_max, dtype=jnp.int32)[None, :] < lens[:, None]
    ).astype(x.dtype)

    def step(h_prev, inp):
        x_t, m_t = inp
        xu, xr, xc = jnp.split(x_t + b, 3, axis=-1)
        gur = jnp.dot(h_prev, w_g)
        u = jax.nn.sigmoid(xu + gur[:, :h])
        r = jax.nn.sigmoid(xr + gur[:, h:])
        c = jnp.tanh(xc + jnp.dot(r * h_prev, w_c))
        out = u * h_prev + (1 - u) * c
        m = m_t[:, None]
        h_new = m * out + (1 - m) * h_prev
        return h_new, out * m

    bsz = x.shape[0]
    z = jnp.zeros((bsz, h), x.dtype)
    _, ys = lax.scan(step, z, (x.swapaxes(0, 1), mask.swapaxes(0, 1)))
    return ys.swapaxes(0, 1)


def _gru_kernel(x_ref, wg_ref, wc_ref, b_ref, lens_ref, y_ref, h_scr):
    bb, tb, h3 = x_ref.shape
    h = h3 // 3
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        h_scr[:] = jnp.zeros_like(h_scr)

    b = b_ref[0, :]
    lens = lens_ref[:, 0]
    t0 = j * tb

    def body(tt, _):
        x_t = x_ref[:, tt, :] + b
        h_prev = h_scr[:]
        xu = x_t[:, :h]
        xr = x_t[:, h : 2 * h]
        xc = x_t[:, 2 * h :]
        gur = jnp.dot(
            h_prev, wg_ref[:], preferred_element_type=jnp.float32
        )
        u = jax.nn.sigmoid(xu + gur[:, :h])
        r = jax.nn.sigmoid(xr + gur[:, h:])
        c = jnp.tanh(
            xc
            + jnp.dot(
                r * h_prev, wc_ref[:], preferred_element_type=jnp.float32
            )
        )
        out = u * h_prev + (1 - u) * c
        m = (t0 + tt < lens).astype(jnp.float32)[:, None]
        h_scr[:] = m * out + (1 - m) * h_prev
        y_ref[:, tt, :] = (out * m).astype(y_ref.dtype)
        return 0

    lax.fori_loop(0, tb, body, 0)


def _gru_plan(bsz, t_max, h):
    tok = 2 * 4 * (3 * h + h)  # x in + y out, double-buffered
    fixed = 4 * (h * 2 * h + h * h + 3 * h) + 4 * 8 * h
    return _plan(bsz, t_max, h, tok, fixed)


def _gru_bwd_kernel(
    x_ref, wg_ref, wc_ref, b_ref, lens_ref, y_ref, yp_ref, dy_ref,
    dx_ref, dwg_ref, dwc_ref, db_ref,
    dh_scr, dgg_scr, dgc_scr, hp_scr, rh_scr, db_scr,
):
    """Reverse-time GRU backward (mirrors _lstm_bwd_kernel): gates
    recomputed from x and the saved output sequence (h_{t-1} = y[t-1]
    wherever the mask is live, previous block's last row at the
    boundary), dW_g/dW_c/db accumulated in resident output blocks."""
    bb, tb, h3 = x_ref.shape
    h = h3 // 3
    i_blk = pl.program_id(0)
    j = pl.program_id(1)
    nt = pl.num_programs(1)
    k = nt - 1 - j
    t0 = k * tb

    @pl.when(j == 0)
    def _init_carry():
        dh_scr[:] = jnp.zeros_like(dh_scr)

    @pl.when((i_blk == 0) & (j == 0))
    def _init_outs():
        dwg_ref[:] = jnp.zeros_like(dwg_ref)
        dwc_ref[:] = jnp.zeros_like(dwc_ref)
        db_ref[:] = jnp.zeros_like(db_ref)

    db_scr[:] = jnp.zeros_like(db_scr)
    b = b_ref[0, :]
    lens = lens_ref[:, 0]
    w_g = wg_ref[:]
    w_c = wc_ref[:]

    def body(s, _):
        tt = tb - 1 - s
        t = t0 + tt
        m = (t < lens).astype(jnp.float32)[:, None]
        tt_prev = jnp.maximum(tt - 1, 0)
        in_blk = (tt > 0).astype(jnp.float32)
        live = jnp.where(t == 0, 0.0, 1.0)
        h_prev = live * (
            in_blk * y_ref[:, tt_prev, :]
            + (1 - in_blk) * yp_ref[:, tb - 1, :]
        )
        # recompute the forward gates
        xb = x_ref[:, tt, :] + b
        gur = jnp.dot(h_prev, w_g, preferred_element_type=jnp.float32)
        u = jax.nn.sigmoid(xb[:, :h] + gur[:, :h])
        r = jax.nn.sigmoid(xb[:, h : 2 * h] + gur[:, h:])
        rh = r * h_prev
        c = jnp.tanh(
            xb[:, 2 * h :]
            + jnp.dot(rh, w_c, preferred_element_type=jnp.float32)
        )
        # backward through the step
        dh_in = dh_scr[:]
        dout = m * (dh_in + dy_ref[:, tt, :])
        du = dout * (h_prev - c)
        dc = dout * (1 - u)
        dg_c = dc * (1 - c * c)
        drh = lax.dot_general(
            dg_c, w_c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dr = drh * h_prev
        dg_u = du * u * (1 - u)
        dg_r = dr * r * (1 - r)
        dg_ur = jnp.concatenate([dg_u, dg_r], axis=-1)
        dh_prev = (
            (1 - m) * dh_in
            + drh * r
            + dout * u
            + lax.dot_general(
                dg_ur, w_g, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        )
        dx = jnp.concatenate([dg_ur, dg_c], axis=-1)
        dx_ref[:, tt, :] = dx.astype(dx_ref.dtype)
        dgg_scr[:, tt, :] = dg_ur
        dgc_scr[:, tt, :] = dg_c
        hp_scr[:, tt, :] = h_prev
        rh_scr[:, tt, :] = rh
        dh_scr[:] = dh_prev
        db_scr[0, :] += jnp.sum(dx, axis=0)
        return 0

    lax.fori_loop(0, tb, body, 0)
    hp2 = hp_scr[:].reshape(bb * tb, h)
    rh2 = rh_scr[:].reshape(bb * tb, h)
    dwg_ref[:] += lax.dot_general(
        hp2, dgg_scr[:].reshape(bb * tb, 2 * h),
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    dwc_ref[:] += lax.dot_general(
        rh2, dgc_scr[:].reshape(bb * tb, h),
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    db_ref[:] += db_scr[:]


def _gru_bwd_plan(bsz, t_max, h):
    # in: x 3h, y h, yp h, dy h; out dx 3h -> 9h tokens double-buffered;
    # scratch dgg 2h + dgc h + hp h + rh h = 5h tokens (single)
    tok = 2 * 4 * 9 * h + 4 * 5 * h
    fixed = 4 * (2 * (h * 2 * h + h * h) + 2 * 3 * h) + 4 * 8 * h
    return _plan(bsz, t_max, h, tok, fixed, budget=_VMEM_BUDGET_BWD)


def _gru_bwd_pallas(x, w_g, w_c, b, lens, y, dy, *, interpret):
    orig = x.dtype
    bsz, t_max, h3 = x.shape
    h = h3 // 3
    plan = _gru_bwd_plan(bsz, t_max, h)
    if plan is None:
        _fell_back("gru_bwd", "no block plan fits VMEM", x)
        return None
    bb, tb, bp, tp = plan
    # same MXU-fill gate as the LSTM backward (measured on v5e)
    if bb < 32 and not interpret:
        _fell_back("gru_bwd", f"batch block {bb} < 32", x)
        return None
    f32 = jnp.float32
    wg_dt, wc_dt = w_g.dtype, w_c.dtype  # cotangents match the primals
    w_g = w_g.astype(f32)
    w_c = w_c.astype(f32)
    b2 = b.astype(f32)[None, :]
    xp = _pad_bt(x.astype(f32), bp, tp)
    yp_ = _pad_bt(y.astype(f32), bp, tp)
    dyp = _pad_bt(dy.astype(f32), bp, tp)
    lensp = jnp.pad(lens, ((0, bp - bsz), (0, 0)))
    nt = tp // tb
    rev = lambda i, j: (i, nt - 1 - j, 0)  # noqa: E731
    prev = lambda i, j: (i, jnp.maximum(nt - 2 - j, 0), 0)  # noqa: E731
    grid = (bp // bb, nt)
    dx, dwg, dwc, db3 = pl.pallas_call(
        _gru_bwd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, tb, h3), rev),
            pl.BlockSpec((h, 2 * h), lambda i, j: (0, 0)),
            pl.BlockSpec((h, h), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 3 * h), lambda i, j: (0, 0)),
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, tb, h), rev),
            pl.BlockSpec((bb, tb, h), prev),
            pl.BlockSpec((bb, tb, h), rev),
        ],
        out_specs=[
            pl.BlockSpec((bb, tb, h3), rev),
            pl.BlockSpec((h, 2 * h), lambda i, j: (0, 0)),
            pl.BlockSpec((h, h), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 3 * h), lambda i, j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, tp, h3), f32),
            jax.ShapeDtypeStruct((h, 2 * h), f32),
            jax.ShapeDtypeStruct((h, h), f32),
            jax.ShapeDtypeStruct((1, 3 * h), f32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bb, h), f32),
            pltpu.VMEM((bb, tb, 2 * h), f32),
            pltpu.VMEM((bb, tb, h), f32),
            pltpu.VMEM((bb, tb, h), f32),
            pltpu.VMEM((bb, tb, h), f32),
            pltpu.VMEM((1, 3 * h), f32),
        ],
        interpret=_ops.pallas_interpret(interpret),
    )(xp, w_g, w_c, b2, lensp, yp_, yp_, dyp)
    return (
        dx[:bsz, :t_max].astype(orig),
        dwg.astype(wg_dt),
        dwc.astype(wc_dt),
        db3[0],
    )


def _gru_fwd_kernel(x, w_g, w_c, b, lens, *, interpret):
    orig = x.dtype
    bsz, t_max, h3 = x.shape
    h = h3 // 3
    plan = _gru_plan(bsz, t_max, h)
    if plan is None:
        _fell_back("gru_fwd", "no block plan fits VMEM", x)
        return None
    bb, tb, bp, tp = plan
    if orig == jnp.bfloat16:
        x, w_g, w_c, b = (
            a.astype(jnp.float32) for a in (x, w_g, w_c, b)
        )
    xp = _pad_bt(x, bp, tp)
    lensp = jnp.pad(lens, ((0, bp - bsz), (0, 0)))
    grid = (bp // bb, tp // tb)
    y = pl.pallas_call(
        _gru_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, tb, h3), lambda i, j: (i, j, 0)),
            pl.BlockSpec((h, 2 * h), lambda i, j: (0, 0)),
            pl.BlockSpec((h, h), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 3 * h), lambda i, j: (0, 0)),
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bb, tb, h), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, tp, h), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bb, h), jnp.float32)],
        interpret=_ops.pallas_interpret(interpret),
    )(xp, w_g, w_c, b[None, :], lensp)
    return y[:bsz, :t_max].astype(orig)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def gru_fused(x, w_g, w_c, b, lens, interpret=False):
    y = _gru_fwd_kernel(
        x, w_g, w_c, b, lens[:, None].astype(jnp.int32), interpret=interpret
    )
    if y is None:  # weights too large for VMEM
        return _ref_like_kernel(gru_ref, x, w_g, w_c, b, lens)
    return y


def _gru_fused_fwd(x, w_g, w_c, b, lens, interpret):
    y = gru_fused(x, w_g, w_c, b, lens, interpret)
    plan = _gru_plan(x.shape[0], x.shape[1], w_c.shape[0])
    # y came from the kernel only if the fwd plan was feasible
    return y, (x, w_g, w_c, b, lens, y if plan is not None else None)


def _gru_fused_bwd(interpret, res, dy):
    x, w_g, w_c, b, lens, y = res
    if y is not None:
        out = _gru_bwd_pallas(
            x, w_g, w_c, b, lens[:, None].astype(jnp.int32), y, dy,
            interpret=interpret,
        )
        if out is not None:
            dx, dwg, dwc, db3 = out
            return (dx, dwg, dwc, db3.astype(b.dtype), None)
    _, vjp = jax.vjp(
        lambda *a: _ref_like_kernel(gru_ref, *a, lens), x, w_g, w_c, b
    )
    return (*vjp(dy), None)


gru_fused.defvjp(_gru_fused_fwd, _gru_fused_bwd)
