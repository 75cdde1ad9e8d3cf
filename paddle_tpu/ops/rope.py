"""Rotary positions: plain and YaRN-scaled frequencies, half-split pairing.

`rope` is the model config's own group for a layer type, e.g.
`{"rope_type": "default", "rope_theta": 500000}` or `{"rope_type": "yarn",
"rope_theta": 500000, "factor": 16, "original_max_position_embeddings":
8192, "beta_fast": 32, "beta_slow": 1, "attention_factor": 1.277...}`.
With `partial_rotary_factor` below 1 in the group only the leading part of a
head turns (`rotary_width`): frequencies, the YaRN blend and the tables are
taken over that width, and `apply` passes the rest of the head through.

`to_heads` takes q or k from a projection's output `[B, T, n * D]` to the
attention kernel's operand `[B, n, T, D]`, turned and scaled, two ways
(`impl`):

- "pass": ONE Pallas pass, x's dtype in and out and float32 inside: per
  block of positions `y = x * C + rot(x, -r/2) * S1 + rot(x, +r/2) * S2` over
  float32 tables `[T, D]` (`C = [cos | cos | 1...]`, `S1 = [-sin | 0 | 0...]`,
  `S2 = [0 | sin | 0...]`: a rotary width narrower than the head is a table,
  not a slice and a concatenation; the whole head turned is ONE rotation by
  half of it, `S1 + S2`), the partner lane by a lane rotation on the XLU,
  rounded to x's dtype, then scaled and rounded again: rounding for rounding
  what `apply`, `(x * scale).astype` and a transpose give. Its backward (a
  `custom_vjp`) is the same pass the other way: the kernel's cotangent
  `[B, n, T, D]` in, scaled, turned by the negative angle, `[B, T, n * D]`
  out; its residuals are the tables alone.
- "plain": that composition itself, which the compiler cannot keep in one
  fusion (each half is narrower than a lane tile, so the float32 pieces are
  written to HBM and read back): the portable path and the reference.

None picks "pass" on a TPU where the shapes fit its blocks (`pass_fits`),
else "plain". Every call counts into `attn.rope_calls` by `path`.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import ops as _ops

LANES = 128
# the pass's block (`_blocks`): positions a grid step, and the bytes of x a
# step takes in (as many heads as fit: a head's row is 256 contiguous bytes
# of the projection's output, and longer runs move faster: on a v5e, 2 rows
# of 8,192 positions of 48 heads take 0.91 ms at 1,024 positions of ONE head
# a step, 0.74 at 2 heads, 0.68 at 4 and 0.66 at 8, for 0.49 ms at the HBM's
# speed; 256 to 2,048 positions read within 3% at equal bytes: PR 40)
PASS_ROWS = 1024
PASS_BLOCK_BYTES = 1 << 20


def yarn_range(head_dim, theta, original, beta_fast, beta_slow):
    """(lo, hi) pair indices YaRN blends between: a pair below `lo` turns
    more than `beta_fast` times within `original` positions and keeps its
    frequency; one above `hi` turns fewer than `beta_slow` times and is
    divided by the factor."""
    def pair_at(rotations):
        return (head_dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(pair_at(beta_fast)), 0)
    hi = min(math.ceil(pair_at(beta_slow)), head_dim - 1)
    return lo, hi


def rotary_width(head_dim: int, rope: dict) -> int:
    """The leading dims of a head that turn: `partial_rotary_factor` of it
    (the whole head without the key)."""
    return int(head_dim * rope.get("partial_rotary_factor", 1))


def inv_freq(head_dim: int, rope: dict):
    """-> ([head_dim / 2] float64 numpy frequencies, factor on cos/sin);
    `head_dim` is the width that turns."""
    i = np.arange(head_dim // 2, dtype=np.float64)
    freq = float(rope["rope_theta"]) ** (-2.0 * i / head_dim)
    if rope.get("rope_type", "default") != "yarn":
        return freq, 1.0
    lo, hi = yarn_range(head_dim, rope["rope_theta"],
                        rope["original_max_position_embeddings"],
                        rope["beta_fast"], rope["beta_slow"])
    keep = 1.0 - np.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    freq = freq / rope["factor"] * (1.0 - keep) + freq * keep
    factor = rope.get("attention_factor")
    if factor is None:
        factor = 0.1 * math.log(rope["factor"]) + 1.0
    return freq, float(factor)


def tables(t: int, head_dim: int, rope: dict):
    """cos, sin [T, head_dim / 2] float32 for positions 0..T-1, computed
    on the device (no T-sized constant in the program); `head_dim` is the
    width that turns."""
    freq, factor = inv_freq(head_dim, rope)
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None]
           * jnp.asarray(freq, jnp.float32)[None, :])
    return jnp.cos(ang) * factor, jnp.sin(ang) * factor


def apply(x, cos, sin):
    """x [B, T, n, head_dim], cos and sin [T, r / 2]: of the leading r dims
    pair (i, i + r / 2) is turned by the position's angle, float32 inside,
    x's dtype out; the dims past r (none when the tables are the head's
    width) pass through untouched."""
    r = 2 * cos.shape[-1]
    if r < x.shape[-1]:
        return jnp.concatenate([apply(x[..., :r], cos, sin), x[..., r:]],
                               axis=-1)
    hd = x.shape[-1]
    xf = x.astype(jnp.float32)
    a, b = xf[..., : hd // 2], xf[..., hd // 2:]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([a * c - b * s, b * c + a * s],
                           axis=-1).astype(x.dtype)


def pass_fits(t: int, d: int, r: int) -> bool:
    """The pass's blocks: a head of whole lane tiles, positions in blocks of
    a lane multiple, an even width that turns."""
    return d % LANES == 0 and t % LANES == 0 and 0 < r <= d and r % 2 == 0


def picks_pass(t: int, d: int, r: int) -> bool:
    """What a call that names no `impl` takes: the pass on a TPU where its
    blocks fit (shapes the attention kernel takes too: `pallas_fits`)."""
    return jax.default_backend() == "tpu" and pass_fits(t, d, r)


def note_path(path: str, calls: int = 1) -> None:
    """`attn.rope_calls` by `path` ("pass" or "plain"): calls that took q or
    k to the attention kernel each way, raised once a TRACED call, read by
    nobody on the step's path."""
    from paddle_tpu import obs

    obs.get_registry().counter("attn.rope_calls").inc(calls, path=path)


def head_tables(cos, sin, d: int):
    """cos, sin [T, r / 2] -> (C, the S tables) [T, d] float32 and the lane
    rotation that brings each S table its partner: one table and one
    rotation where the whole head turns (rotating by half of it reaches both
    partners), else S1 (the partner r / 2 lanes up) and S2 (r / 2 down).
    The d - r lanes that do not turn read 1 in C and 0 in S."""
    t, half = cos.shape
    cos, sin = cos.astype(jnp.float32), sin.astype(jnp.float32)
    if 2 * half == d:
        return (jnp.concatenate([cos, cos], -1),
                (jnp.concatenate([-sin, sin], -1),), (half,))
    rest, zero = (t, d - 2 * half), jnp.zeros_like(sin)
    still = jnp.zeros(rest, jnp.float32)
    return (jnp.concatenate([cos, cos, jnp.ones(rest, jnp.float32)], -1),
            (jnp.concatenate([-sin, zero, still], -1),
             jnp.concatenate([zero, sin, still], -1)), (d - half, half))


def _blocks(t: int, n: int, d: int, itemsize: int):
    """(positions, heads) a grid step: the largest lane multiple up to
    `PASS_ROWS` that divides t, and the most heads that divide n and keep
    the block of x within `PASS_BLOCK_BYTES`."""
    rows = min(PASS_ROWS, t) // LANES * LANES
    while t % rows:
        rows -= LANES
    heads = max(1, min(n, PASS_BLOCK_BYTES // (rows * d * itemsize)))
    while n % heads:
        heads -= 1
    return rows, heads


def _turn_kernel(x_ref, c_ref, *refs, shifts, scale, to_heads):
    """One block of positions of `heads` heads. `to_heads`: x_ref
    (1, rows, heads * d) -> o_ref (1, heads, rows, d), turned, then scaled;
    else the way back: (1, heads, rows, d) -> (1, rows, heads * d), scaled,
    then turned (the S tables it is handed are the negated ones)."""
    from jax.experimental.pallas import tpu as pltpu

    *s_refs, o_ref = refs
    d = c_ref.shape[-1]
    heads = x_ref.shape[-1] // d if to_heads else x_ref.shape[1]
    dtype = o_ref.dtype

    def scaled(y):
        if scale == 1.0:
            return y
        return (y.astype(jnp.float32) * scale).astype(dtype)

    for j in range(heads):
        lanes = slice(j * d, (j + 1) * d)
        x = x_ref[0, :, lanes] if to_heads else scaled(x_ref[0, j])
        x = x.astype(jnp.float32)
        y = x * c_ref[...]
        for s_ref, shift in zip(s_refs, shifts):
            y = y + pltpu.roll(x, shift, 1) * s_ref[...]
        y = y.astype(dtype)
        if to_heads:
            o_ref[0, j] = scaled(y)
        else:
            o_ref[0, :, lanes] = y


def _turn_call(x, c, s, shifts, n, scale, to_heads, interpret):
    """The pass over x [B, T, n * d] -> [B, n, T, d] (`to_heads`) or back.
    The position block is the grid's OUTERMOST axis: a block of the float32
    tables stays in VMEM while the rows and heads go by."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    d = c.shape[-1]
    b, t = (x.shape[0], x.shape[1]) if to_heads else (x.shape[0], x.shape[2])
    rows, heads = _blocks(t, n, d, x.dtype.itemsize)
    flat = pl.BlockSpec((1, rows, heads * d), lambda i, r, h: (r, i, h))
    split = pl.BlockSpec((1, heads, rows, d), lambda i, r, h: (r, h, i, 0))
    table = pl.BlockSpec((rows, d), lambda i, r, h: (i, 0))
    out = (b, n, t, d) if to_heads else (b, t, n * d)
    # the scale as x's dtype holds it: what `x * scale` multiplies by
    scale = float(np.asarray(scale, x.dtype))
    return pl.pallas_call(
        functools.partial(_turn_kernel, shifts=shifts, scale=scale,
                          to_heads=to_heads),
        grid=(t // rows, b, n // heads),
        in_specs=[flat if to_heads else split, table, *[table] * len(s)],
        out_specs=split if to_heads else flat,
        out_shape=jax.ShapeDtypeStruct(out, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=(2 + 2 * len(s)) * x.size, transcendentals=0,
            bytes_accessed=(2 * x.size * x.dtype.itemsize
                            + (1 + len(s)) * c.size * 4)),
        interpret=interpret,
        name="rope_to_heads" if to_heads else "rope_from_heads",
    )(x, c, *s)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _pass(x, c, s, shifts, n, scale, interpret):
    return _turn_call(x, c, s, shifts, n, scale, True, interpret)


def _pass_fwd(x, c, s, shifts, n, scale, interpret):
    return _pass(x, c, s, shifts, n, scale, interpret), (c, s)


def _pass_bwd(shifts, n, scale, interpret, tables, g):
    c, s = tables
    # the transposed rotation is the rotation by the negative angle
    gx = _turn_call(g, c, tuple(-one for one in s), shifts, n, scale, False,
                    interpret)
    return gx, jnp.zeros_like(c), tuple(jnp.zeros_like(one) for one in s)


_pass.defvjp(_pass_fwd, _pass_bwd)


def to_heads(x, cos, sin, n: int, scale: float = 1.0, *, impl=None):
    """x [B, T, n * D] (a projection's output), cos and sin [T, r / 2] ->
    [B, n, T, D]: each head's leading r dims turned as `apply` turns them,
    then `* scale`, each rounded to x's dtype. See the module's docstring
    for `impl`."""
    b, t, width = x.shape
    d, r = width // n, 2 * cos.shape[-1]
    if impl is None:
        impl = "pass" if picks_pass(t, d, r) else "plain"
    note_path(impl)
    if impl == "pass":
        if not pass_fits(t, d, r):
            raise ValueError(
                f"the rotary pass needs T and the head in multiples of "
                f"{LANES} and an even rotary width; got T={t}, D={d}, r={r}")
        c, s, shifts = head_tables(cos, sin, d)
        return _pass(x, c, s, shifts, n, scale, _ops.pallas_interpret())
    if impl == "plain":
        y = apply(x.reshape(b, t, n, d), cos, sin)
        if scale != 1.0:
            y = (y * scale).astype(y.dtype)
        return y.transpose(0, 2, 1, 3)
    raise ValueError(f"unknown rotary impl {impl!r}")
