"""Causal grouped-query attention with an optional sliding window, never
materialising the [T, T] scores or the KV heads repeated per query head.

    q [B, T, H, D], k [B, T, KV, D], v [B, T, KV, Dv]  ->  [B, T, H, Dv]

Query head h attends KV head h // (H / KV); position i sees j <= i and,
with `window`, also i - j < window. The value head may differ in width
from the query/key head (latent attention: 192-wide keys, 128-wide
values; differential attention: 64-wide keys, 128-wide values); scores are
scaled by 1/sqrt(D). Two lowerings (`impl`):

- "pallas": the TPU's splash-attention kernel (jax.experimental.pallas
  .ops.tpu.splash_attention), as multi-query attention over the query heads
  of one KV head, mapped over KV heads and rows. Its block-sparse mask
  skips, forward and backward, every key block wholly outside the window
  or the causal triangle. Its tiles and the form of its backward follow
  the mask and the head widths (`kernel_tiles`): a full causal layer takes
  the scores once, in ONE backward kernel that also produces dq (a partial
  a key block, summed after it); a window layer keeps the two backward
  kernels, whose grids shrink to the blocks the window reaches, and takes
  tiles under the window.
- "blocked": portable. A Python loop over query blocks, each against the
  one key span its mask can reach (so a window layer does a window's work,
  not the square's), each block rematerialised in the backward pass.

None picks "pallas" on a TPU when the shapes fit its tiles, else "blocked".
`grouped` is the kernel's call alone, for a caller whose operands are
already in the kernel's layout.

Both tag what they produce and their backward reads with the name `KEPT`
(the kernel its output and log-sum-exp, the portable loop its output): a
recompute group keeps values of that name (`network._KEEP`) and so does not
run the attention forward a second time. Outside such a group the tag is
the identity.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from paddle_tpu import ops as _ops

KEPT = "attn.core"
NEG_INF = -1e30
LANES = 128
# the kernel's tiles (`kernel_tiles`): the largest it is given (1,024 by
# 1,024 fits VMEM at the cells' 64, 128 and 192-wide keys; 2,048 does not),
# the smallest a window layer takes (at 256 the grid steps cost more than the
# emptied part of a tile saves, on both window shapes timed), and the keys a
# matmul of the full causal forward (a key block of 1,024 in two halves: 4 to
# 10% off the forward call at the three head shapes); then the portable
# loop's query block
KERNEL_BLOCK = 1024
WINDOW_BLOCK = 512
FORWARD_COMPUTE = 512
BLOCKED_BLOCK_Q = 512


def pallas_fits(t: int, d: int, dv: int = None) -> bool:
    """The kernel's tiles: the sequence in blocks of a lane multiple, the
    query/key head and the value head multiples of half a lane tile (64,
    192: Mosaic pads the block's last tile itself; 64/128, 192/128 and
    64/64 compiled for a v5e in tests/test_tpu_compile.py)."""
    dv = d if dv is None else dv
    half = LANES // 2
    return t % LANES == 0 and d > 0 and d % half == 0 and dv % half == 0


def _block(t: int, cap: int) -> int:
    """The largest lane multiple <= cap that divides t."""
    b = min(cap, t) // LANES * LANES
    while t % b:
        b -= LANES
    return b


def kernel_tiles(t, d, dv, window, block_q=None, block_kv=None):
    """The splash kernel's `BlockSizes` for a sequence of `t`, a query/key
    head `d` wide over a value head `dv` wide, under a causal mask with
    `window` (None, or one that `t` does not reach: full causal). The rule
    is read off the table of PERF.md section 6 (PR 38: forward, `dq` and
    `dkv` timed a call on a v5e at T 8,192 for every tile of {1,024, 512,
    256} squared and both backward forms, on both masks at the cells' three
    head shapes) and sees nothing but its arguments:

    - full causal: ONE backward kernel (`use_fused_bwd_kernel`: the scores
      and their exponentials are taken once a tile, 5 matmuls for the 7 of
      two kernels; dq comes as a partial a key block, in q's dtype, and is
      summed after the call), in the largest tiles: 14 to 21% off a layer's
      three calls at 192/128, 128/128 and 64/128, the sum counted in;
    - a window: two backward kernels, whose grids shrink to the blocks the
      window reaches (the one-pass kernel walks the whole grid and writes a
      partial for every block the mask empties: no faster at 1,024, slower
      below), in tiles of half the window, between `WINDOW_BLOCK` and
      `KERNEL_BLOCK`: a query row's kernel work is window + tile keys for
      the window kept, and 512 won for every kernel at a window of 1,024
      (128/128) and of 512 (64/128), square against rectangular too.

    The three head shapes timed took the same tiles; the widths decide
    only what VMEM takes: past a 320-wide head the largest tile is 512.
    `block_q`/`block_kv` are a caller's own tiles for every kernel; the
    form of the backward still follows the mask. A tile is cut to the
    largest lane multiple that divides `t`."""
    from jax.experimental.pallas.ops.tpu import splash_attention as sa

    full = window is None or window >= t
    # the one-pass kernel in tiles of 1,024 compiles for a v5e up to a
    # 320-wide head and not at 384 (tests/test_tpu_compile.py)
    tile = KERNEL_BLOCK if max(d, dv) <= 320 else KERNEL_BLOCK // 2
    if not full:
        tile = min(tile, max(WINDOW_BLOCK, window // 2))
    bq, bkv = _block(t, block_q or tile), _block(t, block_kv or tile)
    if not full:
        return sa.BlockSizes(
            block_q=bq, block_kv=bkv, block_kv_compute=bkv,
            block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=bkv,
            block_q_dq=bq, block_kv_dq=bkv)
    half = bkv if block_kv or bkv % FORWARD_COMPUTE else FORWARD_COMPUTE
    return sa.BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=half,
        block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=bkv,
        use_fused_bwd_kernel=True)


@functools.lru_cache(maxsize=None)
def _splash_kernel(t, group, window, sizes, interpret):
    """-> (the kernel for `group` query heads on one KV head, the pairs in
    the forward tiles its mask's block table visits, a query head)."""
    from jax.experimental.pallas.ops.tpu import splash_attention as sa

    if window is None:
        one = sa.CausalMask((t, t))
    else:
        one = sa.LocalMask((t, t), (window - 1, 0), 0)
    kernel = sa.make_splash_mqa_single_device(
        sa.MultiHeadMask([one] * group), block_sizes=sizes,
        residual_checkpoint_name=KEPT, interpret=interpret,
    )
    table = np.asarray(kernel.fwd_mask_info.block_mask)  # [heads or 1, q, kv]
    tiles = np.count_nonzero(table) // table.shape[0]
    return kernel, tiles * sizes.block_q * sizes.block_kv


def _note_tiles(t, window, in_tiles, sizes) -> None:
    """Counters of how often the rule engages, raised as `ops.note_kept`
    raises its own: by the op, once a TRACED call, read by nobody on the
    step's path. `attn.pairs_in_tiles` over `attn.pairs_kept` (a query head
    and row: the forward tiles the block table visits, and what the mask
    keeps of them) is the work the kernel is given over the model's;
    `attn.backward_passes` by `passes`: calls traced with each backward."""
    from paddle_tpu import obs

    w = t if window is None else window
    reg = obs.get_registry()
    reg.counter("attn.pairs_in_tiles").inc(in_tiles)
    reg.counter("attn.pairs_kept").inc(w * (w + 1) // 2 + (t - w) * w)
    reg.counter("attn.backward_passes").inc(
        1, passes=1 if sizes.use_fused_bwd_kernel else 2)


def _require_fit(t, d, dv) -> None:
    if not pallas_fits(t, d, dv):
        raise ValueError(
            f"the attention kernel needs T in multiples of {LANES}, the "
            f"query/key and the value head in multiples of {LANES // 2}; "
            f"got T={t}, D={d}, Dv={dv}")


def grouped(qg, kt, vt, *, window=None, block_q=None, block_kv=None,
            interpret=None):
    """The kernel on operands that are already its own: qg [B, KV, G, T, D]
    SCALED by 1/sqrt(D) (the kernel does not scale), kt [B, KV, T, D], vt
    [B, KV, T, Dv] -> [B, KV, G, T, Dv]. `gqa_attention(impl="pallas")` is
    this behind a scale and four transposes; a layer whose projections'
    outputs reach the kernel's layout another way (`ops/rope.to_heads`)
    calls it directly."""
    t, d = qg.shape[-2:]
    dv = vt.shape[-1]
    _require_fit(t, d, dv)
    if window is not None and window >= t:
        window = None
    sizes = kernel_tiles(t, d, dv, window, block_q, block_kv)
    # the mask's block tables are built with numpy when the kernel is
    # made: outside any trace, so that they are constants of the program
    with jax.ensure_compile_time_eval():
        kernel, in_tiles = _splash_kernel(
            t, qg.shape[2], window, sizes,
            bool(_ops.pallas_interpret(interpret)))
    _note_tiles(t, window, in_tiles, sizes)
    o = jax.vmap(jax.vmap(kernel))(qg, kt, vt)        # [B, KV, G, T, Dv]
    # the library tags the output and, for its backward kernels, the
    # log-sum-exp (float32 a query and head) itself
    _ops.note_kept(o, jax.ShapeDtypeStruct(o.shape[:-1], jnp.float32))
    return o


def _pallas(q, k, v, window, block_q, block_kv, interpret):
    b, t, h, d = q.shape
    kv = k.shape[2]
    # the kernel does not scale: fold 1/sqrt(D) into q
    qg = (q * (1.0 / math.sqrt(d))).astype(q.dtype)
    qg = qg.transpose(0, 2, 1, 3).reshape(b, kv, h // kv, t, d)
    kt, vt = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    o = grouped(qg, kt, vt, window=window, block_q=block_q,
                block_kv=block_kv, interpret=interpret)
    return o.reshape(b, h, t, v.shape[-1]).transpose(0, 2, 1, 3)


def _blocked(q, k, v, window, block_q):
    b, t, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, t, kv, g, d)

    @functools.partial(jax.checkpoint, static_argnums=(3, 4))
    def one(qs, ks, vs, q0, k0):
        s = jnp.einsum("bqkgd,bskd->bkgqs", qs, ks,
                       preferred_element_type=jnp.float32) * scale
        qi = q0 + jnp.arange(qs.shape[1])[:, None]
        kj = k0 + jnp.arange(ks.shape[1])[None, :]
        m = kj <= qi
        if window is not None:
            m = m & (qi - kj < window)
        s = jnp.where(m[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", p.astype(vs.dtype), vs,
                          preferred_element_type=jnp.float32
                          ).astype(qs.dtype)

    outs = []
    for q0 in range(0, t, block_q):
        q1 = min(q0 + block_q, t)
        k0 = 0 if window is None else max(0, q0 - (window - 1))
        outs.append(one(qg[:, q0:q1], k[:, k0:q1], v[:, k0:q1], q0, k0))
    o = checkpoint_name(jnp.concatenate(outs, axis=1), KEPT)
    _ops.note_kept(o)
    return o.reshape(b, t, h, v.shape[-1])


def gqa_attention(q, k, v, *, window=None, impl=None, block_q=None,
                  block_kv=None, interpret=None):
    """See the module's docstring. `block_q`/`block_kv`: a caller's own
    tile sizes (by default the kernel's follow the mask and the head,
    `kernel_tiles`; the portable loop's query block is 512)."""
    b, t, h, d = q.shape
    dv = v.shape[-1]
    if h % k.shape[2]:
        raise ValueError(f"{h} query heads do not divide over "
                         f"{k.shape[2]} KV heads")
    if window is not None and window >= t:
        window = None
    if impl is None:
        on_tpu = jax.default_backend() == "tpu"
        impl = "pallas" if on_tpu and pallas_fits(t, d, dv) else "blocked"
    if impl == "pallas":
        _require_fit(t, d, dv)
        return _pallas(q, k, v, window, block_q, block_kv,
                       _ops.pallas_interpret(interpret))
    if impl == "blocked":
        return _blocked(q, k, v, window, block_q or BLOCKED_BLOCK_Q)
    raise ValueError(f"unknown attention impl {impl!r}")

