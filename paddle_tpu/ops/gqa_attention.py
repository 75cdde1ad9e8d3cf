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
  or the causal triangle.
- "blocked": portable. A Python loop over query blocks, each against the
  one key span its mask can reach (so a window layer does a window's work,
  not the square's), each block rematerialised in the backward pass.

None picks "pallas" on a TPU when the shapes fit its tiles, else "blocked".

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
from jax.ad_checkpoint import checkpoint_name

from paddle_tpu import ops as _ops

KEPT = "attn.core"
NEG_INF = -1e30
LANES = 128
# the kernel's tiles (query block, key block) and the portable loop's
KERNEL_BLOCK_Q = KERNEL_BLOCK_KV = 1024
BLOCKED_BLOCK_Q = 512


def pallas_fits(t: int, d: int, dv: int = None) -> bool:
    """The kernel's tiles: the sequence in blocks of a lane multiple, the
    value head a lane multiple, the query/key head a multiple of half a
    lane tile (64, 192: Mosaic pads the block's last tile itself; compiled
    for a v5e in tests/test_tpu_compile.py)."""
    dv = d if dv is None else dv
    return (t % LANES == 0 and dv % LANES == 0 and d > 0
            and d % (LANES // 2) == 0)


def _block(t: int, cap: int) -> int:
    """The largest lane multiple <= cap that divides t."""
    b = min(cap, t) // LANES * LANES
    while t % b:
        b -= LANES
    return b


@functools.lru_cache(maxsize=None)
def _splash_kernel(t, group, window, block_q, block_kv, interpret):
    from jax.experimental.pallas.ops.tpu import splash_attention as sa

    if window is None or window >= t:
        one = sa.CausalMask((t, t))
    else:
        one = sa.LocalMask((t, t), (window - 1, 0), 0)
    bq, bkv = _block(t, block_q), _block(t, block_kv)
    sizes = sa.BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=bkv,
        block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=bkv,
        block_q_dq=bq, block_kv_dq=bkv,
    )
    return sa.make_splash_mqa_single_device(
        sa.MultiHeadMask([one] * group), block_sizes=sizes,
        residual_checkpoint_name=KEPT, interpret=interpret,
    )


def _pallas(q, k, v, window, block_q, block_kv, interpret):
    b, t, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    # the mask's block tables are built with numpy when the kernel is
    # made: outside any trace, so that they are constants of the program
    with jax.ensure_compile_time_eval():
        kernel = _splash_kernel(t, g, window, block_q, block_kv,
                                bool(interpret))
    # the kernel does not scale: fold 1/sqrt(D) into q
    qg = (q * (1.0 / math.sqrt(d))).astype(q.dtype)
    qg = qg.transpose(0, 2, 1, 3).reshape(b, kv, g, t, d)
    kt, vt = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    o = jax.vmap(jax.vmap(kernel))(qg, kt, vt)        # [B, KV, G, T, Dv]
    # the library tags the output and, for its backward kernels, the
    # log-sum-exp (float32 a query and head) itself
    _ops.note_kept(o, jax.ShapeDtypeStruct(o.shape[:-1], jnp.float32))
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
    """See the module's docstring. `block_q`/`block_kv`: tile sizes
    (defaults 1024/1024 for the kernel, 512 for the portable loop)."""
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
        if not pallas_fits(t, d, dv):
            raise ValueError(
                f"the attention kernel needs T and the value head in "
                f"multiples of {LANES}, the query/key head in multiples of "
                f"{LANES // 2}; got T={t}, D={d}, Dv={dv}")
        return _pallas(q, k, v, window, block_q or KERNEL_BLOCK_Q,
                       block_kv or KERNEL_BLOCK_KV,
                       _ops.pallas_interpret(interpret))
    if impl == "blocked":
        return _blocked(q, k, v, window, block_q or BLOCKED_BLOCK_Q)
    raise ValueError(f"unknown attention impl {impl!r}")

