"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

New first-class capability (absent in the 2017 reference — SURVEY.md §2
"Pipeline / TP / SP" row): long sequences are sharded over the mesh `seq`
axis. Two strategies:

- ``ring_attention``: K/V blocks rotate around the ring via
  ``lax.ppermute`` while each device keeps its Q shard; softmax is
  accumulated online (flash-attention style running max/denominator), so
  the full [T, T] score matrix never materializes and comm rides ICI
  neighbor links.
- ``ulysses_attention``: ``lax.all_to_all`` reshards seq -> heads, runs
  local attention per head group (dense or flash — at T >= 32k the
  local dense [T, T] scores would not fit, so the long-context rows use
  ``attn_impl="flash"``), and reshards back.

Single-chip, two lowerings of the same masked-attention contract:

- ``dense_attention``: the reference path — materializes [B, H, T, T]
  scores (O(T^2) HBM bytes; the measured bound of the longctx bench
  rows).
- ``flash_dense_attention``: flash attention. On TPU the Pallas kernel
  (jax.experimental.pallas.ops.tpu.flash_attention); on every other
  backend a portable blocked online-softmax lowering
  (``flash_blocked_attention``) with a recompute backward via
  custom_vjp — the same O(T) score-byte algorithm, so parity tests,
  CPU-mesh smokes and HLO byte attribution run without a TPU.

All are numerically identical to dense masked attention (tested on a
virtual CPU mesh in tests/test_parallel_tp_sp.py and
tests/test_flash_attention.py).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.core.mesh import SEQ_AXIS

NEG_INF = -1e30

# flash_blocked_attention unrolls the K/V-block loop up to this many
# blocks (exact static HLO: every block's ops visible to byte
# attribution, no while-loop); longer sequences scan. Either way the
# custom_vjp backward recomputes scores per block, so peak score bytes
# stay O(T * block_k), never O(T^2).
_UNROLL_MAX_BLOCKS = 16


def dense_attention(q, k, v, *, causal=False, kv_len=None, scale=None):
    """Reference masked attention. q,k,v: [B, T, H, D]; kv_len: [B] valid
    K/V length (padding masked out)."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(D).astype(q.dtype)
    with jax.named_scope("dense_attention"):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        mask = jnp.zeros((B, 1, Tq, Tk), q.dtype)
        if kv_len is not None:
            pad = jnp.arange(Tk)[None, :] >= kv_len[:, None]  # [B, Tk]
            mask = jnp.where(pad[:, None, None, :], NEG_INF, mask)
        if causal:
            qpos = jnp.arange(Tq)[:, None]
            kpos = jnp.arange(Tk)[None, :]
            mask = mask + jnp.where(kpos > qpos, NEG_INF, 0.0)
        p = jax.nn.softmax(s + mask, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _pad_time(x, pad, value=0.0):
    return jnp.pad(
        x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2),
        constant_values=value,
    ) if pad else x


def _blocked_kv(k, v, kbias, block_k):
    """Pad Tk to a block multiple and return (k, v, kbias, n_blocks).
    Padding positions carry kbias = NEG_INF so their exp underflows to
    0 in every row."""
    Tk = k.shape[1]
    nb = -(-Tk // block_k)
    pad = nb * block_k - Tk
    return (
        _pad_time(k, pad), _pad_time(v, pad),
        _pad_time(kbias, pad, value=NEG_INF), nb,
    )


def _blocked_fwd(q, k, v, kbias, causal, scale, block_k):
    """Online-softmax forward over K/V blocks. Returns (out f32, lse)
    where lse[b,h,i] = m + log(sum exp(s - m)) is the log-sum-exp the
    backward needs to recompute p without renormalizing. Fully-masked
    query rows get out = 0 and lse = +1e30 (so recomputed p == 0)."""
    B, Tq, H, D = q.shape
    qf = q.astype(jnp.float32)
    qpos = jnp.arange(Tq)

    def one_block(carry, kb, vb, bb, off):
        acc, m, den = carry
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", qf, kb.astype(jnp.float32)
        ) * scale + bb[:, None, None, :]
        if causal:
            kpos = off + jnp.arange(kb.shape[1])
            s = s + jnp.where(
                kpos[None, :] > qpos[:, None], NEG_INF, 0.0
            )[None, None]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        corr = jnp.exp(m - m_new)  # NEG_INF - NEG_INF == 0 (finite)
        # explicit zero for masked positions: in a FULLY-masked row
        # m_new == s == NEG_INF and exp(s - m_new) would be exp(0)=1,
        # silently attending uniformly; with the where, such rows keep
        # den == 0 and the epilogue emits exactly 0 (and lse=+1e30, so
        # the backward's recomputed p is 0 too)
        p = jnp.where(s > 0.5 * NEG_INF,
                      jnp.exp(s - m_new[..., None]), 0.0)
        den_new = den * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhqk,bkhd->bqhd", p, vb.astype(jnp.float32)
        )
        return acc_new, m_new, den_new

    k, v, kbias, nb = _blocked_kv(k, v, kbias, block_k)
    acc = jnp.zeros((B, Tq, H, D), jnp.float32)
    m = jnp.full((B, H, Tq), NEG_INF, jnp.float32)
    den = jnp.zeros((B, H, Tq), jnp.float32)
    if nb <= _UNROLL_MAX_BLOCKS:
        carry = (acc, m, den)
        for i in range(nb):
            sl = slice(i * block_k, (i + 1) * block_k)
            carry = one_block(
                carry, k[:, sl], v[:, sl], kbias[:, sl], i * block_k
            )
        acc, m, den = carry
    else:
        ks = k.reshape(B, nb, block_k, H, D).transpose(1, 0, 2, 3, 4)
        vs = v.reshape(B, nb, block_k, H, D).transpose(1, 0, 2, 3, 4)
        bs = kbias.reshape(B, nb, block_k).transpose(1, 0, 2)
        offs = jnp.arange(nb) * block_k

        def body(carry, xs):
            kb, vb, bb, off = xs
            return one_block(carry, kb, vb, bb, off), None

        (acc, m, den), _ = lax.scan(
            body, (acc, m, den), (ks, vs, bs, offs)
        )
    alive = den > 0.0
    out = acc / jnp.where(alive, den, 1.0).transpose(0, 2, 1)[..., None]
    lse = jnp.where(alive, m + jnp.log(jnp.where(alive, den, 1.0)),
                    jnp.float32(1e30))
    return out, lse


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_blocked(q, k, v, kbias, causal, scale, block_k):
    out, _ = _blocked_fwd(q, k, v, kbias, causal, scale, block_k)
    return out.astype(q.dtype)


def _flash_blocked_fwd(q, k, v, kbias, causal, scale, block_k):
    out, lse = _blocked_fwd(q, k, v, kbias, causal, scale, block_k)
    return out.astype(q.dtype), (q, k, v, kbias, out, lse)


def _flash_blocked_bwd(causal, scale, block_k, res, do):
    """Flash backward: recompute each block's p = exp(s - lse) and
    accumulate dq / per-block dk, dv. Only [B, H, Tq, block_k] score
    tiles ever exist — the backward moves O(T) score bytes too."""
    q, k, v, kbias, out, lse = res
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    qpos = jnp.arange(Tq)
    # delta[b,h,i] = sum_d dO * O — the softmax-jacobian row term
    delta = jnp.einsum("bqhd,bqhd->bhq", dof, out)

    def one_block(dq, kb, vb, bb, off):
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", qf, kb.astype(jnp.float32)
        ) * scale + bb[:, None, None, :]
        if causal:
            kpos = off + jnp.arange(kb.shape[1])
            s = s + jnp.where(
                kpos[None, :] > qpos[:, None], NEG_INF, 0.0
            )[None, None]
        p = jnp.exp(s - lse[..., None])
        dvb = jnp.einsum("bhqk,bqhd->bkhd", p, dof)
        dp = jnp.einsum("bqhd,bkhd->bhqk", dof, vb.astype(jnp.float32))
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bhqk,bkhd->bqhd", ds,
                             kb.astype(jnp.float32))
        dkb = jnp.einsum("bhqk,bqhd->bkhd", ds, qf)
        return dq, dkb, dvb

    k, v, kbias, nb = _blocked_kv(k, v, kbias, block_k)
    dq = jnp.zeros((B, Tq, H, D), jnp.float32)
    if nb <= _UNROLL_MAX_BLOCKS:
        dks, dvs = [], []
        for i in range(nb):
            sl = slice(i * block_k, (i + 1) * block_k)
            dq, dkb, dvb = one_block(
                dq, k[:, sl], v[:, sl], kbias[:, sl], i * block_k
            )
            dks.append(dkb)
            dvs.append(dvb)
        dk = jnp.concatenate(dks, axis=1)
        dv = jnp.concatenate(dvs, axis=1)
    else:
        ks = k.reshape(B, nb, block_k, H, D).transpose(1, 0, 2, 3, 4)
        vs = v.reshape(B, nb, block_k, H, D).transpose(1, 0, 2, 3, 4)
        bs = kbias.reshape(B, nb, block_k).transpose(1, 0, 2)
        offs = jnp.arange(nb) * block_k

        def body(dq, xs):
            kb, vb, bb, off = xs
            dq, dkb, dvb = one_block(dq, kb, vb, bb, off)
            return dq, (dkb, dvb)

        dq, (dks, dvs) = lax.scan(body, dq, (ks, vs, bs, offs))
        dk = dks.transpose(1, 0, 2, 3, 4).reshape(B, nb * block_k, H, D)
        dv = dvs.transpose(1, 0, 2, 3, 4).reshape(B, nb * block_k, H, D)
    Tk_orig = res[1].shape[1]
    return (
        dq.astype(q.dtype),
        dk[:, :Tk_orig].astype(res[1].dtype),
        dv[:, :Tk_orig].astype(res[2].dtype),
        jnp.zeros_like(res[3]),
    )


_flash_blocked.defvjp(_flash_blocked_fwd, _flash_blocked_bwd)


def flash_blocked_attention(q, k, v, *, causal=False, kv_len=None,
                            scale=None, block_k=512):
    """Portable flash attention: online-softmax over K/V blocks with a
    recompute backward (custom_vjp) — the [B, H, Tq, Tk] score matrix
    never exists; peak score bytes are O(Tq * block_k). Same contract
    as dense_attention. Runs on every backend (the CPU-mesh smokes and
    HLO byte attribution use it); on TPU the Pallas kernel
    (flash_dense_attention) is the faster lowering of the same
    algorithm."""
    D = q.shape[-1]
    Tk = k.shape[1]
    scale = float(scale) if scale is not None else 1.0 / float(D) ** 0.5
    kpos = jnp.arange(Tk)[None, :]
    if kv_len is not None:
        kbias = jnp.where(kpos >= kv_len[:, None],
                          jnp.float32(NEG_INF), 0.0)
    else:
        kbias = jnp.zeros((q.shape[0], Tk), jnp.float32)
    return _flash_blocked(q, k, v, kbias, bool(causal), scale,
                          int(block_k))


def _pallas_flash(q, k, v, *, causal, kv_len, q_len, scale):
    """The TPU Pallas kernel behind flash_dense_attention, with the
    wrapper responsibilities: [B,T,H,D] -> [B,H,T,D] layout, padding T
    up to the kernel's block multiple (segment ids mask the pad — pad
    tokens get segment 0, valid get 1, and cross-segment attention is
    masked by the kernel), and slicing the pad back off. Padded QUERY
    rows still emit garbage, which the attention layer zeroes after
    the output projection exactly as in the dense path."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        SegmentIds,
        flash_attention as _flash,
    )

    B, Tq, H, D = q.shape
    Tk = k.shape[1]

    # kernel block sizes must divide each (padded) sequence length:
    # default blocks are min(512, T), so pad to a multiple of 512 past
    # 512 and to the 128-lane minimum below it (pallas_guide tiling) —
    # q and k/v pad independently (cross-attention: Tq != Tk)
    def _padded(t):
        mult = 512 if t > 512 else 128
        return -(-t // mult) * mult

    Tqp, Tkp = _padded(Tq), _padded(Tk)
    q = _pad_time(q, Tqp - Tq)
    k = _pad_time(k, Tkp - Tk)
    v = _pad_time(v, Tkp - Tk)
    seg = None
    if (kv_len is not None or q_len is not None
            or Tqp != Tq or Tkp != Tk):
        q_valid = q_len[:, None] if q_len is not None else (
            kv_len[:, None] if kv_len is not None else Tq
        )
        kv_valid = kv_len[:, None] if kv_len is not None else Tk
        seg = SegmentIds(
            q=(jnp.arange(Tqp)[None, :] < q_valid)
            * jnp.ones((B, 1), jnp.int32),
            kv=(jnp.arange(Tkp)[None, :] < kv_valid)
            * jnp.ones((B, 1), jnp.int32),
        )
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = _flash(qt, kt, vt, segment_ids=seg, causal=causal,
               sm_scale=scale)
    return o.transpose(0, 2, 1, 3)[:, :Tq]


def flash_dense_attention(q, k, v, *, causal=False, kv_len=None,
                          q_len=None, scale=None, impl=None):
    """Single-chip flash attention: same contract as dense_attention —
    q,k,v [B, T, H, D], kv_len [B] — but never materializes the
    [B, H, T, T] score matrix in HBM (the bandwidth bound of the dense
    path at long T; see PERF.md round 8). `impl` selects the lowering:
    "pallas" (TPU kernel), "blocked" (portable online-softmax scan),
    None = pallas on TPU, blocked elsewhere. `q_len` masks query-side
    padding independently of `kv_len` (cross-attention); self-attention
    callers pass only kv_len and get the old behavior."""
    D = q.shape[-1]
    scale = (
        float(scale) if scale is not None else 1.0 / float(D) ** 0.5
    )
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "blocked"
    with jax.named_scope("flash_attention"):
        if impl == "pallas":
            return _pallas_flash(q, k, v, causal=causal, kv_len=kv_len,
                                 q_len=q_len, scale=scale)
        return flash_blocked_attention(
            q, k, v, causal=causal, kv_len=kv_len, scale=scale
        )


# analytic HBM-byte model for the attention CORE (scores + softmax +
# P@V on one layer's forward), the accounting the longctx captures'
# reports carry so "flash removes bytes" is a checkable expectation:
# dense round-trips the [B,H,Tq,Tk] scores ~4 times (QK^T write,
# softmax read+write, P read for P@V); flash never writes them, so
# only the q/k/v/o streams remain.
def attention_hbm_bytes(B, Tq, Tk, H, D, impl, dtype_bytes=2,
                        passes=3):
    """`passes`=3 approximates fwd+bwd (the same convention as the
    rows' analytic FLOP accounting)."""
    io = B * H * D * (2 * Tq + 2 * Tk) * dtype_bytes  # q,o + k,v
    score = 4 * B * H * Tq * Tk * dtype_bytes if impl == "dense" else 0
    return passes * (io + score)


# largest local score tile a ring step may materialize: the per-step
# K/V shard is sub-blocked to [B, H, Tq_local, RING_BLOCK_K] when it
# is larger (and divisible), so a T=128k ring shard streams score
# tiles instead of allocating the full [Tq/s, Tk/s] local square —
# flash semantics inside every ring step, not just across them.
RING_BLOCK_K = 2048


def _ring_body(axis_name, n_shards, causal, scale, q, k0, v0, q_off, kv_lens):
    """Online-softmax accumulation over ring steps. Shapes per shard:
    q: [B, Tq, H, D]; k0/v0: [B, Tk, H, D] (local shard); q_off scalar
    global offset of this shard's queries; kv_lens: [B] global valid len."""
    B, Tq, H, D = q.shape
    Tk = k0.shape[1]
    my = lax.axis_index(axis_name)

    acc = jnp.zeros((B, Tq, H, D), jnp.float32)
    m = jnp.full((B, H, Tq), NEG_INF, jnp.float32)
    den = jnp.zeros((B, H, Tq), jnp.float32)

    qpos = q_off + jnp.arange(Tq)
    blk = (
        RING_BLOCK_K
        if Tk > RING_BLOCK_K and Tk % RING_BLOCK_K == 0 else Tk
    )
    nsub = Tk // blk

    def step(i, carry):
        acc, m, den, k, v = carry
        src = (my - i) % n_shards  # whose K/V block we hold at step i
        k_off = src * Tk

        def sub(j, c):
            acc, m, den = c
            kb = lax.dynamic_slice_in_dim(k, j * blk, blk, axis=1)
            vb = lax.dynamic_slice_in_dim(v, j * blk, blk, axis=1)
            kpos = k_off + j * blk + jnp.arange(blk)
            s = jnp.einsum(
                "bqhd,bkhd->bhqk", q.astype(jnp.float32),
                kb.astype(jnp.float32),
            ) * scale
            neg = jnp.zeros((B, 1, Tq, blk), jnp.float32)
            if kv_lens is not None:
                pad = kpos[None, :] >= kv_lens[:, None]
                neg = jnp.where(pad[:, None, None, :], NEG_INF, neg)
            if causal:
                neg = neg + jnp.where(
                    kpos[None, :] > qpos[:, None], NEG_INF, 0.0
                )[None, None]
            s = s + neg
            blk_max = jnp.max(s, axis=-1)  # [B,H,Tq]
            m_new = jnp.maximum(m, blk_max)
            corr = jnp.exp(m - m_new)  # NEG_INF - NEG_INF == 0
            # explicit zero for masked positions (an all-masked tile
            # would otherwise contribute exp(0) == 1 per position;
            # the stale contribution self-heals once a live tile
            # raises m, but fully-masked ROWS would keep it)
            p = jnp.where(s > 0.5 * NEG_INF,
                          jnp.exp(s - m_new[..., None]), 0.0)
            den_new = den * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum(
                "bhqk,bkhd->bqhd", p, vb.astype(jnp.float32)
            )
            acc_new = acc * corr.transpose(0, 2, 1)[..., None] + pv
            return acc_new, m_new, den_new

        acc, m, den = lax.fori_loop(0, nsub, sub, (acc, m, den))

        def rotate(kv):
            return jax.tree_util.tree_map(
                lambda x: lax.ppermute(
                    x,
                    axis_name,
                    [(j, (j + 1) % n_shards) for j in range(n_shards)],
                ),
                kv,
            )

        # the last step's rotation would be discarded — skip the exchange
        k, v = lax.cond(
            i < n_shards - 1, rotate, lambda kv: kv, (k, v)
        )
        return acc, m, den, k, v

    acc, m, den, _, _ = lax.fori_loop(
        0, n_shards, step, (acc, m, den, k0, v0)
    )
    den = jnp.where(den == 0.0, 1.0, den)  # fully-masked query rows
    out = acc / den.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def _batch_axis(mesh: Mesh):
    from paddle_tpu.core.mesh import DATA_AXIS

    return DATA_AXIS if DATA_AXIS in mesh.axis_names else None


def ring_attention(
    q, k, v, mesh: Mesh, *, axis: str = SEQ_AXIS, causal=False, kv_lens=None
):
    """q,k,v: [B, T, H, D] with T sharded over `axis` (and B over `data`
    when that axis exists). kv_lens: [B] valid lengths (global). Returns
    [B, T, H, D] sharded the same way."""
    n = mesh.shape[axis]
    D = q.shape[-1]
    scale = 1.0 / (D**0.5)
    Tq_local = q.shape[1] // n
    b = _batch_axis(mesh)
    spec = P(b, axis, None, None)

    def local(q, k, v, kv_lens):
        idx = lax.axis_index(axis)
        return _ring_body(
            axis, n, causal, scale, q, k, v, idx * Tq_local, kv_lens
        )

    if kv_lens is None:
        return jax.shard_map(
            lambda a, c, d: local(a, c, d, None),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )(q, k, v)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec, spec, P(b)),
        out_specs=spec,
        check_vma=False,
    )(q, k, v, kv_lens)


def ulysses_attention(
    q, k, v, mesh: Mesh, *, axis: str = SEQ_AXIS, causal=False,
    kv_lens=None, attn_impl="dense",
):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses style): reshard
    [B, T/s, H, D] -> [B, T, H/s, D], local attention per head group,
    reshard back. Heads must divide the axis size. `attn_impl` picks
    the local lowering: "dense" materializes the full local [T, T]
    scores (fine at short T); "flash" uses flash_dense_attention — at
    T >= 32k the dense local scores would be O(T^2) bytes, so the
    long-context multichip rows run flash locally."""
    n = mesh.shape[axis]
    H = q.shape[2]
    assert H % n == 0, f"heads {H} not divisible by seq shards {n}"

    def local(q, k, v, kv_lens):
        # local shapes: q [B, T/s, H, D] -> all_to_all over heads
        qh, kh, vh = (
            lax.all_to_all(x, axis, split_axis=2, concat_axis=1, tiled=True)
            for x in (q, k, v)
        )  # [B, T, H/s, D]
        if attn_impl == "flash":
            out = flash_dense_attention(
                qh, kh, vh, causal=causal, kv_len=kv_lens
            )
        else:
            out = dense_attention(
                qh, kh, vh, causal=causal, kv_len=kv_lens
            )
        return lax.all_to_all(
            out, axis, split_axis=1, concat_axis=2, tiled=True
        )

    b = _batch_axis(mesh)
    spec = P(b, axis, None, None)
    if kv_lens is None:
        return jax.shard_map(
            lambda x, y, z: local(x, y, z, None),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )(q, k, v)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec, spec, P(b)),
        out_specs=spec,
        check_vma=False,
    )(q, k, v, kv_lens)
