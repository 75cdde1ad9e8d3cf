"""The hybrid decoder of Mamba layers and differential attention (ISSUE 35):
`ops/selective_scan.py` (the portable lowering against a step-by-step loop,
the Pallas kernel in interpret mode against the portable lowering),
`diff_attention`, `mamba`, `layer_norm`, the tied head, `gqa_attention` at a
64-wide key over a 128-wide value, each alone and then together against the
plain float32 reference `benchmarks/reference/phi4flash.py`, at a tiny size
on the CPU (hidden 64, 8 query heads on 4 key heads of 8, a window of 8, 128
channels of 4 states, published layers 14-17 of 32, T 32), on seeded weights.

Tolerances: program and reference are both float32 here and differ in the
order of their sums (an associative scan against a step-by-step one, blocked
softmax, a chunked head), so a loss agrees to 1e-6 relative and a gradient
leaf to 2e-5 of its largest entry; where two lowerings of one kernel are held
together the same."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import phi4flash as R
from benchmarks.reference import train as RT
from benchmarks.tests.test_phi4flash_cell import tiny_phi_cell
from paddle_tpu import dsl
from paddle_tpu.core.arg import Arg
from paddle_tpu.models import phi4flash
from paddle_tpu.models.phi4flash import layer_kind
from paddle_tpu.network import Network
from paddle_tpu.ops import gqa_attention as GA
from paddle_tpu.ops import selective_scan as SS

LEAF_TOL = 2e-5
SCAN_ARGS = ("x", "dt", "A", "B", "C", "D")


def tiny_cfg(**over):
    """The tiny cell's configuration (benchmarks/tests/test_phi4flash_cell
    .py shrinks the widths, once)."""
    cfg = tiny_phi_cell().config
    cfg.update(over)
    return cfg


def batch(cfg, rows=2, t=32, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg["vocab_size"], (rows, t)).astype(np.int32)
    lab = rng.integers(0, cfg["vocab_size"], (rows, t)).astype(np.int32)
    lens = np.asarray([t] * rows, np.int32)
    feed = {"ids": Arg(ids=jnp.asarray(ids), seq_lens=jnp.asarray(lens)),
            "label": Arg(ids=jnp.asarray(lab), seq_lens=jnp.asarray(lens))}
    ref = {"ids": jnp.asarray(ids), "label": jnp.asarray(lab),
           "lens": jnp.asarray(lens)}
    return feed, ref


def leaf_gaps(got, want):
    return {k: float(np.abs(np.asarray(got[k]) - np.asarray(want[k])).max()
                     / (np.abs(np.asarray(want[k])).max() + 1e-30))
            for k in want}


def program_and_reference(cfg):
    net = Network(phi4flash(cfg))
    spec = R.param_spec(cfg)
    assert {k: tuple(v.dims) for k, v in net.param_confs.items()} == {
        k: tuple(s) for k, (s, _) in spec.items()}
    p = RT.init_params(spec, 7)
    feed, ref = batch(cfg)
    prog = jax.jit(jax.value_and_grad(
        lambda p: net.loss_fn(p, feed, train=True)[0]))
    plain = jax.jit(jax.value_and_grad(lambda p: R.loss(cfg, p, ref)))
    return prog(p), plain(p)


# ---- the scan ----

def scan_inputs(b, t, c, n, seed=0):
    r = np.random.default_rng(seed)
    args = (r.normal(size=(b, t, c)),
            np.log1p(np.exp(r.normal(size=(b, t, c)) - 2.0)),
            -np.exp(r.normal(size=(c, n))), r.normal(size=(b, t, n)),
            r.normal(size=(b, t, n)), r.normal(size=(c,)))
    weigh = jnp.asarray(r.normal(size=(b, t, c)), jnp.float32)
    return tuple(jnp.asarray(a, jnp.float32) for a in args), weigh


def step_by_step(x, dt, a, bm, cm, d):
    """The module's equations, one position at a time."""
    b, t, c = x.shape
    h = jnp.zeros((b, c, a.shape[1]), jnp.float32)
    out = []
    for i in range(t):
        h = (jnp.exp(dt[:, i, :, None] * a[None]) * h
             + (dt[:, i] * x[:, i])[:, :, None] * bm[:, i, None, :])
        out.append(jnp.sum(h * cm[:, i, None, :], -1) + d * x[:, i])
    return jnp.stack(out, 1)


def value_and_grads(fn, args, weigh):
    return jax.value_and_grad(lambda *a: jnp.sum(fn(*a) * weigh),
                              argnums=tuple(range(6)))(*args)


def gaps(got, want):
    return {name: float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w)))
            for name, g, w in zip(SCAN_ARGS, got, want)}


@pytest.mark.parametrize("t,chunk", [(20, 8), (16, 8), (5, 16), (33, 32)],
                         ids=["ragged", "whole", "short", "one-over"])
def test_portable_scan_is_the_step_by_step_recurrence(t, chunk):
    """Value and every gradient, T a multiple of the chunk and not."""
    args, weigh = scan_inputs(2, t, 24, 4, seed=t)
    v0, g0 = value_and_grads(step_by_step, args, weigh)
    v1, g1 = value_and_grads(
        lambda *a: SS.selective_scan(*a, impl="chunked", chunk=chunk),
        args, weigh)
    assert float(v1) == pytest.approx(float(v0), rel=1e-5)
    assert max(gaps(g1, g0).values()) < LEAF_TOL, gaps(g1, g0)
    np.testing.assert_allclose(
        SS.selective_scan(*args, impl="chunked", chunk=chunk),
        step_by_step(*args), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("c,n,chunk", [(256, 4, 8), (1024, 2, 16),
                                       (2048, 3, 8)],
                         ids=["two-tiles", "one-block", "two-blocks"])
def test_scan_kernel_is_the_portable_scan(c, n, chunk):
    """The Pallas kernel (interpret mode here) against the portable
    lowering: value, every gradient, the largest state at a chunk's start;
    channels as fewer than 8 tiles, as one block of 8, as two blocks."""
    args, weigh = scan_inputs(2, 32, c, n, seed=c)
    v0, g0 = value_and_grads(
        lambda *a: SS.selective_scan(*a, impl="chunked", chunk=chunk),
        args, weigh)
    v1, g1 = value_and_grads(
        lambda *a: SS.selective_scan(*a, impl="pallas", chunk=chunk),
        args, weigh)
    assert float(v1) == pytest.approx(float(v0), rel=2e-5)
    assert max(gaps(g1, g0).values()) < LEAF_TOL, gaps(g1, g0)
    s0, h0 = SS.selective_scan(*args, impl="chunked", chunk=chunk,
                               with_state_absmax=True)
    s1, h1 = SS.selective_scan(*args, impl="pallas", chunk=chunk,
                               with_state_absmax=True)
    np.testing.assert_allclose(s1, s0, rtol=2e-5, atol=2e-5)
    assert float(h1) == pytest.approx(float(h0), rel=1e-5) and float(h0) > 0
    # no gradient comes back through the watch
    g = jax.grad(lambda x: SS.selective_scan(
        x, *args[1:], impl="pallas", chunk=chunk,
        with_state_absmax=True)[1])(args[0])
    assert not np.any(np.asarray(g))


def test_scan_state_runs_across_chunks_and_takes_bfloat16_inputs():
    args, _ = scan_inputs(1, 32, 128, 2, seed=3)
    whole = SS.selective_scan(*args, impl="pallas", chunk=32)
    cut = SS.selective_scan(*args, impl="pallas", chunk=8)
    np.testing.assert_allclose(cut, whole, rtol=1e-5, atol=1e-5)
    # a decay near 1: position 31 still reads position 0's input
    x, dt, a, bm, cm, d = args
    slow = (x, dt, 1e-3 * a, jnp.ones_like(bm), jnp.ones_like(cm), 0 * d)
    base = SS.selective_scan(*slow, impl="pallas", chunk=8)
    moved = SS.selective_scan(x.at[:, 0].add(1.0), *slow[1:], impl="pallas",
                              chunk=8)
    assert float(jnp.min(jnp.abs(moved[:, 31] - base[:, 31]))) > 1e-3
    low = SS.selective_scan(x.astype(jnp.bfloat16), dt, a,
                            bm.astype(jnp.bfloat16), cm.astype(jnp.bfloat16),
                            d, impl="pallas", chunk=8)
    assert low.dtype == jnp.float32
    np.testing.assert_allclose(low, cut, rtol=0.05, atol=0.05)


def test_on_a_tpu_the_kernel_is_what_the_cells_shapes_get(monkeypatch):
    """The scan and the attention of this model choose their kernels by a
    TPU's rules: no silent portable loop on the chip."""
    assert SS.pallas_fits(8192, 5120) and SS.pallas_fits(256, 256)
    assert not SS.pallas_fits(8192, 5000) and not SS.pallas_fits(8200, 5120)
    assert not SS.pallas_fits(8192, 9 * 128)       # 9 tiles: no block of 8
    assert GA.pallas_fits(8192, 64, 128) and GA.pallas_fits(8192, 128)
    assert GA.pallas_fits(8192, 64)                 # 64/64 since ISSUE 41
    assert not GA.pallas_fits(8192, 32, 128) and not GA.pallas_fits(8192, 64, 96)
    taken = []
    monkeypatch.setattr(SS, "_pallas", lambda *a: taken.append(
        (a[0].shape, a[5], a[6])) or SS._chunked(*a[:6]))
    monkeypatch.setattr(SS.jax, "default_backend", lambda: "tpu")
    args, _ = scan_inputs(1, 256, 256, 2)
    SS.selective_scan(*args)
    assert taken == [((1, 256, 256), 128, False)]
    SS.selective_scan(*scan_inputs(1, 32, 24, 2)[0])    # the tiny test size
    assert len(taken) == 1
    with pytest.raises(ValueError, match="multiples of the chunk"):
        SS.selective_scan(*scan_inputs(1, 32, 24, 2)[0], impl="pallas")
    with pytest.raises(ValueError, match="unknown scan impl"):
        SS.selective_scan(*args, impl="cuda")


# ---- attention at a 64-wide key over a 128-wide value, and the difference ----

def _qkv(b, t, h, kv, d, dv, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (b, t, h, d)),
            jax.random.normal(ks[1], (b, t, kv, d)),
            jax.random.normal(ks[2], (b, t, kv, dv)))


def dense_attention(q, k, v, window=None):
    """softmax(mask(q k^T / sqrt(D))) v with the [T, T] scores whole."""
    b, t, h, d = q.shape
    g = h // k.shape[2]
    s = jnp.einsum("bqhd,bshd->bhqs", q, jnp.repeat(k, g, axis=2))
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    m = j <= i
    if window is not None:
        m = m & (i - j < window)
    p = jax.nn.softmax(jnp.where(m, s / math.sqrt(d), -jnp.inf), axis=-1)
    return jnp.einsum("bhqs,bshd->bqhd", p, jnp.repeat(v, g, axis=2))


@pytest.mark.parametrize("window", [None, 64], ids=["full", "window"])
def test_blocked_attention_takes_64_wide_keys_over_128_wide_values(window):
    q, k, v = _qkv(1, 256, 4, 2, 64, 128)
    weigh = jax.random.normal(jax.random.key(5), (1, 256, 4, 128))

    def run(fn):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * weigh),
            argnums=(0, 1, 2))(q, k, v)

    v0, g0 = run(lambda q, k, v: dense_attention(q, k, v, window))
    v1, g1 = run(lambda q, k, v: GA.gqa_attention(
        q, k, v, window=window, impl="blocked", block_q=64))
    assert float(v1) == pytest.approx(float(v0), rel=1e-5)
    for a, b in zip(g1, g0):
        assert float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))) < LEAF_TOL
    with jax.default_matmul_precision("highest"):
        ref = R.attend(q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2),
                       window, "f32", block=64)
    np.testing.assert_allclose(
        GA.gqa_attention(q, k, v, window=window, impl="blocked"), ref,
        rtol=2e-5, atol=2e-5)


def _one_layer(type_, **attrs):
    with dsl.model() as g:
        inp = dsl.data("x", dim=(64,), is_seq=True)
        dsl._add(type_, [inp], name="a", size=64, bias=False, **attrs)
    net = Network(g.conf)
    p = {k: 0.2 * jax.random.normal(jax.random.key(i), tuple(v.dims))
         for i, (k, v) in enumerate(sorted(net.param_confs.items()))}
    return net, p


@pytest.mark.parametrize("l,window", [(15, 8), (17, None)],
                         ids=["window", "full"])
def test_diff_attention_is_the_equations_in_plain_einsums(l, window):
    """The layer against the difference of two softmax maps written out
    head by head: differential head i = 2j + a from query heads 4j + a and
    4j + 2 + a on key heads 2j and 2j + 1, both over the pair's ONE value;
    value and every leaf's gradient; and against the reference's."""
    net, p = _one_layer("diff_attention", num_heads=8, num_kv_heads=4,
                        head_dim=8, window=window, layer_index=l,
                        epsilon=1e-5)
    assert {k: tuple(v.dims) for k, v in net.param_confs.items()} == {
        "_a.wqkv": (64, 128), "_a.bqkv": (128,), "_a.wo": (64, 64),
        "_a.bo": (64,), "_a.lambda_q1": (8,), "_a.lambda_k1": (8,),
        "_a.lambda_q2": (8,), "_a.lambda_k2": (8,), "_a.subln": (16,)}
    p["_a.subln"] = 1.0 + p["_a.subln"]
    x = jax.random.normal(jax.random.key(9), (2, 32, 64))
    weigh = jax.random.normal(jax.random.key(10), (2, 32, 64))
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * l)

    def plain(p):
        qkv = x @ p["_a.wqkv"] + p["_a.bqkv"]
        q = qkv[..., :64].reshape(2, 32, 8, 8)
        k = qkv[..., 64:96].reshape(2, 32, 4, 8)
        v = qkv[..., 96:].reshape(2, 32, 4, 8)
        lam = (jnp.exp(p["_a.lambda_q1"] @ p["_a.lambda_k1"])
               - jnp.exp(p["_a.lambda_q2"] @ p["_a.lambda_k2"]) + lam0)
        i_, j_ = jnp.arange(32)[:, None], jnp.arange(32)[None, :]
        m = j_ <= i_
        if window is not None:
            m = m & (i_ - j_ < window)

        def a_map(h):
            s = jnp.einsum("bqd,bsd->bqs", q[:, :, h], k[:, :, h // 2])
            w = jax.nn.softmax(jnp.where(m, s / math.sqrt(8), -jnp.inf), -1)
            pair = jnp.concatenate([v[:, :, 2 * (h // 4)],
                                    v[:, :, 2 * (h // 4) + 1]], -1)
            return jnp.einsum("bqs,bsd->bqd", w, pair)

        heads = []
        for i in range(4):
            j, a = divmod(i, 2)
            d = a_map(4 * j + a) - lam * a_map(4 * j + 2 + a)
            d = d / jnp.sqrt(jnp.mean(d * d, -1, keepdims=True) + 1e-5)
            heads.append((1 - lam0) * d * p["_a.subln"])
        return jnp.concatenate(heads, -1) @ p["_a.wo"] + p["_a.bo"]

    def layer(p):
        outs, _ = net.forward(p, {"x": Arg(
            value=x, seq_lens=jnp.asarray([32, 32]))})
        return outs["a"].value, outs["a@stats"].value

    with jax.default_matmul_precision("highest"):
        v0, g0 = jax.value_and_grad(lambda p: jnp.sum(plain(p) * weigh))(p)
        v1, g1 = jax.value_and_grad(
            lambda p: jnp.sum(layer(p)[0] * weigh))(p)
        cfg = tiny_cfg()
        want = R.diff_attention(
            cfg, {k.replace("_a.", f"_l{l}_attn."): v for k, v in p.items()},
            f"l{l}_attn", l, x, "f32")
    assert float(v1) == pytest.approx(float(v0), rel=1e-5)
    assert max(leaf_gaps(g1, g0).values()) < LEAF_TOL, leaf_gaps(g1, g0)
    out, lam = layer(p)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    # the gauge is the layer's learned lam
    assert lam.shape == (1, 1) and float(lam[0, 0]) == pytest.approx(float(
        jnp.exp(p["_a.lambda_q1"] @ p["_a.lambda_k1"])
        - jnp.exp(p["_a.lambda_q2"] @ p["_a.lambda_k2"]) + lam0), rel=1e-6)
    assert R.lambda_init(15) == pytest.approx(0.7933, abs=1e-4)
    assert R.lambda_init(17) == pytest.approx(0.7963, abs=1e-4)


def test_mamba_layer_is_the_references():
    net, p = _one_layer("mamba", d_state=4, d_conv=4, expand=2, dt_rank=4)
    assert {k: tuple(v.dims) for k, v in net.param_confs.items()} == {
        "_a.w_in": (64, 256), "_a.conv_w": (128, 4), "_a.conv_b": (128,),
        "_a.w_x": (128, 12), "_a.w_dt": (4, 128), "_a.b_dt": (128,),
        "_a.a_log": (128, 4), "_a.d": (128,), "_a.w_out": (128, 64)}
    x = jax.random.normal(jax.random.key(9), (2, 32, 64))
    weigh = jax.random.normal(jax.random.key(10), (2, 32, 64))
    cfg = tiny_cfg()

    def layer(p):
        outs, _ = net.forward(p, {"x": Arg(
            value=x, seq_lens=jnp.asarray([32, 32]))})
        return outs["a"].value, outs["a@stats"].value

    def ref(p):
        return R.mamba(cfg, {k.replace("_a.", "_l14_mamba."): v
                             for k, v in p.items()}, "l14_mamba", x, "f32")

    with jax.default_matmul_precision("highest"):
        v0, g0 = jax.value_and_grad(lambda p: jnp.sum(ref(p) * weigh))(p)
        v1, g1 = jax.value_and_grad(
            lambda p: jnp.sum(layer(p)[0] * weigh))(p)
    assert float(v1) == pytest.approx(float(v0), rel=1e-5)
    assert max(leaf_gaps(g1, g0).values()) < LEAF_TOL, leaf_gaps(g1, g0)
    assert float(layer(p)[1][0, 0]) > 0            # the largest |h| seen
    # the convolution is causal: the output at t reads nothing after t
    moved = dict(p)
    later = net.forward(p, {"x": Arg(value=x.at[:, 20:].add(1.0),
                                     seq_lens=jnp.asarray([32, 32]))})[0]
    np.testing.assert_array_equal(np.asarray(later["a"].value[:, :20]),
                                  np.asarray(layer(moved)[0][:, :20]))


def test_layer_norm_has_a_weight_and_a_bias_and_float32_inside():
    with dsl.model() as g:
        inp = dsl.data("x", dim=(64,), is_seq=True)
        dsl._add("layer_norm", [inp], name="n", bias=False, epsilon=1e-5)
    net = Network(g.conf)
    assert {k: tuple(v.dims) for k, v in net.param_confs.items()} == {
        "_n.w0": (64,), "_n.b0": (64,)}
    p = net.init_params(jax.random.key(0))
    np.testing.assert_array_equal(np.asarray(p["_n.w0"]), 1.0)
    np.testing.assert_array_equal(np.asarray(p["_n.b0"]), 0.0)
    p = {"_n.w0": jax.random.normal(jax.random.key(1), (64,)),
         "_n.b0": jax.random.normal(jax.random.key(2), (64,))}
    x = 3.0 + 2.0 * jax.random.normal(jax.random.key(3), (2, 5, 64))
    arg = Arg(value=x, seq_lens=jnp.asarray([5, 5]))
    got = net.forward(p, {"x": arg})[0]["n"].value
    mu, var = x.mean(-1, keepdims=True), x.var(-1, keepdims=True)
    want = (x - mu) / jnp.sqrt(var + 1e-5) * p["_n.w0"] + p["_n.b0"]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        got, R.layer_norm(x, p["_n.w0"], p["_n.b0"], 1e-5), rtol=1e-5,
        atol=1e-5)
    low = net.forward(p, {"x": Arg(value=x.astype(jnp.bfloat16),
                                   seq_lens=arg.seq_lens)})[0]["n"].value
    assert low.dtype == jnp.bfloat16
    np.testing.assert_allclose(low.astype(jnp.float32), want, rtol=0.02,
                               atol=0.05)


# ---- the builder ----

def test_builder_knows_which_published_layer_is_which():
    cfg = tiny_cfg()
    kinds = [layer_kind(cfg, l) for l in range(18)]
    assert kinds == ["mamba", "window"] * 8 + ["mamba", "full"]
    assert [R.kind_of(cfg, l) for l in range(18)] == kinds
    assert [layer_kind(cfg, l) for l in (14, 15, 16, 17)] == [
        "mamba", "window", "mamba", "full"]
    for l, what in ((18, "gated memory unit"), (19, "cross-attention"),
                    (30, "gated memory unit"), (31, "cross-attention")):
        for rule in (layer_kind, R.kind_of):
            with pytest.raises(NotImplementedError, match=what):
                rule(cfg, l)
    with pytest.raises(NotImplementedError, match="layer 17's keys"):
        layer_kind(cfg, 19)
    with pytest.raises(NotImplementedError, match="layer 16's scan"):
        layer_kind(cfg, 18)
    with pytest.raises(ValueError, match="layers 0 to 31"):
        layer_kind(cfg, 32)
    with pytest.raises(NotImplementedError, match="second decoder"):
        phi4flash(tiny_cfg(first_layer=16))        # 16-19: 18 is the first
    conf = phi4flash(cfg)
    types = [conf.layer(n).type for n in (
        "l14_mamba", "l15_attn", "l16_mamba", "l17_attn")]
    assert types == ["mamba", "diff_attention", "mamba", "diff_attention"]
    assert conf.layer("l15_attn").attrs["window"] == 8
    assert conf.layer("l17_attn").attrs["window"] is None
    assert [conf.layer(f"l{l}_attn").attrs["layer_index"]
            for l in (15, 17)] == [15, 17]
    assert conf.layer("l14_mamba").attrs["dt_rank"] == 4
    assert phi4flash(tiny_cfg(mamba_dt_rank="auto")).layer(
        "l14_mamba").attrs["dt_rank"] == 4         # ceil(64 / 16)
    assert conf.layer("head").attrs["tied_to"] == "emb"
    assert conf.recompute == [
        [f"l{l}_norm1", f"l{l}_{m}", f"l{l}_res1", f"l{l}_norm2",
         f"l{l}_mlp", f"l{l}_res2"]
        for l, m in ((14, "mamba"), (15, "attn"), (16, "mamba"),
                     (17, "attn"))]
    assert phi4flash(tiny_cfg(recompute=None)).recompute == []
    # the first decoder whole: 18 layers from 0, a head of its own untied
    whole = Network(phi4flash(tiny_cfg(first_layer=0, num_hidden_layers=18,
                                       tie_word_embeddings=False)))
    assert "_head.w0" in whole.param_confs and "_l0_mamba.a_log" in \
        whole.param_confs and "_l17_attn.subln" in whole.param_confs


# ---- the whole model ----

def test_loss_and_every_leafs_gradient_agree_with_the_reference():
    (l1, g1), (l2, g2) = program_and_reference(tiny_cfg())
    assert float(l1) == pytest.approx(float(l2), rel=1e-6)
    # a Mamba layer 16 leaves, an attention layer 16, embedding, final norm
    assert set(g1) == set(g2) and len(g1) == 2 * 16 + 2 * 16 + 3
    gaps_ = leaf_gaps(g1, g2)
    assert max(gaps_.values()) < LEAF_TOL, gaps_
    assert all(np.any(np.asarray(g)) for g in g2.values())


def test_the_tied_leafs_gradient_is_the_sum_of_its_two_uses():
    """ONE leaf `_emb.w0`, read as [V, D] by the embedding and as [D, V]
    by the head: against the same graph untied on the same values, its
    gradient is the embedding's plus the head's transposed."""
    tied, untied = tiny_cfg(), tiny_cfg(tie_word_embeddings=False)
    net, net2 = Network(phi4flash(tied)), Network(phi4flash(untied))
    assert "_head.w0" not in net.param_confs
    assert tuple(net.param_confs["_emb.w0"].dims) == (96, 64)
    assert tuple(net2.param_confs["_head.w0"].dims) == (64, 96)
    assert net.layer_params["head"] == {"w0": "_emb.w0"}
    p = RT.init_params(R.param_spec(tied), 7)
    feed, _ = batch(tied)
    l1, g1 = jax.value_and_grad(
        lambda p: net.loss_fn(p, feed, train=True)[0])(p)
    p2 = dict(p, **{"_head.w0": p["_emb.w0"].T})
    l2, g2 = jax.value_and_grad(
        lambda p: net2.loss_fn(p, feed, train=True)[0])(p2)
    assert float(l1) == pytest.approx(float(l2), rel=1e-6)
    both = g2["_emb.w0"] + g2["_head.w0"].T
    assert np.any(np.asarray(g2["_emb.w0"])) and np.any(
        np.asarray(g2["_head.w0"]))
    np.testing.assert_allclose(g1["_emb.w0"], both, rtol=1e-5,
                               atol=1e-6 * float(jnp.max(jnp.abs(both))))
    for k in g1:
        if k != "_emb.w0":
            np.testing.assert_allclose(g1[k], g2[k], rtol=1e-4, atol=1e-7)


def test_recomputation_on_and_off_give_the_same_gradients():
    (l1, g1), _ = program_and_reference(tiny_cfg(recompute="block"))
    (l2, g2), _ = program_and_reference(tiny_cfg(recompute=None))
    assert float(l1) == float(l2)
    assert max(leaf_gaps(g1, g2).values()) < 1e-6

    def remats(cfg):
        feed, _ = batch(cfg)
        net = Network(phi4flash(cfg))
        p = RT.init_params(R.param_spec(cfg), 7)
        return str(jax.make_jaxpr(
            lambda p: net.loss_fn(p, feed, train=True)[0])(p)).count("remat2[")

    # the four blocks; the portable scan's chunks are groups of their own
    assert remats(tiny_cfg(recompute="block")) == remats(
        tiny_cfg(recompute=None)) + 4


# ---- the precision policy ----

def test_under_the_bfloat16_policy_the_recurrences_leaves_stay_float32(
        monkeypatch):
    from paddle_tpu.core import flags

    cfg = tiny_cfg()
    net = Network(phi4flash(cfg))
    p = RT.init_params(R.param_spec(cfg), 7)
    feed, _ = batch(cfg)
    seen = {}
    plain = SS.selective_scan

    def spy(x, dt, a, bm, cm, d, **kw):
        seen.update(x=x.dtype, dt=dt.dtype, a=a.dtype, bm=bm.dtype,
                    d=d.dtype)
        out = plain(x, dt, a, bm, cm, d, **kw)
        seen["s"] = out[0].dtype
        return out

    monkeypatch.setattr(SS, "selective_scan", spy)
    attn = {}
    plain_attn = GA.gqa_attention

    def spy_attn(q, k, v, **kw):
        attn.update(q=q.dtype, shapes=(q.shape, k.shape, v.shape))
        return plain_attn(q, k, v, **kw)

    monkeypatch.setattr(GA, "gqa_attention", spy_attn)
    was = flags.get_flag("matmul_precision")
    flags.set_flag("matmul_precision", "bfloat16")
    try:
        jaxpr = str(jax.make_jaxpr(
            lambda p: net.loss_fn(p, feed, train=True)[0])(p))
    finally:
        flags.set_flag("matmul_precision", was)
    assert seen == {"x": jnp.bfloat16, "dt": jnp.float32, "a": jnp.float32,
                    "bm": jnp.bfloat16, "d": jnp.float32, "s": jnp.float32}
    # 8 query heads of 8 over 4 key heads of 8, each with a 16-wide value
    assert attn == {"q": jnp.bfloat16, "shapes": (
        (2, 32, 8, 8), (2, 32, 4, 8), (2, 32, 4, 16))}
    assert "bf16" in jaxpr


# ---- through SGD.train: the normal path, Adam, the gauges ----

def test_trains_through_sgd_train_against_the_reference_and_publishes_its_gauges():
    """Three steps of `SGD.train` on seeded weights against the plain
    reference's three steps of its own Adam: each loss, every leaf's first
    gradient as the optimizer got it, every leaf's change."""
    from paddle_tpu.core import flags
    from paddle_tpu.core.config import OptimizationConf
    from paddle_tpu.data import feeder as F
    from paddle_tpu.data.reader import batched
    from paddle_tpu.obs import metrics as om
    from paddle_tpu.trainer import SGD
    from paddle_tpu.trainer.events import EndIteration

    cfg = tiny_cfg()
    spec = R.param_spec(cfg)
    opt = dict(cfg["optimizer"], learning_rate=1e-2)
    rng = np.random.default_rng(1)
    rows = [(rng.integers(0, 96, 32).astype(np.int32),
             rng.integers(0, 96, 32).astype(np.int32)) for _ in range(6)]
    feeder = F.DataFeeder({"ids": 0, "label": 1}, {
        "ids": F.integer_value_sequence(96),
        "label": F.integer_value_sequence(96)})
    reg = om.get_registry()
    reg.reset_prefix("ssm.")
    reg.reset_prefix("attn.")
    was = flags.get_flag("timeline_sample_period")
    flags.set_flag("timeline_sample_period", 1)
    try:
        trainer = SGD(phi4flash(cfg), OptimizationConf(
            learning_method="adam", learning_rate=opt["learning_rate"],
            adam_beta1=opt["beta1"], adam_beta2=opt["beta2"],
            adam_epsilon=opt["epsilon"]), seed=3,
            params=RT.init_params(spec, 5))
        costs, grad1 = [], {}

        def handle(e):
            if isinstance(e, EndIteration):
                costs.append(e.cost)
                if len(costs) == 1:
                    grad1.update({
                        k: np.asarray(RT.first_gradient_from_state(opt, s))
                        for k, s in trainer.opt_state.items()})

        trainer.train(reader=batched(lambda: iter(rows), 2), feeder=feeder,
                      num_passes=1, event_handler=handle)
    finally:
        flags.set_flag("timeline_sample_period", was)
    assert {k: tuple(v.shape) for k, v in trainer.params.items()} == {
        k: tuple(s) for k, (s, _) in spec.items()}
    assert set(trainer.opt_state) == set(spec)     # a state for every leaf
    lens = jnp.asarray([32, 32], jnp.int32)
    batches = [{"ids": jnp.asarray(np.stack([a[0], b[0]])),
                "label": jnp.asarray(np.stack([a[1], b[1]])), "lens": lens}
               for a, b in zip(rows[::2], rows[1::2])]
    p0 = RT.init_params(spec, 5)
    want_grad = jax.grad(lambda p: R.loss(cfg, p, batches[0]))(p0)
    ref = RT.first_steps(lambda p, b: R.loss(cfg, p, b), opt,
                         RT.init_params(spec, 5), batches)
    assert costs == pytest.approx(ref["loss"], rel=1e-5)
    assert max(leaf_gaps(grad1, want_grad).values()) < LEAF_TOL
    moved = RT.delta(dict(trainer.params), p0)
    for k, want in ref["delta"].items():
        # three Adam steps of 1e-2 on a leaf: the norms agree to 5e-3 (a
        # gradient entry that is rounding alone, as the key bias's, which
        # no softmax sees, takes either sign under another order of sums,
        # and Adam makes a whole step of either sign)
        assert float(moved[k]) == pytest.approx(want, rel=5e-3), k
    for layer in ("l14_mamba", "l16_mamba"):
        assert reg.gauge("ssm.state_absmax").get(layer=layer) > 0
    for layer, l in (("l15_attn", 15), ("l17_attn", 17)):
        lam = reg.gauge("attn.lambda").get(layer=layer)
        assert abs(lam - R.lambda_init(l)) < 0.2 and lam != R.lambda_init(l)
    # what `python -m paddle_tpu metrics` prints
    text = reg.render_text()
    assert "ssm.state_absmax" in text and "attn.lambda" in text
    assert 'layer="l16_mamba"' in text or "l16_mamba" in text
