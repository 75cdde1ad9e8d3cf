"""The hybrid decoder of gated short convolutions and QK-normed grouped
attention over dense and routed-expert blocks (ISSUE 41): `short_conv`, the
helper `causal_depthwise` (ops/short_conv.py) it shares with `mamba`,
`gqa_attention` with `qk_norm`, the splash kernel at a 64-wide value head,
`models/lfm2.py` and the `moe` layer under sigmoid scores with a selection
bias 32 wide, each alone and then together against the plain float32 reference
`benchmarks/reference/lfm2.py`, at a tiny size on the CPU (hidden 64, 4 query
heads on 2 KV heads of 16; published layers 0 (conv, dense), 2 (attention)
and 3 (conv) over 32 experts top 4; T 32), on seeded weights.

Tolerances, as tests/test_kimi.py and tests/test_laguna.py set them: program
and reference are both float32 here and differ in the order of their sums
(blocked softmax, grouped products, chunked head, the convolution's sum), so
a loss agrees to 1e-6 relative and a gradient leaf to 2e-5 of its largest
entry; a block's output to 5e-6 absolute, a lone layer's on weights of std
0.2 to 0.3 to 1e-5; where one code is traced two ways, letter for letter.
A bfloat16 reference misses each by orders of magnitude: its gaps are the
1e-3 to 1e-2 of `benchmarks/tests/test_lfm2_cell.py`'s controls."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from benchmarks.reference import lfm2 as R
from benchmarks.reference import train as RT
from benchmarks.tests.test_lfm2_cell import tiny_lfm2_cell
from paddle_tpu import dsl, obs
from paddle_tpu import ops as OPS
from paddle_tpu.core.arg import Arg
from paddle_tpu.models import lfm2
from paddle_tpu.network import Network
from paddle_tpu.ops import gqa_attention as GA
from paddle_tpu.ops import moe as M, rope
from paddle_tpu.ops import selective_scan as _scan
from tests.test_kimi import batch, leaf_gaps

LEAF_TOL = 2e-5


def tiny_cfg(**over):
    """The tiny cell's configuration (benchmarks/tests/test_lfm2_cell.py
    shrinks the widths, once), here holding all 32 experts unless told."""
    cfg = tiny_lfm2_cell().config
    cfg.update(num_experts=32, experts_held_first=0)
    cfg.update(over)
    return cfg


def program_and_reference(cfg):
    net = Network(lfm2(cfg))
    spec = R.param_spec(cfg)
    assert {k: tuple(v.dims) for k, v in net.param_confs.items()} == {
        k: tuple(s) for k, (s, _) in spec.items()}
    p = RT.init_params(spec, 7)
    feed, ref = batch(cfg)
    prog = jax.jit(jax.value_and_grad(
        lambda p: net.loss_fn(p, feed, train=True)[0]))
    plain = jax.jit(jax.value_and_grad(lambda p: R.loss(cfg, p, ref)))
    return prog(p), plain(p)


def _one_layer(type_, **attrs):
    """One layer `a` of `type_` on a sequence `x` 64 wide."""
    with dsl.model() as g:
        inp = dsl.data("x", dim=(64,), is_seq=True)
        dsl._add(type_, [inp], name="a", size=64, bias=False, **attrs)
    return Network(g.conf)


def _seq(x):
    return {"x": Arg(value=x, seq_lens=jnp.asarray([x.shape[1]] * x.shape[0]))}


def _random(net, scale=0.2):
    return {k: scale * jax.random.normal(jax.random.key(i), tuple(v.dims))
            for i, (k, v) in enumerate(sorted(net.param_confs.items()))}


def _calls(path):
    return obs.get_registry().counter("attn.rope_calls").get(path=path)


# ---- the whole model ----

@pytest.mark.parametrize("share", [(0, 32), (8, 8)], ids=["whole", "share"])
def test_loss_and_every_leafs_gradient_agree_with_the_reference(share):
    cfg = tiny_cfg(experts_held_first=share[0], num_experts=share[1])
    (l1, g1), (l2, g2) = program_and_reference(cfg)
    assert float(l1) == pytest.approx(float(l2), rel=1e-6)
    # layer 0 (conv, dense) 8 leaves, layer 2 (attention, experts) 13, layer
    # 3 (conv, experts) 10, the tied embedding and the final norm
    assert set(g1) == set(g2) and len(g1) == 8 + 13 + 10 + 2
    gaps = leaf_gaps(g1, g2)
    assert max(gaps.values()) < LEAF_TOL, gaps
    for name in ("_l0_conv.conv_w", "_l3_conv.conv_w", "_l2_attn.q_norm",
                 "_l2_attn.k_norm"):
        assert np.any(np.asarray(g1[name])), name
    # the selection bias chooses and takes no gradient
    for l in (2, 3):
        bias = f"_l{l}_moe.e_score_correction_bias"
        assert not np.any(np.asarray(g1[bias]))
        assert not np.any(np.asarray(g2[bias]))


def test_the_graph_is_built_from_the_configs_own_keys():
    cfg = tiny_cfg()
    conf = lfm2(cfg)
    assert [lc.name for lc in conf.layers if lc.name.endswith(
        ("_conv", "_attn", "_mlp", "_moe"))] == [
        "l0_conv", "l0_mlp", "l2_attn", "l2_moe", "l3_conv", "l3_moe"]
    assert conf.layer("l0_conv").type == "short_conv"
    assert conf.layer("l0_conv").attrs["L"] == 3
    att = conf.layer("l2_attn").attrs
    assert (att["num_heads"], att["num_kv_heads"], att["head_dim"],
            att["window"], att["qk_norm"], att["epsilon"], att["rope"]) == (
        4, 2, 16, None, True, 1e-5, {"rope_theta": 1000000})
    assert att.get("gate") is None
    moe = conf.layer("l2_moe").attrs
    assert (moe["num_experts"], moe["top_k"], moe["held"], moe["hidden"],
            moe["scoring_func"], moe["topk_method"], moe["norm_topk"],
            moe["routed_scaling_factor"]) == (
        32, 4, (0, 32), 24, "sigmoid", "noaux_tc", True, 1)
    assert conf.layer("l0_mlp").attrs["hidden"] == 96
    assert conf.layer("head").attrs["tied_to"] == "emb"
    assert conf.layer("l0_norm1").attrs["epsilon"] == 1e-5
    assert conf.recompute == [
        [f"l{l}_norm1", f"l{l}_{m}", f"l{l}_res1", f"l{l}_norm2",
         f"l{l}_{f}", f"l{l}_res2"]
        for l, m, f in ((0, "conv", "mlp"), (2, "attn", "moe"),
                        (3, "conv", "moe"))]
    assert lfm2(tiny_cfg(recompute=None)).recompute == []
    # by default the first num_hidden_layers of the published list
    unsaid = tiny_cfg()
    del unsaid["layers_held"]
    first = lfm2(unsaid)
    assert [lc.name for lc in first.layers if lc.name.endswith(
        ("_conv", "_attn"))] == ["l0_conv", "l1_conv", "l2_attn"]
    assert first.layer("l1_mlp").type == "gated_mlp"   # published 1 is dense
    # no selection bias without use_expert_bias
    plain = Network(lfm2(tiny_cfg(use_expert_bias=False)))
    assert "_l2_moe.e_score_correction_bias" not in plain.param_confs
    with pytest.raises(AssertionError):
        lfm2(tiny_cfg(layers_held=[0, 2]))
    with pytest.raises(AssertionError, match="no bias"):
        lfm2(tiny_cfg(conv_bias=True))


def test_recomputation_on_and_off_give_the_same_gradients():
    (l1, g1), _ = program_and_reference(tiny_cfg(recompute="block"))
    (l2, g2), _ = program_and_reference(tiny_cfg(recompute=None))
    assert float(l1) == float(l2)
    assert max(leaf_gaps(g1, g2).values()) < 1e-6


def test_the_tied_leafs_gradient_is_the_sum_of_its_two_uses():
    """ONE leaf `_emb.w0`, read as [V, D] by the embedding and as [D, V]
    by the head: against the same graph untied on the same values, its
    gradient is the embedding's plus the head's transposed."""
    tied, untied = tiny_cfg(), tiny_cfg(tie_word_embeddings=False)
    net, net2 = Network(lfm2(tied)), Network(lfm2(untied))
    assert "_head.w0" not in net.param_confs
    assert net.layer_params["head"] == {"w0": "_emb.w0"}
    assert tuple(net2.param_confs["_head.w0"].dims) == (64, 96)
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


# ---- the share ----

@pytest.mark.parametrize("chips", [2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(chips):
    """The 32 experts on `chips` chips, 32 / chips each (4 chips of 8: the
    cell's deployment): every chip computes the QK-normed attention alike
    and its own experts' part of the routed result. Attention counted ONCE,
    the routed parts summed, are the uncut reference's whole layer."""
    each = 32 // chips
    cfg = tiny_cfg()
    p = RT.init_params(R.param_spec(cfg), 11)
    x = jax.random.normal(jax.random.key(3), (2, 32, 64))
    with jax.default_matmul_precision("highest"):
        whole = R.layer(cfg, p, 2, x, "f32")
        gates, chosen = R.route(cfg, p, "l2_moe", R.mixer_half(
            cfg, p, 2, x, "f32")[1], "f32")
    np.testing.assert_allclose(jnp.sum(gates, -1), 1.0, rtol=1e-6)
    experts = ("_l2_moe.w_gate", "_l2_moe.w_up", "_l2_moe.w_down")
    routed, once, here = [], None, 0.0
    for first in range(0, 32, each):
        share = dict(cfg, experts_held_first=first, num_experts=each)
        conf = lfm2(share)
        # the layer's graph alone, fed the residual stream
        with dsl.model() as g:
            dsl.data("x", dim=(64,), is_seq=True)
        block = [lc for lc in conf.layers if lc.name.startswith("l2_")]
        block[0].inputs[0].name = block[2].inputs[0].name = "x"
        g.conf.layers.extend(block)
        net = Network(g.conf)
        ps = {k: (v[first:first + each] if k in experts else v)
              for k, v in p.items() if k in net.param_confs}
        outs, _ = net.forward(ps, _seq(x))
        routed.append(outs["l2_moe"].value)
        same = outs["l2_res1"].value
        if once is None:
            once = same
        np.testing.assert_array_equal(same, once)   # every chip alike
        slots, on_chip = (float(s) for s in outs["l2_moe@stats"].value[0, :2])
        assert slots == 2 * 32 * 4
        # `moe.slots_here` is what the reference routes to the held experts
        assert on_chip == float(jnp.sum(
            (chosen >= first) & (chosen < first + each)))
        here += on_chip
        # what the share's own reference gives is the share's whole layer
        with jax.default_matmul_precision("highest"):
            mine = R.layer(share, ps, 2, x, "f32")
        np.testing.assert_allclose(outs["l2_res2"].value, mine, atol=5e-6)
    assert here == 2 * 32 * 4               # every slot on exactly one chip
    np.testing.assert_allclose(once + sum(routed), whole, atol=5e-6)


def test_the_selection_bias_changes_the_choice_and_not_the_weights():
    """Sigmoid scores 32 wide, top 4: a bias that lifts expert 5 makes every
    token choose it, and the weights stay each chosen expert's score over
    the chosen scores' sum, the bias in neither; program and reference
    choose alike and weigh alike."""
    cfg = tiny_cfg()
    ks = jax.random.split(jax.random.key(8), 3)
    x = jax.random.normal(ks[0], (128, 64))
    router = 0.3 * jax.random.normal(ks[1], (64, 32))
    bias = 0.1 * jax.random.normal(ks[2], (32,))
    score = jax.nn.sigmoid(x @ router)
    lifted = bias.at[5].set(10.0)
    for b in (bias, lifted):
        p = {"_m.router": router, "_m.e_score_correction_bias": b}
        w, e = M.route_topk(x, router, 4, scoring="sigmoid", bias=b)
        with jax.default_matmul_precision("highest"):
            gates, chosen = R.route(cfg, p, "m", x, "f32")
        np.testing.assert_array_equal(jnp.sort(e, -1), jnp.sort(chosen, -1))
        top = jnp.take_along_axis(score, e, axis=-1)
        np.testing.assert_allclose(w, top / jnp.sum(top, -1, keepdims=True),
                                   rtol=1e-5)
        np.testing.assert_allclose(
            jnp.take_along_axis(gates, e, axis=-1), w, rtol=1e-5)
    w0, e0 = M.route_topk(x, router, 4, scoring="sigmoid", bias=bias)
    w1, e1 = M.route_topk(x, router, 4, scoring="sigmoid", bias=lifted)
    assert bool(jnp.all(jnp.any(e1 == 5, -1)))
    assert not bool(jnp.all(jnp.any(e0 == 5, -1)))
    # where the choice did not change, neither did a weight
    kept = jnp.all(jnp.sort(e0, -1) == jnp.sort(e1, -1), -1)
    assert 0 < int(jnp.sum(kept)) < 128
    order0, order1 = jnp.argsort(e0, -1), jnp.argsort(e1, -1)
    # (to the sum's order: the four are added in the order chosen)
    np.testing.assert_allclose(
        jnp.take_along_axis(w0, order0, -1)[kept],
        jnp.take_along_axis(w1, order1, -1)[kept], rtol=1e-6)
    # expert 5's weight is its score's share, not lifted by the bias
    at5 = jnp.sum(jnp.where(e1 == 5, w1, 0.0), -1)
    top1 = jnp.take_along_axis(score, e1, axis=-1)
    np.testing.assert_allclose(at5, score[:, 5] / jnp.sum(top1, -1),
                               rtol=1e-5)
    assert float(jnp.max(at5)) < 1.0


# ---- the gated short convolution ----

def _mix_by_position(u, w_in, conv_w):
    """C * s of a short convolution, position by position in numpy
    float64, from its equations: s_t = sum_j w[:, j] z_{t-2+j}, z = B * x,
    nothing before a row's start."""
    u, w_in, conv_w = (np.asarray(a, np.float64) for a in (u, w_in, conv_w))
    d, big_l = conv_w.shape
    bcx = u @ w_in
    b_, c_, x_ = bcx[..., :d], bcx[..., d: 2 * d], bcx[..., 2 * d:]
    z = b_ * x_
    s = np.zeros_like(z)
    for t in range(z.shape[1]):
        for j in range(big_l):
            at = t - (big_l - 1) + j
            if at >= 0:
                s[:, t] += conv_w[:, j] * z[:, at]
    return c_ * s


def test_short_conv_is_its_equations_position_by_position():
    net = _one_layer("short_conv")
    assert {k: tuple(v.dims) for k, v in net.param_confs.items()} == {
        "_a.w_in": (64, 192), "_a.conv_w": (64, 3), "_a.w_out": (64, 64)}
    assert list(net.stat_outputs) == ["a@stats"]
    p = _random(net)
    x = jax.random.normal(jax.random.key(9), (2, 32, 64))
    outs, _ = net.forward(p, _seq(x))
    mix = _mix_by_position(x, p["_a.w_in"], p["_a.conv_w"])
    np.testing.assert_allclose(outs["a"].value, mix @ np.asarray(
        p["_a.w_out"], np.float64), rtol=1e-5, atol=1e-5)
    # the gauge: the largest |C * s|, ONE float
    stat = outs["a@stats"].value
    assert stat.shape == (1, 1) and stat.dtype == jnp.float32
    assert float(stat[0, 0]) == pytest.approx(float(np.abs(mix).max()),
                                              rel=1e-5)
    # a row's first two positions see zeros before it: s_0 = w_2 z_0 and
    # s_1 = w_1 z_0 + w_2 z_1
    bcx = np.asarray(x, np.float64) @ np.asarray(p["_a.w_in"], np.float64)
    z = bcx[..., :64] * bcx[..., 128:]
    w = np.asarray(p["_a.conv_w"], np.float64)
    np.testing.assert_allclose(
        mix[:, 0], bcx[:, 0, 64:128] * w[:, 2] * z[:, 0], rtol=1e-10)
    np.testing.assert_allclose(
        mix[:, 1], bcx[:, 1, 64:128] * (w[:, 1] * z[:, 0] + w[:, 2] * z[:, 1]),
        rtol=1e-10)
    # causal, row by row: a later position and the other row change nothing
    later = net.forward(p, _seq(x.at[:, 20:].add(1.0)))[0]["a"].value
    np.testing.assert_array_equal(later[:, :20], outs["a"].value[:, :20])
    other = net.forward(p, _seq(x.at[1].add(1.0)))[0]["a"].value
    np.testing.assert_array_equal(other[0], outs["a"].value[0])


def test_short_conv_and_its_gradient_are_the_references():
    net = _one_layer("short_conv", L=3)
    p = _random(net, 0.3)
    x = jax.random.normal(jax.random.key(9), (2, 32, 64))
    weigh = jax.random.normal(jax.random.key(10), (2, 32, 64))
    cfg = tiny_cfg()

    def layer(p):
        return net.forward(p, _seq(x))[0]["a"].value

    def ref(p):
        return R.short_conv(cfg, {k.replace("_a.", "_l0_conv."): v
                                  for k, v in p.items()}, 0, x, "f32")

    with jax.default_matmul_precision("highest"):
        v0, g0 = jax.value_and_grad(lambda p: jnp.sum(ref(p) * weigh))(p)
        v1, g1 = jax.value_and_grad(lambda p: jnp.sum(layer(p) * weigh))(p)
    assert float(v1) == pytest.approx(float(v0), rel=1e-5)
    assert max(leaf_gaps(g1, g0).values()) < LEAF_TOL, leaf_gaps(g1, g0)


def test_under_the_bfloat16_policy_the_convolution_mixes_in_float32():
    from paddle_tpu.core import flags

    net = _one_layer("short_conv")
    p = _random(net)
    x = jax.random.normal(jax.random.key(9), (2, 32, 64))
    was = flags.get_flag("matmul_precision")
    flags.set_flag("matmul_precision", "bfloat16")
    try:
        outs, _ = net.forward(p, _seq(x))
        text = str(jax.make_jaxpr(
            lambda p: net.forward(p, _seq(x))[0]["a"].value)(p))
    finally:
        flags.set_flag("matmul_precision", was)
    assert outs["a"].value.dtype == jnp.bfloat16
    assert "f32[2,32,64]" in text and "bf16[2,32,192]" in text
    mix = _mix_by_position(x, p["_a.w_in"], p["_a.conv_w"])
    want = mix @ np.asarray(p["_a.w_out"], np.float64)
    np.testing.assert_allclose(outs["a"].value.astype(jnp.float32), want,
                               rtol=0.05, atol=0.05)


# ---- the helper mamba shares, and a mamba layer as it was ----

def _parent_mamba_forward(params, u, a):
    """`MambaLayer.forward` as it stood before the convolution became a
    helper, letter for letter, under the scope `Network` gives it; -> the
    output and the gauge's extra output."""
    t = u.shape[1]
    c, n, k, r = (a.get("expand", 2) * u.shape[-1], a.get("d_state", 16),
                  a.get("d_conv", 4), a["dt_rank"])
    with jax.named_scope("mamba:a"):
        with jax.named_scope("ssm.in"):
            xz = jnp.dot(u, params["w_in"])
            xr, z = xz[..., :c], xz[..., c:]
        with jax.named_scope("ssm.conv"):
            padded = jnp.pad(xr, ((0, 0), (k - 1, 0), (0, 0))).astype(
                jnp.float32)
            w = params["conv_w"].astype(jnp.float32)
            acc = params["conv_b"] + sum(
                w[:, j] * padded[:, j: j + t] for j in range(k))
            x = jax.nn.silu(acc).astype(u.dtype)
        with jax.named_scope("ssm.proj"):
            rbc = jnp.dot(x, params["w_x"])
            dt = jax.nn.softplus(
                jnp.dot(rbc[..., :r], params["w_dt"],
                        preferred_element_type=jnp.float32)
                + params["b_dt"])
        with jax.named_scope("ssm.scan"):
            s, hmax = _scan.selective_scan(
                x, dt, -jnp.exp(params["a_log"]), rbc[..., r: r + n],
                rbc[..., r + n:], params["d"], with_state_absmax=True)
            stats = lax.stop_gradient(hmax).astype(jnp.float32).reshape(1, 1)
        with jax.named_scope("ssm.gate"):
            g = (s * jax.nn.silu(z.astype(jnp.float32))).astype(u.dtype)
        with jax.named_scope("ssm.out"):
            y = jnp.dot(g, params["w_out"])
    return y, stats


def test_a_mamba_layer_with_the_shared_helper_traces_as_the_parents():
    """The Phi cell's mixer (d_conv 4, with a bias) at a tiny width: the
    parent's jaxpr letter for letter, forward and gradient, and its values
    bit for bit."""
    attrs = dict(d_state=4, d_conv=4, expand=2, dt_rank=4)
    net = _one_layer("mamba", **attrs)
    p = _random(net)
    x = jax.random.normal(jax.random.key(9), (2, 32, 64))

    def now(p, x):
        outs = net.forward(p, _seq(x))[0]
        return outs["a"].value, outs["a@stats"].value

    def then(p, x):
        return _parent_mamba_forward({k[3:]: v for k, v in p.items()}, x,
                                     attrs)

    assert str(jax.make_jaxpr(now)(p, x)) == str(jax.make_jaxpr(then)(p, x))
    grad = (lambda f: jax.grad(lambda p, x: jnp.sum(jnp.sin(f(p, x)[0]))))
    assert (str(jax.make_jaxpr(grad(now))(p, x))
            == str(jax.make_jaxpr(grad(then))(p, x)))
    for a, b in zip(now(p, x), then(p, x)):
        np.testing.assert_array_equal(a, b)


# ---- QK-norm ----

def test_qk_norm_is_an_rms_norm_a_head_before_the_rotary_positions():
    """Against its definition, written out: each head's q and k over its
    16 lanes normed with their own weights, then turned, then attended;
    value and every leaf's gradient against the reference's layer too."""
    cfg = tiny_cfg()
    net = _one_layer("gqa_attention", num_heads=4, num_kv_heads=2,
                     head_dim=16, window=None, qk_norm=True, epsilon=1e-5,
                     rope={"rope_theta": 1000000})
    assert {k: tuple(v.dims) for k, v in net.param_confs.items()} == {
        "_a.wq": (64, 64), "_a.wk": (64, 32), "_a.wv": (64, 32),
        "_a.wo": (64, 64), "_a.q_norm": (16,), "_a.k_norm": (16,)}
    assert net.stat_outputs == {}
    init = net.init_params(jax.random.key(0))
    np.testing.assert_array_equal(np.asarray(init["_a.q_norm"]), 1.0)
    np.testing.assert_array_equal(np.asarray(init["_a.k_norm"]), 1.0)
    p = _random(net, 0.3)
    p["_a.q_norm"] = 1.0 + p["_a.q_norm"]
    p["_a.k_norm"] = 1.0 + p["_a.k_norm"]
    x = jax.random.normal(jax.random.key(9), (2, 32, 64))
    # the heads of q differ in scale: a norm over the whole row is not this
    x = x * jnp.linspace(0.5, 3.0, 64)

    def by_definition(p):
        def norm(v, w):
            return w * v / jnp.sqrt(jnp.mean(v * v, -1, keepdims=True) + 1e-5)

        q = norm((x @ p["_a.wq"]).reshape(2, 32, 4, 16), p["_a.q_norm"])
        k = norm((x @ p["_a.wk"]).reshape(2, 32, 2, 16), p["_a.k_norm"])
        v = (x @ p["_a.wv"]).reshape(2, 32, 2, 16)
        q, k = R.rotary(q, 1000000), R.rotary(k, 1000000)
        s = jnp.einsum("bqhd,bshd->bhqs", q, jnp.repeat(k, 2, axis=2)) / 4.0
        causal = jnp.arange(32)[:, None] >= jnp.arange(32)[None, :]
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        o = jnp.einsum("bhqs,bshd->bqhd", a, jnp.repeat(v, 2, axis=2))
        return o.reshape(2, 32, 64) @ p["_a.wo"]

    def layer(p):
        return net.forward(p, _seq(x))[0]["a"].value

    def ref(p):
        return R.qk_norm_attention(cfg, {k.replace("_a.", "_l2_attn."): v
                                         for k, v in p.items()}, 2, x, "f32")

    weigh = jax.random.normal(jax.random.key(10), (2, 32, 64))
    with jax.default_matmul_precision("highest"):
        got = layer(p)
        np.testing.assert_allclose(got, by_definition(p), rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(got, ref(p), rtol=1e-5, atol=1e-5)
        g1 = jax.grad(lambda p: jnp.sum(layer(p) * weigh))(p)
        g0 = jax.grad(lambda p: jnp.sum(by_definition(p) * weigh))(p)
    assert max(leaf_gaps(g1, g0).values()) < LEAF_TOL, leaf_gaps(g1, g0)
    assert np.any(np.asarray(g1["_a.q_norm"]))
    # without the norm the layer is another function
    plain = _one_layer("gqa_attention", num_heads=4, num_kv_heads=2,
                       head_dim=16, window=None, rope={"rope_theta": 1000000})
    pp = {k: v for k, v in p.items() if "norm" not in k}
    assert not np.allclose(plain.forward(pp, _seq(x))[0]["a"].value, got,
                           atol=1e-3)


def test_under_the_bfloat16_policy_the_qk_norm_weights_stay_float32(
        monkeypatch):
    from paddle_tpu.core import flags
    from paddle_tpu.layers import decoder

    net = _one_layer("gqa_attention", num_heads=4, num_kv_heads=2,
                     head_dim=16, window=None, qk_norm=True,
                     rope={"rope_theta": 10000})
    p = _random(net)
    x = jax.random.normal(jax.random.key(9), (2, 32, 64))
    seen = []
    plain = decoder._rms

    def spy(v, w, eps):
        seen.append((v.dtype, w.dtype, eps))
        return plain(v, w, eps)

    monkeypatch.setattr(decoder, "_rms", spy)
    was = flags.get_flag("matmul_precision")
    flags.set_flag("matmul_precision", "bfloat16")
    try:
        out = net.forward(p, _seq(x))[0]["a"].value
    finally:
        flags.set_flag("matmul_precision", was)
    assert out.dtype == jnp.bfloat16
    assert seen == [(jnp.bfloat16, jnp.float32, 1e-6)] * 2     # q, then k


def test_a_qk_normed_layer_takes_the_plain_rotary_path_on_a_tpu(monkeypatch):
    """Where the rotary pass would fit (heads of 128, T 128), the layer
    without the norm takes it and the QK-normed one takes the plain
    composition: the pass has no norm inside."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(OPS, "pallas_interpret", lambda requested=None: True)
    x = jnp.zeros((1, 128, 64))
    for qk_norm, path in ((False, "pass"), (True, "plain")):
        with dsl.model() as g:
            inp = dsl.data("x", dim=(64,), is_seq=True)
            dsl._add("gqa_attention", [inp], name="a", size=64, bias=False,
                     num_heads=2, num_kv_heads=1, head_dim=128, window=None,
                     rope={"rope_theta": 10000}, qk_norm=qk_norm)
        net = Network(g.conf)
        p = _random(net)
        before = _calls("plain"), _calls("pass")
        text = str(jax.make_jaxpr(
            lambda p: net.forward(p, _seq(x))[0]["a"].value)(p))
        after = _calls("plain"), _calls("pass")
        assert after[path == "pass"] == before[path == "pass"] + 2
        assert after[path != "pass"] == before[path != "pass"]
        assert ("rope_to_heads" in text) == (not qk_norm)


def _parent_forward(params, x, a):
    """`GQAAttentionLayer.forward` as it stood before QK-norm, letter for
    letter, on the way a CPU takes (the Mellum and the Laguna cell's layers
    there), but for the gauge, under the scope `Network` gives it."""
    hd, h, kv = a["head_dim"], a["num_heads"], a["num_kv_heads"]
    b, t, _ = x.shape
    r = rope.rotary_width(hd, a["rope"])
    with jax.named_scope("gqa_attention:a"):
        q = jnp.dot(x, params["wq"]).reshape(b, t, h, hd)
        k = jnp.dot(x, params["wk"]).reshape(b, t, kv, hd)
        v = jnp.dot(x, params["wv"]).reshape(b, t, kv, hd)
        with jax.named_scope("attn.rope"):
            cos, sin = rope.tables(t, r, a["rope"])
            q, k = rope.apply(q, cos, sin), rope.apply(k, cos, sin)
        with jax.named_scope("attn.core"):
            o = GA.gqa_attention(q, k, v, window=a.get("window"))
        if a.get("gate"):
            with jax.named_scope("attn.gate"):
                g = jax.nn.sigmoid(jnp.dot(
                    x, params["wg"], preferred_element_type=jnp.float32))
                stats = lax.stop_gradient(jnp.mean(g)).astype(
                    jnp.float32).reshape(1, 1)
                o = o * g.astype(o.dtype)[..., None]
        y = jnp.dot(o.reshape(b, t, h * hd), params["wo"])
    return (y, stats) if a.get("gate") else (y,)


MELLUM_WINDOW = {"rope_type": "default", "rope_theta": 500000}
LAGUNA_FULL = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
               "original_max_position_embeddings": 4096, "beta_slow": 1,
               "beta_fast": 64, "attention_factor": 1.4158883083359672,
               "partial_rotary_factor": 0.5}


@pytest.mark.parametrize("said", ["unsaid", "said"])
@pytest.mark.parametrize("kind", ["mellum-window", "laguna-full-gated"])
def test_a_layer_without_qk_norm_traces_as_the_parents(kind, said):
    """The Mellum cell's window layer and the Laguna cell's gated full layer,
    at a tiny width: built without `qk_norm`, or with it False, the parent's
    parameters and jaxpr letter for letter, forward and gradient."""
    attrs = (dict(num_heads=4, num_kv_heads=2, head_dim=16, window=8,
                  rope=dict(MELLUM_WINDOW)) if kind == "mellum-window" else
             dict(num_heads=4, num_kv_heads=2, head_dim=16, window=None,
                  rope=dict(LAGUNA_FULL), gate="per_head"))
    net = _one_layer("gqa_attention", **attrs, **(
        {"qk_norm": False} if said == "said" else {}))
    assert sorted(net.param_confs) == sorted(
        ["_a.wk", "_a.wo", "_a.wq", "_a.wv"]
        + (["_a.wg"] if attrs.get("gate") else []))
    p = _random(net)
    x = jax.random.normal(jax.random.key(9), (2, 32, 64))

    def now(p, x):
        outs = net.forward(p, _seq(x))[0]
        return ((outs["a"].value, outs["a@stats"].value) if attrs.get("gate")
                else (outs["a"].value,))

    def then(p, x):
        return _parent_forward({k[3:]: v for k, v in p.items()}, x, attrs)

    assert str(jax.make_jaxpr(now)(p, x)) == str(jax.make_jaxpr(then)(p, x))
    grad = (lambda f: jax.grad(lambda p, x: jnp.sum(jnp.sin(f(p, x)[0]))))
    assert (str(jax.make_jaxpr(grad(now))(p, x))
            == str(jax.make_jaxpr(grad(then))(p, x)))
    for a, b in zip(now(p, x), then(p, x)):
        np.testing.assert_array_equal(a, b)


# ---- the kernel at a 64-wide value head ----

@pytest.mark.parametrize("h,kv", [(4, 1), (8, 2)])
def test_the_kernel_and_the_blocked_lowering_agree_at_64_64(h, kv):
    """4 query heads a KV head of 64, values 64 wide (32 on 8 in the cell),
    under a full causal mask, in interpret mode on the CPU, float32."""
    assert GA.pallas_fits(8192, 64, 64) and GA.pallas_fits(256, 64)
    ks = jax.random.split(jax.random.key(4), 3)
    q = jax.random.normal(ks[0], (1, 256, h, 64))
    k = jax.random.normal(ks[1], (1, 256, kv, 64))
    v = jax.random.normal(ks[2], (1, 256, kv, 64))

    def loss(**how):
        return lambda q, k, v: jnp.sum(jnp.sin(GA.gqa_attention(q, k, v,
                                                                **how)))

    kernel = dict(impl="pallas", block_q=128, block_kv=128)
    np.testing.assert_allclose(GA.gqa_attention(q, k, v, **kernel),
                               GA._blocked(q, k, v, None, 64), atol=5e-6)
    got = jax.grad(loss(**kernel), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(impl="blocked", block_q=64), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-5)
    full = GA.kernel_tiles(8192, 64, 64, None)
    assert (full.block_q, full.block_kv, full.block_kv_compute,
            full.use_fused_bwd_kernel) == (1024, 1024, 512, True)


# ---- through SGD.train: the normal path, Adam, the gauges ----

def test_trains_through_sgd_train_and_publishes_its_gauges_and_counters():
    from paddle_tpu.core import flags
    from paddle_tpu.core.config import OptimizationConf
    from paddle_tpu.data import feeder as F
    from paddle_tpu.data.reader import batched
    from paddle_tpu.obs import metrics as om
    from paddle_tpu.trainer import SGD
    from paddle_tpu.trainer.events import EndIteration

    cfg = tiny_cfg(num_experts=8, experts_held_first=8)
    spec = R.param_spec(cfg)
    p0 = RT.init_params(spec, 5)
    rng = np.random.default_rng(1)
    rows = [(rng.integers(0, 96, 32).astype(np.int32),
             rng.integers(0, 96, 32).astype(np.int32)) for _ in range(4)]
    feeder = F.DataFeeder({"ids": 0, "label": 1}, {
        "ids": F.integer_value_sequence(96),
        "label": F.integer_value_sequence(96)})
    reg = om.get_registry()
    reg.reset_prefix("moe.")
    reg.reset_prefix("conv.")
    # what the reference says of the FIRST batch on the seeded weights
    ids = jnp.asarray(np.stack([rows[0][0], rows[1][0]]))
    with jax.default_matmul_precision("highest"):
        x = p0["_emb.w0"][ids]
        a0 = R.rms(x, p0["_l0_norm1.w0"], cfg["norm_eps"])
        mix0 = float(np.abs(_mix_by_position(
            a0, p0["_l0_conv.w_in"], p0["_l0_conv.conv_w"])).max())
        x = R.layer(cfg, p0, 0, x, "f32")
        _, chosen = R.route(cfg, p0, "l2_moe", R.mixer_half(
            cfg, p0, 2, x, "f32")[1], "f32")
    here0 = float(jnp.sum((chosen >= 8) & (chosen < 16)))
    biases = {l: np.asarray(p0[f"_l{l}_moe.e_score_correction_bias"])
              for l in (2, 3)}
    was = flags.get_flag("timeline_sample_period")
    flags.set_flag("timeline_sample_period", 1)
    try:
        trainer = SGD(lfm2(cfg), OptimizationConf(
            learning_method="adam", learning_rate=1e-2, adam_beta2=0.95),
            seed=3, params=p0)
        costs, first = [], {}

        def handle(e):
            if isinstance(e, EndIteration):
                costs.append(e.cost)
                if len(costs) == 1:      # fenced every step: after step 1
                    first.update(
                        mix=reg.gauge("conv.mix_absmax").get(layer="l0_conv"),
                        here=reg.counter("moe.slots_here").get(
                            layer="l2_moe"))

        trainer.train(reader=batched(lambda: iter(rows * 4), 2),
                      feeder=feeder, num_passes=1, event_handler=handle)
    finally:
        flags.set_flag("timeline_sample_period", was)
    assert len(costs) == 8 and costs[-1] < costs[0]    # the fixed rows learn
    assert {k: tuple(v.shape) for k, v in trainer.params.items()} == {
        k: tuple(s) for k, (s, _) in spec.items()}
    # the selection bias is a constant of the job
    for l in (2, 3):
        np.testing.assert_array_equal(
            trainer.params[f"_l{l}_moe.e_score_correction_bias"], biases[l])
    # the gauge is the reference's largest |C * s|, the counter what the
    # reference routes to the experts held
    assert first["mix"] == pytest.approx(mix0, rel=1e-5)
    assert first["here"] == here0
    for l in (0, 3):
        assert reg.gauge("conv.mix_absmax").get(layer=f"l{l}_conv") > 0
    for l in (2, 3):
        layer = f"l{l}_moe"
        assert reg.counter("moe.slots").get(layer=layer) == 8 * 256
        here = reg.counter("moe.slots_here").get(layer=layer)
        assert 0 < here < 8 * 256
    text = reg.render_text()
    for name in ("conv.mix_absmax", "moe.slots", "moe.slots_here"):
        assert name in text
