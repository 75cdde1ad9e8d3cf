"""The latent-attention decoder with shared-beside-routed experts (ISSUE 33):
`mla_attention` (a head's key 24 wide, its value 16, up from a latent of 32,
one rotary key for all heads), `gated_mlp` (the dense layer and the shared
experts), the `moe` layer under sigmoid scores with a selection bias and a
scale, `gqa_attention` with a value head narrower than the query/key head,
each alone and then together against the plain float32 reference
`benchmarks/reference/kimi.py`, at a tiny size on the CPU (hidden 64, 4
heads, a dense layer and two expert layers of 8 experts top-2 beside 2 shared
experts, T 32), on seeded weights.

Tolerances: program and reference are both float32 here and differ in the
order of their sums (blocked softmax, grouped products, chunked head), so a
loss agrees to 1e-6 relative and a gradient leaf to 2e-5 of its largest
entry; where two lowerings of one kernel are held together the same."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import kimi as R
from benchmarks.reference import train as RT
from benchmarks.tests.test_kimi_cell import tiny_kimi_cell
from paddle_tpu.core.arg import Arg
from paddle_tpu.models import kimi
from paddle_tpu.network import Network
from paddle_tpu.ops import gqa_attention as GA
from paddle_tpu.ops import moe as M, rope

LEAF_TOL = 2e-5


def tiny_cfg(**over):
    """The tiny cell's configuration (benchmarks/tests/test_kimi_cell.py
    shrinks the widths, once), here holding all 8 experts unless told."""
    cfg = tiny_kimi_cell().config
    cfg.update(n_routed_experts=8, experts_held_first=0)
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
    net = Network(kimi(cfg))
    spec = R.param_spec(cfg)
    assert {k: tuple(v.dims) for k, v in net.param_confs.items()} == {
        k: tuple(s) for k, (s, _) in spec.items()}
    p = RT.init_params(spec, 7)
    feed, ref = batch(cfg)
    prog = jax.jit(jax.value_and_grad(
        lambda p: net.loss_fn(p, feed, train=True)[0]))
    plain = jax.jit(jax.value_and_grad(lambda p: R.loss(cfg, p, ref)))
    return prog(p), plain(p)


# ---- the whole model ----

@pytest.mark.parametrize("share", [(0, 8), (2, 4)], ids=["whole", "share"])
def test_loss_and_every_leafs_gradient_agree_with_the_reference(share):
    cfg = tiny_cfg(experts_held_first=share[0], n_routed_experts=share[1])
    (l1, g1), (l2, g2) = program_and_reference(cfg)
    assert float(l1) == pytest.approx(float(l2), rel=1e-6)
    # a dense layer 10 leaves, an expert layer 15, embedding, norm and head
    assert set(g1) == set(g2) and len(g1) == 10 + 2 * 15 + 3
    gaps = leaf_gaps(g1, g2)
    assert max(gaps.values()) < LEAF_TOL, gaps
    # no gradient reaches the selection bias, in either
    for g in (g1, g2):
        for i in (1, 2):
            assert not np.any(np.asarray(
                g[f"_l{i}_moe.e_score_correction_bias"]))


def test_recomputation_on_and_off_give_the_same_gradients():
    on, off = tiny_cfg(recompute="block"), tiny_cfg(recompute=None)
    conf = kimi(on)
    # a block with three branches into the residual is one group
    assert conf.recompute == [
        ["l0_norm1", "l0_attn", "l0_res1", "l0_norm2", "l0_mlp", "l0_res2"],
        ["l1_norm1", "l1_attn", "l1_res1", "l1_norm2", "l1_moe", "l1_shared",
         "l1_res2"],
        ["l2_norm1", "l2_attn", "l2_res1", "l2_norm2", "l2_moe", "l2_shared",
         "l2_res2"]]
    assert conf.layer("l1_res2").input_names() == [
        "l1_res1", "l1_moe", "l1_shared"]
    assert kimi(off).recompute == []
    (l1, g1), _ = program_and_reference(on)
    (l2, g2), _ = program_and_reference(off)
    assert float(l1) == float(l2)
    assert max(leaf_gaps(g1, g2).values()) < 1e-6

    def remats(cfg):
        feed, _ = batch(cfg)
        net = Network(kimi(cfg))
        p = RT.init_params(R.param_spec(cfg), 7)
        return str(jax.make_jaxpr(
            lambda p: net.loss_fn(p, feed, train=True)[0])(p)).count("remat2[")

    assert remats(on) == remats(off) + 3


# ---- the share ----

def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Experts 0..7 on eight chips, one each: every chip computes attention
    and the shared experts alike and its own expert's part of the routed
    result. Attention and the shared experts counted ONCE, the eight routed
    parts summed, are the uncut reference's whole layer."""
    from paddle_tpu import dsl

    cfg = tiny_cfg(num_hidden_layers=1, first_k_dense_replace=0)
    p = RT.init_params(R.param_spec(cfg), 11)
    # a bias large enough to decide choices
    p["_l0_moe.e_score_correction_bias"] = 0.3 * jax.random.normal(
        jax.random.key(2), (8,))
    x = jax.random.normal(jax.random.key(3), (2, 32, 64))
    with jax.default_matmul_precision("highest"):
        whole = R.layer(cfg, p, 0, x, "f32")
    experts = ("_l0_moe.w_gate", "_l0_moe.w_up", "_l0_moe.w_down")
    routed, once, here = [], None, 0.0
    for first in range(8):
        share = dict(cfg, experts_held_first=first, n_routed_experts=1)
        conf = kimi(share)
        # the layer's graph alone, fed the residual stream
        with dsl.model() as g:
            dsl.data("x", dim=(64,), is_seq=True)
        block = [lc for lc in conf.layers if lc.name.startswith("l0_")]
        block[0].inputs[0].name = block[2].inputs[0].name = "x"
        g.conf.layers.extend(block)
        net = Network(g.conf)
        ps = {k: (v[first:first + 1] if k in experts else v)
              for k, v in p.items() if k in net.param_confs}
        outs, _ = net.forward(
            ps, {"x": Arg(value=x, seq_lens=jnp.asarray([32, 32]))})
        routed.append(outs["l0_moe"].value)
        same = outs["l0_res1"].value + outs["l0_shared"].value
        if once is None:
            once = same
        np.testing.assert_array_equal(same, once)   # every chip alike
        slots, on_chip = (float(s) for s in outs["l0_moe@stats"].value[0, :2])
        assert slots == 128.0
        here += on_chip
        # what the share's own reference gives is the share's whole layer
        with jax.default_matmul_precision("highest"):
            mine = R.layer(share, ps, 0, x, "f32")
        np.testing.assert_allclose(outs["l0_res2"].value, mine, atol=3e-6)
    assert here == 128.0                    # every slot on exactly one chip
    np.testing.assert_allclose(once + sum(routed), whole, atol=5e-6)


# ---- routing ----

def _old_route_topk(x, router_w, top_k, norm_topk=True):
    """`route_topk` as it stood before this PR, letter for letter."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    prob = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(prob, top_k)
    if norm_topk:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return top, idx.astype(jnp.int32)


@pytest.mark.parametrize("norm", [True, False])
def test_softmax_routing_is_bit_for_bit_what_it_was(norm):
    ks = jax.random.split(jax.random.key(5), 2)
    x = jax.random.normal(ks[0], (128, 64))
    w = 0.3 * jax.random.normal(ks[1], (64, 8))
    new = jax.jit(lambda x, w: M.route_topk(x, w, 2, norm))
    old = jax.jit(lambda x, w: _old_route_topk(x, w, 2, norm))
    for a, b in zip(new(x, w), old(x, w)):
        np.testing.assert_array_equal(a, b)
    # and the same program: nothing of the new arguments is traced
    assert (str(jax.make_jaxpr(lambda x, w: M.route_topk(x, w, 2, norm))(x, w))
            == str(jax.make_jaxpr(
                lambda x, w: _old_route_topk(x, w, 2, norm))(x, w)))


def test_sigmoid_routing_with_a_planted_bias():
    """The bias chooses and does not weigh: an expert it lifts is chosen by
    every token, its weight is its sigmoid score without the bias, and the
    weights sum to the scale."""
    ks = jax.random.split(jax.random.key(6), 2)
    x = jax.random.normal(ks[0], (128, 64))
    w = 0.3 * jax.random.normal(ks[1], (64, 8))
    score = jax.nn.sigmoid(jnp.dot(x, w, precision="highest"))
    plain_w, plain_e = M.route_topk(x, w, 2, scoring="sigmoid", scale=2.446)
    np.testing.assert_array_equal(plain_e, jax.lax.top_k(score, 2)[1])
    assert not bool(jnp.all(jnp.any(plain_e == 5, axis=-1)))
    bias = jnp.zeros(8).at[5].set(10.0)
    weight, expert = M.route_topk(x, w, 2, scoring="sigmoid", bias=bias,
                                  scale=2.446)
    assert bool(jnp.all(expert[:, 0] == 5))          # lifted over every score
    # the other choice is the best of the rest by score alone
    rest = jax.lax.top_k(score.at[:, 5].set(-1.0), 1)[1][:, 0]
    np.testing.assert_array_equal(expert[:, 1], rest)
    s5, s2 = score[:, 5], jnp.take_along_axis(score, rest[:, None], -1)[:, 0]
    np.testing.assert_allclose(weight[:, 0], 2.446 * s5 / (s5 + s2),
                               rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(weight, -1), 2.446, rtol=1e-6)
    # unnormalised: the scores themselves, scaled; the bias nowhere
    raw, _ = M.route_topk(x, w, 2, norm_topk=False, scoring="sigmoid",
                          bias=bias, scale=2.446)
    np.testing.assert_allclose(raw[:, 0], 2.446 * s5, rtol=1e-6)
    # no gradient reaches the bias; the router's flows through the scores
    def total(w, b):
        return jnp.sum(M.route_topk(x, w, 2, norm_topk=False,
                                    scoring="sigmoid", bias=b)[0])

    gw, gb = jax.grad(total, (0, 1))(w, bias)
    assert not np.any(np.asarray(gb)) and np.any(np.asarray(gw))
    with pytest.raises(ValueError, match="scoring"):
        M.route_topk(x, w, 2, scoring="tanh")


def test_the_expert_layer_under_sigmoid_routing_agrees_with_the_reference():
    cfg = tiny_cfg(experts_held_first=2, n_routed_experts=4)
    ks = jax.random.split(jax.random.key(8), 6)
    x = jax.random.normal(ks[0], (128, 64))
    p = {"_m.router": 0.3 * jax.random.normal(ks[1], (64, 8)),
         "_m.e_score_correction_bias": 0.2 * jax.random.normal(ks[2], (8,)),
         "_m.w_gate": 0.1 * jax.random.normal(ks[3], (4, 64, 24)),
         "_m.w_up": 0.1 * jax.random.normal(ks[4], (4, 64, 24)),
         "_m.w_down": 0.1 * jax.random.normal(ks[5], (4, 24, 64))}
    y, stats = M.dropless_moe(
        x, p["_m.router"], p["_m.w_gate"], p["_m.w_up"], p["_m.w_down"],
        top_k=2, held_first=2, scoring="sigmoid",
        select_bias=p["_m.e_score_correction_bias"], routed_scale=2.446)
    with jax.default_matmul_precision("highest"):
        want = R.experts(cfg, p, "m", x, "f32")
    np.testing.assert_allclose(y, want, atol=3e-6)
    gates, chosen = R.route(cfg, p, "m", x, "f32")
    assert float(stats[1]) == float(jnp.sum((chosen >= 2) & (chosen < 6)))
    np.testing.assert_allclose(jnp.sum(gates, -1), 2.446, rtol=1e-6)


# ---- attention: a value head narrower than the query/key head ----

def _dense(q, k, v):
    b, t, h, d = q.shape
    g = h // k.shape[2]
    s = jnp.einsum("bihd,bjhd->bhij", q, jnp.repeat(k, g, axis=2))
    m = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    w = jax.nn.softmax(jnp.where(m, s / math.sqrt(d), -jnp.inf), axis=-1)
    return jnp.einsum("bhij,bjhd->bihd", w, jnp.repeat(v, g, axis=2))


def _qkv(b, t, h, kv, d, dv, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (b, t, h, d)),
            jax.random.normal(ks[1], (b, t, kv, d)),
            jax.random.normal(ks[2], (b, t, kv, dv)))


@pytest.mark.parametrize("h,kv", [(2, 2), (4, 2)])
def test_the_kernel_the_blocked_lowering_and_a_plain_softmax_agree_at_192_128(
        h, kv):
    q, k, v = _qkv(1, 256, h, kv, 192, 128, seed=1)

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))

    def pallas(q, k, v):
        return GA.gqa_attention(q, k, v, impl="pallas", block_q=128,
                                block_kv=128)

    def blocked(q, k, v):
        return GA.gqa_attention(q, k, v, impl="blocked", block_q=64)

    o1, o2, o3 = pallas(q, k, v), blocked(q, k, v), _dense(q, k, v)
    assert o1.shape == o2.shape == (1, 256, h, 128)
    np.testing.assert_allclose(o1, o3, atol=5e-6)
    np.testing.assert_allclose(o2, o3, atol=5e-6)
    g1, g2, g3 = (jax.grad(loss(f), (0, 1, 2))(q, k, v)
                  for f in (pallas, blocked, _dense))
    for a, b, c in zip(g1, g2, g3):
        assert a.shape == b.shape == c.shape
        np.testing.assert_allclose(a, c, atol=2e-5)
        np.testing.assert_allclose(b, c, atol=2e-5)


def test_on_a_tpu_the_kernel_is_what_192_128_gets(monkeypatch):
    """The shapes of this model choose the kernel by a TPU's rules: no
    silent blocked loop on the chip."""
    assert GA.pallas_fits(8192, 192, 128) and GA.pallas_fits(8192, 128)
    assert not GA.pallas_fits(8192, 192, 96)
    assert not GA.pallas_fits(8192, 96, 128)
    assert not GA.pallas_fits(8200, 192, 128)
    taken = []

    def spy(q, k, v, window, bq, bkv, interpret):
        taken.append((q.shape[-1], v.shape[-1], interpret))
        return GA._blocked(q, k, v, window, 128)

    monkeypatch.setattr(GA, "_pallas", spy)
    monkeypatch.setattr(GA.jax, "default_backend", lambda: "tpu")
    q, k, v = _qkv(1, 256, 2, 2, 192, 128)
    out = GA.gqa_attention(q, k, v)
    assert taken == [(192, 128, False)] and out.shape == (1, 256, 2, 128)
    GA.gqa_attention(*_qkv(1, 32, 2, 2, 24, 16))      # the tiny test size
    assert len(taken) == 1
    with pytest.raises(ValueError, match="multiples of 128"):
        GA.gqa_attention(*_qkv(1, 32, 2, 2, 24, 16), impl="pallas")


def test_rotary_turns_the_rotary_part_alone_and_the_one_shared_key(
        monkeypatch):
    """What the attention op is handed: a head's first 16 query and key
    dimensions as the projections left them, the last 8 turned by the
    position; the 8 rotary key dimensions the same for every head; all as
    the reference's `mla_qkv` has them."""
    cfg = tiny_cfg(num_hidden_layers=1, first_k_dense_replace=0)
    from paddle_tpu import dsl

    with dsl.model() as g:
        inp = dsl.data("x", dim=(64,), is_seq=True)
        dsl._add("mla_attention", [inp], name="a", size=64, bias=False,
                 num_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
                 qk_rope_head_dim=8, v_head_dim=16, rope_theta=800000,
                 epsilon=1e-5)
    net = Network(g.conf)
    assert {k: tuple(v.dims) for k, v in net.param_confs.items()} == {
        "_a.wq": (64, 96), "_a.wkva": (64, 40), "_a.kv_norm": (32,),
        "_a.wkvb": (32, 128), "_a.wo": (64, 64)}
    p = {k: 0.2 * jax.random.normal(jax.random.key(i), tuple(v.dims))
         for i, (k, v) in enumerate(sorted(net.param_confs.items()))}
    p["_a.kv_norm"] = 1.0 + p["_a.kv_norm"]
    x = jax.random.normal(jax.random.key(9), (2, 32, 64))
    seen = {}
    plain = GA.gqa_attention

    def spy(q, k, v, **kw):
        seen.update(q=q, k=k, v=v)
        return plain(q, k, v, **kw)

    monkeypatch.setattr(GA, "gqa_attention", spy)
    outs, _ = net.forward(p, {"x": Arg(value=x,
                                       seq_lens=jnp.asarray([32, 32]))})
    q, k, v = seen["q"], seen["k"], seen["v"]
    assert (q.shape, k.shape, v.shape) == (
        (2, 32, 4, 24), (2, 32, 4, 24), (2, 32, 4, 16))
    with jax.default_matmul_precision("highest"):
        rq, rk, rv = R.mla_qkv(cfg, {k_.replace("_a.", "_l0_attn."): v_
                                     for k_, v_ in p.items()},
                               "l0_attn", x, "f32")
        want = R.mla(cfg, {k_.replace("_a.", "_l0_attn."): v_
                           for k_, v_ in p.items()}, "l0_attn", x, "f32")
    for got, ref in ((q, rq), (k, rk), (v, rv)):
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(outs["a"].value, want, rtol=1e-5, atol=1e-5)
    raw_q = jnp.dot(x, p["_a.wq"]).reshape(2, 32, 4, 24)
    np.testing.assert_array_equal(q[..., :16], raw_q[..., :16])    # not turned
    np.testing.assert_array_equal(q[:, 0], raw_q[:, 0])            # position 0
    assert not np.allclose(q[:, 5, :, 16:], raw_q[:, 5, :, 16:])
    cos, sin = rope.tables(32, 8, {"rope_theta": 800000})
    np.testing.assert_array_equal(
        q[..., 16:], rope.apply(raw_q[..., 16:], cos, sin))
    # a turned pair keeps its norm: pairs (i, i + 4) of the 8
    np.testing.assert_allclose(
        jnp.hypot(q[..., 16:20], q[..., 20:]),
        jnp.hypot(raw_q[..., 16:20], raw_q[..., 20:]), rtol=1e-5)
    for head in (1, 2, 3):                         # ONE rotary key
        np.testing.assert_array_equal(k[:, :, head, 16:], k[:, :, 0, 16:])
    raw_k = jnp.dot(x, p["_a.wkva"])[..., 32:]
    np.testing.assert_array_equal(
        k[:, :, 0, 16:], rope.apply(raw_k[:, :, None], cos, sin)[:, :, 0])
    assert not np.allclose(k[:, :, 1, :16], k[:, :, 0, :16])


# ---- the precision policy ----

def test_under_the_bfloat16_policy_the_router_and_its_bias_stay_float32(
        monkeypatch):
    from paddle_tpu.core import flags

    cfg = tiny_cfg(num_hidden_layers=2)
    net = Network(kimi(cfg))
    p = RT.init_params(R.param_spec(cfg), 7)
    feed, _ = batch(cfg)
    seen = {}
    plain = M.dropless_moe

    def spy(x, router_w, w_gate, *a, **kw):
        seen.update(x=x.dtype, router=router_w.dtype, w_gate=w_gate.dtype,
                    bias=kw["select_bias"].dtype, scoring=kw["scoring"],
                    scale=kw["routed_scale"])
        return plain(x, router_w, w_gate, *a, **kw)

    monkeypatch.setattr(M, "dropless_moe", spy)
    was = flags.get_flag("matmul_precision")
    flags.set_flag("matmul_precision", "bfloat16")
    try:
        jax.make_jaxpr(lambda p: net.loss_fn(p, feed, train=True)[0])(p)
    finally:
        flags.set_flag("matmul_precision", was)
    assert seen == {"x": jnp.bfloat16, "router": jnp.float32,
                    "w_gate": jnp.bfloat16, "bias": jnp.float32,
                    "scoring": "sigmoid", "scale": 2.446}


# ---- through SGD.train: the normal path, Adam, the counters ----

def test_trains_through_sgd_train_and_publishes_its_counters():
    from paddle_tpu.core import flags
    from paddle_tpu.core.config import OptimizationConf
    from paddle_tpu.data import feeder as F
    from paddle_tpu.data.reader import batched
    from paddle_tpu.obs import metrics as om
    from paddle_tpu.trainer import SGD
    from paddle_tpu.trainer.events import EndIteration

    cfg = tiny_cfg(n_routed_experts=4, experts_held_first=2)
    spec = R.param_spec(cfg)
    p0 = RT.init_params(spec, 5)
    bias0 = {k: np.asarray(v) for k, v in p0.items() if k.endswith("_bias")}
    assert len(bias0) == 2 and all(np.any(v) for v in bias0.values())
    rng = np.random.default_rng(1)
    rows = [(rng.integers(0, 96, 32).astype(np.int32),
             rng.integers(0, 96, 32).astype(np.int32)) for _ in range(4)]
    feeder = F.DataFeeder({"ids": 0, "label": 1}, {
        "ids": F.integer_value_sequence(96),
        "label": F.integer_value_sequence(96)})
    om.get_registry().reset_prefix("moe.")
    was = flags.get_flag("timeline_sample_period")
    flags.set_flag("timeline_sample_period", 2)
    try:
        trainer = SGD(kimi(cfg), OptimizationConf(
            learning_method="adam", learning_rate=1e-2, adam_beta2=0.95),
            seed=3, params=p0)
        costs = []
        trainer.train(
            reader=batched(lambda: iter(rows * 4), 2), feeder=feeder,
            num_passes=1, event_handler=lambda e: costs.append(e.cost)
            if isinstance(e, EndIteration) else None)
    finally:
        flags.set_flag("timeline_sample_period", was)
    assert len(costs) == 8 and costs[-1] < costs[0]    # the fixed rows learn
    # the trainer's leaves are the reference's, the bias among them: a
    # constant of the job, with an optimizer state that stayed zero
    assert {k: tuple(v.shape) for k, v in trainer.params.items()} == {
        k: tuple(s) for k, (s, _) in spec.items()}
    for name, was_ in bias0.items():
        np.testing.assert_array_equal(np.asarray(trainer.params[name]), was_)
        assert not any(np.any(np.asarray(v))
                       for v in trainer.opt_state[name].values())
    assert np.any(np.asarray(trainer.opt_state["_l1_moe.router"]["m"]))
    reg = om.get_registry()
    fenced = 4                           # steps 2, 4, 6, 8 of 8
    for i in (1, 2):                     # the expert layers; layer 0 is dense
        layer = f"l{i}_moe"
        assert reg.counter("moe.slots").get(layer=layer) == fenced * 128
        here = reg.counter("moe.slots_here").get(layer=layer)
        assert 0 < here < fenced * 128
        assert reg.gauge("moe.load_max_over_mean").get(layer=layer) >= 1.0
    assert reg.counter("moe.slots").get(layer="l0_moe") == 0
