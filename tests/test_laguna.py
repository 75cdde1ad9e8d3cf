"""The decoder whose layers differ in head count, mask and rotary width, with
a per-head gate on attention's output and routed experts beside a shared one
(ISSUE 39): `gqa_attention` with `gate="per_head"` and a rotary width
narrower than the head, `ops/rope.py` over a rotary width, `models/laguna.py`,
the `moe` layer routing 32 wide with a scale, each alone and then together
against the plain float32 reference `benchmarks/reference/laguna.py`, at a
tiny size on the CPU (hidden 64 on 2 KV heads of 16; a full layer of 4 heads
with rotary on half a head under YaRN over a dense block, a window layer of
8 heads and a second full layer over 32 experts top 4 beside a shared one;
T 32), on seeded weights.

Tolerances, as tests/test_kimi.py and tests/test_phi4flash.py set them:
program and reference are both float32 here and differ in the order of their
sums (blocked softmax, grouped products, chunked head), so a loss agrees to
1e-6 relative and a gradient leaf to 2e-5 of its largest entry; a block's
output to 5e-6 absolute, a lone layer's on weights of std 0.3 (outputs near
10) to 1e-5 relative; where one code is traced two ways, letter for letter or
bit for bit."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import laguna as R
from benchmarks.reference import train as RT
from benchmarks.tests.test_laguna_cell import tiny_laguna_cell
from paddle_tpu import dsl
from paddle_tpu.core.arg import Arg
from paddle_tpu.models import laguna
from paddle_tpu.network import Network
from paddle_tpu.ops import gqa_attention as GA
from paddle_tpu.ops import moe as M, rope
from tests.test_kimi import batch, leaf_gaps

LEAF_TOL = 2e-5
FULL = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5}
WINDOW = {"rope_type": "default", "rope_theta": 10000,
          "partial_rotary_factor": 1}


def tiny_cfg(**over):
    """The tiny cell's configuration (benchmarks/tests/test_laguna_cell.py
    shrinks the widths, once), here holding all 32 experts unless told."""
    cfg = tiny_laguna_cell().config
    cfg.update(num_experts=32, experts_held_first=0)
    cfg.update(over)
    return cfg


def program_and_reference(cfg):
    net = Network(laguna(cfg))
    spec = R.param_spec(cfg)
    assert {k: tuple(v.dims) for k, v in net.param_confs.items()} == {
        k: tuple(s) for k, (s, _) in spec.items()}
    p = RT.init_params(spec, 7)
    feed, ref = batch(cfg)
    prog = jax.jit(jax.value_and_grad(
        lambda p: net.loss_fn(p, feed, train=True)[0]))
    plain = jax.jit(jax.value_and_grad(lambda p: R.loss(cfg, p, ref)))
    return prog(p), plain(p)


def _attention_layer(**attrs):
    """One `gqa_attention` layer `a` on a sequence `x` 64 wide."""
    with dsl.model() as g:
        inp = dsl.data("x", dim=(64,), is_seq=True)
        dsl._add("gqa_attention", [inp], name="a", size=64, bias=False,
                 **attrs)
    return Network(g.conf)


def _seq(x):
    return {"x": Arg(value=x, seq_lens=jnp.asarray([x.shape[1]] * x.shape[0]))}


# ---- the whole model ----

@pytest.mark.parametrize("share", [(0, 32), (8, 8)], ids=["whole", "share"])
def test_loss_and_every_leafs_gradient_agree_with_the_reference(share):
    cfg = tiny_cfg(experts_held_first=share[0], num_experts=share[1])
    (l1, g1), (l2, g2) = program_and_reference(cfg)
    assert float(l1) == pytest.approx(float(l2), rel=1e-6)
    # a dense layer 10 leaves (the gate among them), an expert layer 14,
    # embedding, norm and head
    assert set(g1) == set(g2) and len(g1) == 10 + 2 * 14 + 3
    gaps = leaf_gaps(g1, g2)
    assert max(gaps.values()) < LEAF_TOL, gaps
    for i in range(3):                   # a gradient reaches every gate
        assert np.any(np.asarray(g1[f"_l{i}_attn.wg"]))


def test_the_graph_is_built_from_the_configs_own_lists():
    conf = laguna(tiny_cfg())
    attrs = [conf.layer(f"l{i}_attn").attrs for i in range(3)]
    assert [(a["num_heads"], a["num_kv_heads"], a["window"], a["gate"])
            for a in attrs] == [(4, 2, None, "per_head"),
                                (8, 2, 8, "per_head"),
                                (4, 2, None, "per_head")]
    assert [a["rope"]["partial_rotary_factor"] for a in attrs] == [0.5, 1, 0.5]
    assert [a["rope"].get("rope_type") for a in attrs] == [
        "yarn", "default", "yarn"]
    moe = conf.layer("l1_moe").attrs
    assert (moe["num_experts"], moe["top_k"], moe["held"], moe["hidden"],
            moe["scoring_func"], moe["routed_scaling_factor"],
            moe["norm_topk"]) == (32, 4, (0, 32), 24, "softmax", 2.5, True)
    assert conf.layer("l0_res2").input_names() == ["l0_res1", "l0_mlp"]
    assert conf.layer("l1_res2").input_names() == [
        "l1_res1", "l1_moe", "l1_shared"]
    # the published lists may stand whole: the first entries are built
    long = tiny_cfg(
        layer_types=tiny_cfg()["layer_types"] + ["sliding_attention"] * 5,
        num_attention_heads_per_layer=[4, 8, 4] + [8] * 5,
        mlp_layer_types=["dense", "sparse", "sparse"] + ["sparse"] * 5)
    assert [lc.name for lc in laguna(long).layers] == [
        lc.name for lc in conf.layers]
    # no `gating`: the Mellum layer, no gate parameter
    plain = Network(laguna(tiny_cfg(gating=False)))
    assert "_l0_attn.wg" not in plain.param_confs
    assert not [n for n in plain.stat_outputs if "attn" in n]


def test_recomputation_on_and_off_give_the_same_gradients():
    on, off = tiny_cfg(recompute="block"), tiny_cfg(recompute=None)
    # a block whose attention reads its input twice is one group, as a block
    # with three branches into the residual is
    assert laguna(on).recompute == [
        ["l0_norm1", "l0_attn", "l0_res1", "l0_norm2", "l0_mlp", "l0_res2"],
        ["l1_norm1", "l1_attn", "l1_res1", "l1_norm2", "l1_moe", "l1_shared",
         "l1_res2"],
        ["l2_norm1", "l2_attn", "l2_res1", "l2_norm2", "l2_moe", "l2_shared",
         "l2_res2"]]
    assert laguna(off).recompute == []
    (l1, g1), _ = program_and_reference(on)
    (l2, g2), _ = program_and_reference(off)
    assert float(l1) == float(l2)
    assert max(leaf_gaps(g1, g2).values()) < 1e-6


# ---- the share ----

@pytest.mark.parametrize("chips", [2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(chips):
    """The 32 experts on `chips` chips, 32 / chips each: every chip computes
    attention (gate and all) and the shared expert alike and its own experts'
    part of the routed result at scale 2.5. Attention and the shared expert
    counted ONCE, the routed parts summed, are the uncut reference's whole
    layer."""
    each = 32 // chips
    cfg = tiny_cfg(num_hidden_layers=2)
    p = RT.init_params(R.param_spec(cfg), 11)
    x = jax.random.normal(jax.random.key(3), (2, 32, 64))
    with jax.default_matmul_precision("highest"):
        whole = R.layer(cfg, p, 1, x, "f32")
        gates, chosen = R.route(cfg, p, "l1_moe", R.attention_half(
            cfg, p, 1, x, "f32")[1], "f32")
    np.testing.assert_allclose(jnp.sum(gates, -1), 2.5, rtol=1e-6)
    experts = ("_l1_moe.w_gate", "_l1_moe.w_up", "_l1_moe.w_down")
    routed, once, here = [], None, 0.0
    for first in range(0, 32, each):
        share = dict(cfg, experts_held_first=first, num_experts=each)
        conf = laguna(share)
        # the layer's graph alone, fed the residual stream
        with dsl.model() as g:
            dsl.data("x", dim=(64,), is_seq=True)
        block = [lc for lc in conf.layers if lc.name.startswith("l1_")]
        block[0].inputs[0].name = block[2].inputs[0].name = "x"
        g.conf.layers.extend(block)
        net = Network(g.conf)
        ps = {k: (v[first:first + each] if k in experts else v)
              for k, v in p.items() if k in net.param_confs}
        outs, _ = net.forward(ps, _seq(x))
        routed.append(outs["l1_moe"].value)
        same = outs["l1_res1"].value + outs["l1_shared"].value
        if once is None:
            once = same
        np.testing.assert_array_equal(same, once)   # every chip alike
        slots, on_chip = (float(s) for s in outs["l1_moe@stats"].value[0, :2])
        assert slots == 2 * 32 * 4
        # `moe.slots_here` is what the reference routes to the held experts
        assert on_chip == float(jnp.sum(
            (chosen >= first) & (chosen < first + each)))
        here += on_chip
        # what the share's own reference gives is the share's whole layer
        with jax.default_matmul_precision("highest"):
            mine = R.layer(share, ps, 1, x, "f32")
        np.testing.assert_allclose(outs["l1_res2"].value, mine, atol=5e-6)
    assert here == 2 * 32 * 4               # every slot on exactly one chip
    np.testing.assert_allclose(once + sum(routed), whole, atol=5e-6)


def test_the_expert_layer_routing_32_wide_with_a_scale_agrees_with_the_reference():
    cfg = tiny_cfg(experts_held_first=8, num_experts=8)
    ks = jax.random.split(jax.random.key(8), 5)
    x = jax.random.normal(ks[0], (128, 64))
    p = {"_m.router": 0.3 * jax.random.normal(ks[1], (64, 32)),
         "_m.w_gate": 0.1 * jax.random.normal(ks[2], (8, 64, 24)),
         "_m.w_up": 0.1 * jax.random.normal(ks[3], (8, 64, 24)),
         "_m.w_down": 0.1 * jax.random.normal(ks[4], (8, 24, 64))}
    y, stats = M.dropless_moe(
        x, p["_m.router"], p["_m.w_gate"], p["_m.w_up"], p["_m.w_down"],
        top_k=4, held_first=8, scoring="softmax", routed_scale=2.5)
    with jax.default_matmul_precision("highest"):
        want = R.experts(cfg, p, "m", x, "f32")
    np.testing.assert_allclose(y, want, atol=5e-6)
    gates, chosen = R.route(cfg, p, "m", x, "f32")
    assert float(stats[0]) == 128 * 4
    assert float(stats[1]) == float(jnp.sum((chosen >= 8) & (chosen < 16)))
    np.testing.assert_allclose(jnp.sum(gates, -1), 2.5, rtol=1e-6)
    weight, expert = M.route_topk(x, p["_m.router"], 4, scale=2.5)
    np.testing.assert_array_equal(jnp.sort(expert, -1), jnp.sort(chosen, -1))
    np.testing.assert_allclose(jnp.sum(weight, -1), 2.5, rtol=1e-6)


# ---- a layer without the new attributes is the parent's ----

def _parent_apply(x, cos, sin):
    """`ops/rope.apply` as it stood before this PR, letter for letter."""
    hd = x.shape[-1]
    xf = x.astype(jnp.float32)
    a, b = xf[..., : hd // 2], xf[..., hd // 2:]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([a * c - b * s, b * c + a * s],
                           axis=-1).astype(x.dtype)


def _parent_forward(params, x, a):
    """`GQAAttentionLayer.forward` as it stood before this PR, letter for
    letter (the Mellum cell's layer), under the scope `Network` gives it."""
    hd, h, kv = a["head_dim"], a["num_heads"], a["num_kv_heads"]
    b, t, _ = x.shape
    with jax.named_scope("gqa_attention:a"):
        q = jnp.dot(x, params["wq"]).reshape(b, t, h, hd)
        k = jnp.dot(x, params["wk"]).reshape(b, t, kv, hd)
        v = jnp.dot(x, params["wv"]).reshape(b, t, kv, hd)
        with jax.named_scope("attn.rope"):
            cos, sin = rope.tables(t, hd, a["rope"])
            q, k = _parent_apply(q, cos, sin), _parent_apply(k, cos, sin)
        with jax.named_scope("attn.core"):
            o = GA.gqa_attention(q, k, v, window=a.get("window"))
        return jnp.dot(o.reshape(b, t, h * hd), params["wo"])


MELLUM_FULL = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
               "original_max_position_embeddings": 8192, "beta_fast": 32,
               "beta_slow": 1, "attention_factor": 1.2772588722239782}
MELLUM_WINDOW = {"rope_type": "default", "rope_theta": 500000}


@pytest.mark.parametrize("kind", ["window", "full"])
@pytest.mark.parametrize("said", ["unsaid", "said"])
def test_a_layer_without_gate_and_partial_rotary_traces_as_the_parents(
        kind, said):
    """The Mellum cell's layer: 4 heads on 2 KV heads of 16 here, its two
    rotary groups as its configuration has them. Built without the new
    attributes, or with them saying what was (`gate` None, the whole head
    turned), it has the parent's parameters, no extra output, and the
    parent's jaxpr letter for letter, forward and gradient."""
    group = dict(MELLUM_WINDOW if kind == "window" else MELLUM_FULL)
    attrs = dict(num_heads=4, num_kv_heads=2, head_dim=16,
                 window=8 if kind == "window" else None, rope=group)
    mine = dict(attrs)
    if said == "said":
        mine.update(gate=None, rope=dict(group, partial_rotary_factor=1))
    net = _attention_layer(**mine)
    assert sorted(net.param_confs) == ["_a.wk", "_a.wo", "_a.wq", "_a.wv"]
    assert net.stat_outputs == {} and "a@stats" not in net.specs
    p = {k: 0.2 * jax.random.normal(jax.random.key(i), tuple(v.dims))
         for i, (k, v) in enumerate(sorted(net.param_confs.items()))}
    x = jax.random.normal(jax.random.key(9), (2, 32, 64))

    def now(p, x):
        return net.forward(p, _seq(x))[0]["a"].value

    def then(p, x):
        return _parent_forward({k[3:]: v for k, v in p.items()}, x, attrs)

    assert str(jax.make_jaxpr(now)(p, x)) == str(jax.make_jaxpr(then)(p, x))
    grad = (lambda f: jax.grad(lambda p, x: jnp.sum(jnp.sin(f(p, x)))))
    assert (str(jax.make_jaxpr(grad(now))(p, x))
            == str(jax.make_jaxpr(grad(then))(p, x)))
    np.testing.assert_array_equal(now(p, x), then(p, x))


def _parent_gated_forward(params, x, a):
    """`GQAAttentionLayer.forward` as PR 39 left it, letter for letter but
    for the gauge: `rope.apply` over the rotary width, the wrapper, the gate."""
    hd, h, kv = a["head_dim"], a["num_heads"], a["num_kv_heads"]
    b, t, _ = x.shape
    q = jnp.dot(x, params["wq"]).reshape(b, t, h, hd)
    k = jnp.dot(x, params["wk"]).reshape(b, t, kv, hd)
    v = jnp.dot(x, params["wv"]).reshape(b, t, kv, hd)
    cos, sin = rope.tables(t, rope.rotary_width(hd, a["rope"]), a["rope"])
    q, k = rope.apply(q, cos, sin), rope.apply(k, cos, sin)
    o = GA.gqa_attention(q, k, v, window=a.get("window"))
    g = jax.nn.sigmoid(jnp.dot(x, params["wg"],
                               preferred_element_type=jnp.float32))
    o = o * g.astype(o.dtype)[..., None]
    return jnp.dot(o.reshape(b, t, h * hd), params["wo"])


@pytest.mark.parametrize("kind", ["full", "window"])
def test_the_gated_layer_on_the_plain_path_is_the_parents_to_the_bit(kind):
    """ISSUE 40 gave the layer a second way to the kernel, taken on a TPU
    where the rotary pass fits. Everywhere else (here) the layer is the
    parent's: the cell's two kinds, gated, rotary on half a head under YaRN
    and on the whole head, output and every parameter's gradient bit for
    bit, and the call counted as `plain`."""
    from paddle_tpu import obs

    attrs = dict(num_heads=4 if kind == "full" else 8, num_kv_heads=2,
                 head_dim=16, window=8 if kind == "window" else None,
                 rope=dict(FULL if kind == "full" else WINDOW),
                 gate="per_head")
    net = _attention_layer(**attrs)
    p = {k: 0.2 * jax.random.normal(jax.random.key(i), tuple(v.dims))
         for i, (k, v) in enumerate(sorted(net.param_confs.items()))}
    x = jax.random.normal(jax.random.key(9), (2, 32, 64))
    calls = obs.get_registry().counter("attn.rope_calls")
    before = calls.get(path="plain"), calls.get(path="pass")

    def now(p):
        return net.forward(p, _seq(x))[0]["a"].value

    def then(p):
        return _parent_gated_forward({k[3:]: v for k, v in p.items()}, x,
                                     attrs)

    np.testing.assert_array_equal(now(p), then(p))
    assert (calls.get(path="plain"), calls.get(path="pass")) == (
        before[0] + 2, before[1])                       # q and k
    grad = (lambda f: jax.grad(lambda p: jnp.sum(jnp.sin(f(p)))))
    got, want = grad(now)(p), grad(then)(p)
    assert sorted(got) == sorted(want) == sorted(p)
    for name in want:
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)


# ---- rotary positions over a rotary width ----

def test_yarn_blends_over_the_rotary_width_the_config_implies():
    assert rope.rotary_width(128, FULL) == 64
    assert rope.rotary_width(128, WINDOW) == 128
    assert rope.rotary_width(128, MELLUM_WINDOW) == 128
    # by hand over r = 64: a pair turns beta times in 4,096 positions at
    # index 64 ln(4096 / (2 pi beta)) / (2 ln 500000): 5.66 at 64, 15.80 at 1
    assert rope.yarn_range(64, 500000, 4096, 64, 1) == (5, 16)
    assert R.yarn_range(64, 500000, 4096, 64, 1) == (5, 16)
    freq, factor = rope.inv_freq(64, FULL)
    assert freq.shape == (32,)
    assert factor == 1.4158883083359672 == pytest.approx(
        0.1 * math.log(64) + 1)
    plain = [500000 ** (-2 * j / 64) for j in range(32)]
    for j in (0, 3, 5):
        assert freq[j] == pytest.approx(plain[j], rel=1e-12)
    for j in (16, 20, 31):
        assert freq[j] == pytest.approx(plain[j] / 64, rel=1e-12)
    keep = 1 - (10 - 5) / (16 - 5)
    assert freq[10] == pytest.approx(
        plain[10] / 64 * (1 - keep) + plain[10] * keep, rel=1e-12)
    ref_freq, ref_factor = R.inv_freq(64, FULL)
    np.testing.assert_allclose(np.asarray(ref_freq), freq, rtol=2e-6)
    assert ref_factor == factor
    # the window group: plain frequencies over the whole head
    freq, factor = rope.inv_freq(128, WINDOW)
    assert factor == 1.0 and freq[63] == pytest.approx(
        10000 ** (-126 / 128), rel=1e-12)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rotary_at_64_of_128_leaves_the_rest_of_a_head_bit_for_bit(dtype):
    """At the model's widths: a head of 128 under the full group turns its
    first 64 dims, pairs (j, j + 32), and passes dims 64-127 through."""
    x = jax.random.normal(jax.random.key(3), (2, 48, 6, 128)).astype(dtype)
    r = rope.rotary_width(128, FULL)
    cos, sin = rope.tables(48, r, FULL)
    assert cos.shape == sin.shape == (48, 32)
    got = rope.apply(x, cos, sin)
    assert got.shape == x.shape and got.dtype == x.dtype
    np.testing.assert_array_equal(got[..., 64:], x[..., 64:])
    # the turned part is the whole-width code on the leading dims alone
    np.testing.assert_array_equal(got[..., :64],
                                  _parent_apply(x[..., :64], cos, sin))
    xf = x.astype(jnp.float32)
    np.testing.assert_allclose(
        got.astype(jnp.float32), R.rotary(xf, FULL),
        **({"rtol": 1e-5, "atol": 1e-5} if dtype == jnp.float32
           else {"rtol": 2e-2, "atol": 2e-2}))
    # position 0 is turned by nothing but the factor; a pair keeps its norm
    factor = 1.4158883083359672
    np.testing.assert_allclose(got[:, 0, :, :64].astype(jnp.float32),
                               xf[:, 0, :, :64] * factor, rtol=1e-2)
    if dtype == jnp.float32:
        np.testing.assert_allclose(
            jnp.hypot(got[..., :32], got[..., 32:64]),
            jnp.hypot(x[..., :32], x[..., 32:64]) * factor, rtol=1e-5)
    # the window group turns the whole head, as the parent's code does
    cos, sin = rope.tables(48, rope.rotary_width(128, WINDOW), WINDOW)
    np.testing.assert_array_equal(rope.apply(x, cos, sin),
                                  _parent_apply(x, cos, sin))


# ---- the gated layer alone ----

@pytest.mark.parametrize("kind,heads", [("full_attention", 4),
                                        ("sliding_attention", 8)])
def test_the_gated_layer_agrees_with_the_reference_and_publishes_its_mean_gate(
        kind, heads, monkeypatch):
    cfg = tiny_cfg()
    i = 0 if kind == "full_attention" else 1
    assert R.heads_of(cfg, i) == heads
    net = _attention_layer(
        num_heads=heads, num_kv_heads=2, head_dim=16,
        window=8 if kind == "sliding_attention" else None,
        rope=dict(cfg["rope_parameters"][kind]), gate="per_head")
    assert {k: tuple(v.dims) for k, v in net.param_confs.items()} == {
        "_a.wq": (64, heads * 16), "_a.wk": (64, 32), "_a.wv": (64, 32),
        "_a.wo": (heads * 16, 64), "_a.wg": (64, heads)}
    assert list(net.stat_outputs) == ["a@stats"]
    p = {k: 0.3 * jax.random.normal(jax.random.key(i_), tuple(v.dims))
         for i_, (k, v) in enumerate(sorted(net.param_confs.items()))}
    x = jax.random.normal(jax.random.key(9), (2, 32, 64))
    seen = {}
    plain = GA.gqa_attention

    def spy(q, k, v, **kw):
        seen.update(q=q, k=k, window=kw.get("window"))
        return plain(q, k, v, **kw)

    monkeypatch.setattr(GA, "gqa_attention", spy)
    outs, _ = net.forward(p, _seq(x))
    rp = {k.replace("_a.", f"_l{i}_attn."): v for k, v in p.items()}
    with jax.default_matmul_precision("highest"):
        want = R.attention(cfg, rp, i, x, "f32")
        gate = R.gate_of(cfg, rp, i, x, "f32")
    np.testing.assert_allclose(outs["a"].value, want, rtol=1e-5, atol=1e-5)
    # the gauge: the mean gate, ONE float; the gates differ and are no 0.5
    assert gate.shape == (2, 32, heads) and float(jnp.std(gate)) > 0.05
    stat = outs["a@stats"].value
    assert stat.shape == (1, 1) and stat.dtype == jnp.float32
    assert float(stat[0, 0]) == pytest.approx(float(jnp.mean(gate)), rel=1e-6)
    # the kernel's operands: on the full layer dims 8-15 of every head are
    # the projection's own, on the window layer the whole head has turned
    raw_q = jnp.dot(x, p["_a.wq"]).reshape(2, 32, heads, 16)
    if kind == "full_attention":
        np.testing.assert_array_equal(seen["q"][..., 8:], raw_q[..., 8:])
        assert not np.allclose(seen["q"][:, 5, :, :8], raw_q[:, 5, :, :8])
        assert seen["window"] is None
    else:
        assert not np.allclose(seen["q"][:, 5, :, 8:], raw_q[:, 5, :, 8:])
        assert seen["window"] == 8
    # a gate shut is a head lost: its output no longer reaches the result
    shut = dict(p, **{"_a.wg": jnp.full_like(p["_a.wg"], -1e4 / 64) * jnp.sign(
        jnp.sum(x, (0, 1)))[:, None]})
    outs, _ = net.forward(shut, _seq(x))
    assert float(outs["a@stats"].value[0, 0]) < 0.5
    with pytest.raises(AssertionError, match="unknown attention gate"):
        _attention_layer(num_heads=4, num_kv_heads=2, head_dim=16,
                         rope=dict(WINDOW), gate="per_token")


def test_under_the_bfloat16_policy_the_gates_sigmoid_is_float32(monkeypatch):
    from paddle_tpu.core import flags

    cfg = tiny_cfg(num_hidden_layers=1)
    net = Network(laguna(cfg))
    p = RT.init_params(R.param_spec(cfg), 7)
    feed, _ = batch(cfg)
    seen = []
    plain = jax.nn.sigmoid

    def spy(x):
        seen.append(x.dtype)
        return plain(x)

    monkeypatch.setattr(jax.nn, "sigmoid", spy)
    was = flags.get_flag("matmul_precision")
    flags.set_flag("matmul_precision", "bfloat16")
    try:
        text = str(jax.make_jaxpr(
            lambda p: net.loss_fn(p, feed, train=False)[0])(p))
    finally:
        flags.set_flag("matmul_precision", was)
    assert seen == [jnp.float32]          # the gate's; a bfloat16 product in
    assert "bf16[2,32,4,16]" in text      # and the gated output goes on so


# ---- the kernel at the two head counts ----

@pytest.mark.parametrize("h,kv,window", [(6, 1, None), (8, 1, 128),
                                         (12, 2, None)])
def test_the_kernel_and_the_blocked_lowering_agree_at_the_new_groupings(
        h, kv, window):
    """6 query heads a KV head (48 on 8) under a full causal mask, 8 (64 on
    8) under a window, in interpret mode on the CPU, float32."""
    ks = jax.random.split(jax.random.key(4), 3)
    q = jax.random.normal(ks[0], (1, 256, h, 128))
    k = jax.random.normal(ks[1], (1, 256, kv, 128))
    v = jax.random.normal(ks[2], (1, 256, kv, 128))

    def loss(**how):
        return lambda q, k, v: jnp.sum(jnp.sin(GA.gqa_attention(
            q, k, v, window=window, **how)))

    kernel = dict(impl="pallas", block_q=128, block_kv=128)
    np.testing.assert_allclose(
        GA.gqa_attention(q, k, v, window=window, **kernel),
        GA._blocked(q, k, v, window, 64), atol=5e-6)
    got = jax.grad(loss(**kernel), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(impl="blocked", block_q=64), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-5)


def test_the_cells_two_attention_shapes_take_the_rules_tiles():
    """`kernel_tiles` sees the mask and the widths, not the head count: 48
    on 8 under a full causal mask takes ONE backward kernel in tiles of
    1,024, 64 on 8 under a window of 512 two in tiles of 512, as the other
    cells' layers of those masks do."""
    full = GA.kernel_tiles(8192, 128, 128, None)
    assert (full.block_q, full.block_kv, full.block_kv_compute,
            full.use_fused_bwd_kernel) == (1024, 1024, 512, True)
    win = GA.kernel_tiles(8192, 128, 128, 512)
    assert (win.block_q, win.block_kv, win.block_q_dq, win.block_kv_dkv,
            win.use_fused_bwd_kernel) == (512, 512, 512, 512, False)
    assert GA.pallas_fits(8192, 128, 128)


# ---- through SGD.train: the normal path, Adam, the counters ----

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
    reg.reset_prefix("attn.gate_mean")
    # what the reference says of the FIRST batch on the seeded weights
    ids = jnp.asarray(np.stack([rows[0][0], rows[1][0]]))
    with jax.default_matmul_precision("highest"):
        x = p0["_emb.w0"][ids]
        a0 = R.rms(x, p0["_l0_norm1.w0"], cfg["rms_norm_eps"])
        gate0 = float(jnp.mean(R.gate_of(cfg, p0, 0, a0, "f32")))
        x = R.layer(cfg, p0, 0, x, "f32")
        _, chosen = R.route(cfg, p0, "l1_moe", R.attention_half(
            cfg, p0, 1, x, "f32")[1], "f32")
    here0 = float(jnp.sum((chosen >= 8) & (chosen < 16)))
    was = flags.get_flag("timeline_sample_period")
    flags.set_flag("timeline_sample_period", 1)
    try:
        trainer = SGD(laguna(cfg), OptimizationConf(
            learning_method="adam", learning_rate=1e-2, adam_beta2=0.95),
            seed=3, params=p0)
        costs, first = [], {}

        def handle(e):
            if isinstance(e, EndIteration):
                costs.append(e.cost)
                if len(costs) == 1:      # fenced every step: after step 1
                    first.update(
                        gate=reg.gauge("attn.gate_mean").get(layer="l0_attn"),
                        here=reg.counter("moe.slots_here").get(
                            layer="l1_moe"))

        trainer.train(reader=batched(lambda: iter(rows * 4), 2),
                      feeder=feeder, num_passes=1, event_handler=handle)
    finally:
        flags.set_flag("timeline_sample_period", was)
    assert len(costs) == 8 and costs[-1] < costs[0]    # the fixed rows learn
    assert {k: tuple(v.shape) for k, v in trainer.params.items()} == {
        k: tuple(s) for k, (s, _) in spec.items()}
    # the gauge is the reference's mean gate, the counter what the reference
    # routes to the experts held
    assert first["gate"] == pytest.approx(gate0, rel=1e-5)
    assert first["here"] == here0
    for i in range(3):
        g = reg.gauge("attn.gate_mean").get(layer=f"l{i}_attn")
        assert 0.3 < g < 0.7
    for i in (1, 2):                     # the expert layers; layer 0 is dense
        layer = f"l{i}_moe"
        assert reg.counter("moe.slots").get(layer=layer) == 8 * 256
        here = reg.counter("moe.slots_here").get(layer=layer)
        assert 0 < here < 8 * 256
        moved = reg.counter("moe.rows_moved").get(layer=layer)
        assert here <= moved <= 8 * 256
    assert reg.counter("moe.slots").get(layer="l0_moe") == 0
    text = reg.render_text()
    for name in ("attn.gate_mean", "moe.slots", "moe.slots_here",
                 "moe.rows_moved"):
        assert name in text
