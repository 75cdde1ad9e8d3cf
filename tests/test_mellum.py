"""The present-day decoder block (ISSUE 28): `rms_norm`, `gqa_attention`
with a sliding window and two kinds of rotary positions, the dropless top-k
`moe` layer that holds a share of the experts, per-block recomputation and
the chunked head, each alone and then together against the plain float32
reference `benchmarks/reference/mellum.py`, at a tiny size on the CPU
(hidden 64, 4 heads on 2 KV heads of 16, 8 experts top-2, window 8, T 32,
the 4 layers of the published pattern), on seeded weights.

Tolerances: program and reference are both float32 here and differ in the
order of their sums (blocked softmax, grouped products, chunked head), so a
loss agrees to 1e-6 relative and a gradient leaf to 2e-5 of its largest
entry; where two lowerings of one kernel are held together the same."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import mellum as R
from benchmarks.reference import train as RT
from benchmarks.tests.test_mellum_cell import tiny_cell
from paddle_tpu.core.arg import Arg
from paddle_tpu.models import mellum
from paddle_tpu.network import Network
from paddle_tpu.ops import gqa_attention as GA
from paddle_tpu.ops import lm_head, moe as M, rope

LEAF_TOL = 2e-5


def tiny_cfg(**over):
    """The tiny cell's configuration (benchmarks/tests/test_mellum_cell.py
    shrinks the widths, once), here holding all 8 experts unless told."""
    cfg = tiny_cell().config
    cfg.update(num_experts=8, experts_held_first=0)
    cfg.update(over)
    return cfg


def batch(cfg, rows=2, t=32, seed=0, lens=None):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg["vocab_size"], (rows, t)).astype(np.int32)
    lab = rng.integers(0, cfg["vocab_size"], (rows, t)).astype(np.int32)
    lens = np.asarray(lens or [t] * rows, np.int32)
    feed = {"ids": Arg(ids=jnp.asarray(ids), seq_lens=jnp.asarray(lens)),
            "label": Arg(ids=jnp.asarray(lab), seq_lens=jnp.asarray(lens))}
    ref = {"ids": jnp.asarray(ids), "label": jnp.asarray(lab),
           "lens": jnp.asarray(lens)}
    return feed, ref


def leaf_gaps(got, want):
    return {k: float(np.abs(np.asarray(got[k]) - np.asarray(want[k])).max()
                     / (np.abs(np.asarray(want[k])).max() + 1e-30))
            for k in want}


def program_and_reference(cfg, lens=None):
    net = Network(mellum(cfg))
    spec = R.param_spec(cfg)
    assert {k: tuple(v.dims) for k, v in net.param_confs.items()} == {
        k: tuple(s) for k, (s, _) in spec.items()}
    p = RT.init_params(spec, 7)
    feed, ref = batch(cfg, lens=lens)
    prog = jax.jit(jax.value_and_grad(
        lambda p: net.loss_fn(p, feed, train=True)[0]))
    plain = jax.jit(jax.value_and_grad(lambda p: R.loss(cfg, p, ref)))
    return prog(p), plain(p)


# ---- the whole model ----

@pytest.mark.parametrize("share", [(0, 8), (2, 4)], ids=["whole", "share"])
def test_loss_and_every_leafs_gradient_agree_with_the_reference(share):
    cfg = tiny_cfg(experts_held_first=share[0], num_experts=share[1])
    (l1, g1), (l2, g2) = program_and_reference(cfg)
    assert float(l1) == pytest.approx(float(l2), rel=1e-6)
    assert set(g1) == set(g2) and len(g1) == 4 * 10 + 3
    gaps = leaf_gaps(g1, g2)
    assert max(gaps.values()) < LEAF_TOL, gaps


def test_padded_positions_are_left_out_of_the_loss_and_the_experts():
    cfg = tiny_cfg()
    (l1, g1), (l2, g2) = program_and_reference(cfg, lens=[32, 19])
    assert float(l1) == pytest.approx(float(l2), rel=1e-6)
    # the experts see no padded token in the program and all of them in the
    # reference (whose cost masks them): the gradients agree all the same
    assert max(leaf_gaps(g1, g2).values()) < LEAF_TOL


def test_recomputation_on_and_off_give_the_same_gradients():
    on, off = tiny_cfg(recompute="block"), tiny_cfg(recompute=None)
    conf = mellum(on)
    assert len(conf.recompute) == 4 and conf.recompute[0] == [
        "l0_norm1", "l0_attn", "l0_res1", "l0_norm2", "l0_moe", "l0_res2"]
    assert mellum(off).recompute == []
    (l1, g1), _ = program_and_reference(on)
    (l2, g2), _ = program_and_reference(off)
    assert float(l1) == float(l2)
    assert max(leaf_gaps(g1, g2).values()) < 1e-6
    # and the recomputation is in the program: a remat a block more
    def remats(cfg):
        feed, _ = batch(cfg)
        net = Network(mellum(cfg))
        p = RT.init_params(R.param_spec(cfg), 7)
        return str(jax.make_jaxpr(
            lambda p: net.loss_fn(p, feed, train=True)[0])(p)).count("remat2[")

    assert remats(on) == remats(off) + 4


def test_a_recompute_group_must_be_a_run_of_stateless_layers():
    conf = mellum(tiny_cfg())
    conf.recompute.append(["l0_norm1", "l0_res1"])
    with pytest.raises(ValueError, match="consecutive"):
        Network(conf)


# ---- rotary positions ----

def test_yarn_blends_between_the_pairs_the_config_implies():
    full = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782}
    assert rope.yarn_range(128, 500000, 8192, 32, 1) == (18, 35)
    assert R.yarn_range(128, 500000, 8192, 32, 1) == (18, 35)
    freq, factor = rope.inv_freq(128, full)
    assert factor == 1.2772588722239782 == pytest.approx(
        0.1 * math.log(16) + 1)
    # by hand: pair i turns at theta^(-2i/128); below pair 18 as it is, above
    # pair 35 divided by 16, between them blended linearly
    plain = [500000 ** (-2 * i / 128) for i in range(64)]
    for i in (0, 5, 18):
        assert freq[i] == pytest.approx(plain[i], rel=1e-12)
    for i in (35, 40, 63):
        assert freq[i] == pytest.approx(plain[i] / 16, rel=1e-12)
    keep = 1 - (27 - 18) / (35 - 18)
    assert freq[27] == pytest.approx(
        plain[27] / 16 * (1 - keep) + plain[27] * keep, rel=1e-12)
    ref_freq, ref_factor = R.inv_freq(128, full)
    np.testing.assert_allclose(np.asarray(ref_freq), freq, rtol=2e-6)
    assert ref_factor == factor


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_rotary_turns_half_split_pairs_as_the_reference_does(kind):
    cfg = tiny_cfg()
    group = cfg["rope_parameters"][kind]
    x = jax.random.normal(jax.random.key(3), (2, 32, 4, 16))
    cos, sin = rope.tables(32, 16, group)
    got = rope.apply(x, cos, sin)
    np.testing.assert_allclose(got, R.rotary(x, group), rtol=1e-5, atol=1e-6)
    # position 0 is turned by nothing but the factor; a pair keeps its norm
    factor = rope.inv_freq(16, group)[1]
    np.testing.assert_allclose(got[:, 0], x[:, 0] * factor, rtol=1e-6)
    n_in = jnp.hypot(x[..., :8], x[..., 8:])
    n_out = jnp.hypot(got[..., :8], got[..., 8:])
    np.testing.assert_allclose(n_out, n_in * factor, rtol=1e-5)


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_the_attention_layer_on_the_plain_path_is_the_parents_to_the_bit(
        kind):
    """ISSUE 40 gave `gqa_attention` layers a second way to the kernel,
    taken on a TPU where the rotary pass fits. Everywhere else (here) the
    layer is the parent's: this cell's two kinds (4 heads on 2 KV heads of
    16, the configuration's own rotary groups), output and every parameter's
    gradient bit for bit, under `jit` as a step runs it."""
    from tests.test_laguna import _attention_layer, _parent_forward, _seq

    attrs = dict(num_heads=4, num_kv_heads=2, head_dim=16,
                 window=8 if kind == "sliding_attention" else None,
                 rope=dict(tiny_cfg()["rope_parameters"][kind]))
    net = _attention_layer(**attrs)
    p = {k: 0.2 * jax.random.normal(jax.random.key(i), tuple(v.dims))
         for i, (k, v) in enumerate(sorted(net.param_confs.items()))}
    x = jax.random.normal(jax.random.key(9), (2, 32, 64))

    def now(p):
        return net.forward(p, _seq(x))[0]["a"].value

    def then(p):
        return _parent_forward({k[3:]: v for k, v in p.items()}, x, attrs)

    def both(f):
        return jax.jit(lambda p: (f(p), jax.grad(
            lambda p: jnp.sum(jnp.sin(f(p))))(p)))(p)

    (got, got_g), (want, want_g) = both(now), both(then)
    np.testing.assert_array_equal(got, want)
    assert sorted(got_g) == sorted(want_g) == sorted(p)
    for name in want_g:
        np.testing.assert_array_equal(got_g[name], want_g[name],
                                      err_msg=name)


# ---- attention: the window's mask, the two lowerings ----

def _dense(q, k, v, window):
    b, t, h, d = q.shape
    g = h // k.shape[2]
    s = jnp.einsum("bihd,bjhd->bhij", q, jnp.repeat(k, g, axis=2))
    w = jax.nn.softmax(jnp.where(R.mask(t, window), s / math.sqrt(d),
                                 -jnp.inf), axis=-1)
    return jnp.einsum("bhij,bjhd->bihd", w, jnp.repeat(v, g, axis=2))


def _qkv(b, t, h, kv, d, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (b, t, h, d)),
            jax.random.normal(ks[1], (b, t, kv, d)),
            jax.random.normal(ks[2], (b, t, kv, d)))


@pytest.mark.parametrize("window", [8, None])
def test_window_mask_alone(window):
    q, k, v = _qkv(2, 32, 4, 2, 16)
    got = GA.gqa_attention(q, k, v, window=window, impl="blocked", block_q=8)
    np.testing.assert_allclose(got, _dense(q, k, v, window), atol=2e-6)
    # position 20 sees 13..20 under a window of 8, and 0..20 without one
    k2 = k.at[:, 12].add(5.0)
    v2 = v.at[:, 12].add(5.0)
    moved = GA.gqa_attention(q, k2, v2, window=window, impl="blocked",
                             block_q=8)
    assert bool(jnp.all(moved[:, :12] == got[:, :12]))       # causal
    same_at_20 = bool(jnp.all(moved[:, 20] == got[:, 20]))
    assert same_at_20 == (window is not None)
    assert not bool(jnp.all(moved[:, 19] == got[:, 19]))     # 12 in 12..19


@pytest.mark.parametrize("t,window", [(256, 128), (256, None), (384, 200)])
def test_the_attention_kernel_in_interpret_mode_and_the_blocked_lowering_agree(
        t, window):
    q, k, v = _qkv(1, t, 4, 2, 128, seed=1)

    def loss(impl):
        return lambda q, k, v: jnp.sum(jnp.sin(GA.gqa_attention(
            q, k, v, window=window, impl=impl, block_q=128, block_kv=128)))

    o1 = GA.gqa_attention(q, k, v, window=window, impl="pallas",
                          block_q=128, block_kv=128)
    o2 = GA.gqa_attention(q, k, v, window=window, impl="blocked", block_q=128)
    np.testing.assert_allclose(o1, o2, atol=5e-6)
    np.testing.assert_allclose(o1, _dense(q, k, v, window), atol=5e-6)
    g1 = jax.grad(loss("pallas"), (0, 1, 2))(q, k, v)
    g2 = jax.grad(loss("blocked"), (0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_the_kernel_is_asked_for_only_where_its_tiles_fit():
    assert not GA.pallas_fits(32, 16) and GA.pallas_fits(8192, 128)
    with pytest.raises(ValueError, match="multiples of 128"):
        GA.gqa_attention(*_qkv(1, 32, 4, 2, 16), impl="pallas")
    with pytest.raises(ValueError, match="do not divide"):
        GA.gqa_attention(*_qkv(1, 32, 4, 3, 16))
    # off the TPU, and on shapes the tiles do not fit, the portable loop
    q, k, v = _qkv(1, 32, 4, 2, 16)
    np.testing.assert_array_equal(
        GA.gqa_attention(q, k, v, window=8),
        GA.gqa_attention(q, k, v, window=8, impl="blocked"))


# ---- the expert layer ----

def _moe_weights(d=64, e=8, eh=8, f=32, seed=5):
    ks = jax.random.split(jax.random.key(seed), 5)
    return (jax.random.normal(ks[0], (128, d)),
            0.3 * jax.random.normal(ks[1], (d, e)),
            0.1 * jax.random.normal(ks[2], (eh, d, f)),
            0.1 * jax.random.normal(ks[3], (eh, d, f)),
            0.1 * jax.random.normal(ks[4], (eh, f, d)))


def _ref_moe(cfg, x, wr, wg, wu, wd):
    p = {"_m.router": wr, "_m.w_gate": wg, "_m.w_up": wu, "_m.w_down": wd}
    with jax.default_matmul_precision("highest"):
        return R.experts(cfg, p, "m", x, "f32")


def test_dropless_routing_under_a_planted_imbalance():
    """Every token's first choice is expert 3: it gets all 128 tokens, 8
    times its even share, and none is lost."""
    cfg = tiny_cfg()
    x, wr, wg, wu, wd = _moe_weights()
    x = jnp.abs(x)                       # so that one column can win always
    wr = wr.at[:, 3].set(2.0)
    y, stats = M.dropless_moe(x, wr, wg, wu, wd, top_k=2)
    slots, here, load, moved = (float(s) for s in stats)
    assert (slots, here, moved) == (256.0, 256.0, 256.0)
    _, chosen = M.route_topk(x, wr, 2)
    counts = np.bincount(np.asarray(chosen).reshape(-1), minlength=8)
    assert counts[3] == 128 and load == pytest.approx(128 * 8 / 256)
    np.testing.assert_allclose(y, _ref_moe(cfg, x, wr, wg, wu, wd),
                               atol=2e-6)
    # a layer that holds expert 3 alone still computes all its 128 slots
    y3, stats3 = M.dropless_moe(x, wr, wg[3:4], wu[3:4], wd[3:4], top_k=2,
                                held_first=3)
    assert [float(s) for s in stats3] == [256.0, 128.0, 1.0, 256.0]
    cfg3 = dict(cfg, num_experts=1, experts_held_first=3)
    np.testing.assert_allclose(
        y3, _ref_moe(cfg3, x, wr, wg[3:4], wu[3:4], wd[3:4]), atol=2e-6)


def test_the_shares_parts_add_up_to_the_uncut_layer():
    """Experts {0-1, 2-3, 4-5, 6-7} on four chips: each chip's part of the
    result, summed, is the uncut reference's whole layer (there is no
    shared expert to count once)."""
    cfg = tiny_cfg()
    x, wr, wg, wu, wd = _moe_weights()
    whole = _ref_moe(cfg, x, wr, wg, wu, wd)
    parts, slots_here = [], 0.0
    for first in (0, 2, 4, 6):
        y, stats = M.dropless_moe(
            x, wr, wg[first:first + 2], wu[first:first + 2],
            wd[first:first + 2], top_k=2, held_first=first)
        parts.append(y)
        slots_here += float(stats[1])
        assert float(stats[0]) == 256.0
    assert slots_here == 256.0           # every slot on exactly one chip
    np.testing.assert_allclose(sum(parts), whole, atol=3e-6)
    # and through the graph: the layer told which experts it holds
    from paddle_tpu import dsl

    with dsl.model() as g:
        inp = dsl.data("x", dim=(64,))
        dsl._add("moe", [inp], name="m", bias=False, num_experts=8, top_k=2,
                 held=(2, 2), hidden=32)
    net = Network(g.conf)
    assert {k: tuple(v.dims) for k, v in net.param_confs.items()} == {
        "_m.router": (64, 8), "_m.w_gate": (2, 64, 32),
        "_m.w_up": (2, 64, 32), "_m.w_down": (2, 32, 64)}
    p = {"_m.router": wr, "_m.w_gate": wg[2:4], "_m.w_up": wu[2:4],
         "_m.w_down": wd[2:4]}
    outs, _ = net.forward(p, {"x": Arg(value=x)})
    np.testing.assert_allclose(outs["m"].value, parts[1], atol=1e-6)
    assert outs["m@stats"].value.shape == (1, 4)
    assert list(net.stat_outputs) == ["m@stats"]
    assert net.stat_outputs["m@stats"] is net.layers["m"]


@pytest.mark.parametrize("first,held", [(0, 8), (2, 2), (6, 2)])
def test_the_grouped_matmul_kernel_in_interpret_mode_and_ragged_dot_agree(
        first, held):
    x, wr, wg, wu, wd = _moe_weights(d=128, f=128)
    wg, wu, wd = (w[first:first + held] for w in (wg, wu, wd))

    def run(impl):
        def f(x, wr, wg, wu, wd):
            return M.dropless_moe(x, wr, wg, wu, wd, top_k=2,
                                  held_first=first, impl=impl)[0]
        y = f(x, wr, wg, wu, wd)
        g = jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))), (0, 1, 2, 3, 4))(
            x, wr, wg, wu, wd)
        return y, g

    (y1, g1), (y2, g2) = run("pallas"), run("ragged")
    # products of 128 terms summed tile by tile or row by row: 2e-5 of
    # an entry, or of 1 for the small ones
    np.testing.assert_allclose(y1, y2, rtol=2e-5, atol=2e-5)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
    # the product alone; rows past the last group are not defined
    sizes = jnp.asarray([5, 0, 100], jnp.int32)
    lhs = jax.random.normal(jax.random.key(9), (128, 128))
    rhs = jax.random.normal(jax.random.key(10), (3, 128, 128))
    got = M.grouped_matmul(lhs, rhs, sizes, impl="pallas")[:105]
    np.testing.assert_allclose(
        got, M.grouped_matmul(lhs, rhs, sizes, impl="ragged")[:105],
        atol=1e-4)
    np.testing.assert_allclose(got[:5], lhs[:5] @ rhs[0], atol=1e-4)
    np.testing.assert_allclose(got[5:], lhs[5:105] @ rhs[2], atol=1e-4)


def test_undefined_rows_of_the_row_buffer_never_reach_a_result():
    """The kernel leaves the rows of slots no held expert owns as they lay
    in memory. Planted NaNs there (a grouped product that fills them)
    change neither the layer's result nor any gradient."""
    x, wr, wg, wu, wd = _moe_weights()
    wg, wu, wd = wg[2:4], wu[2:4], wd[2:4]

    def run():
        def f(x, wr, wg, wu, wd):
            return M.dropless_moe(x, wr, wg, wu, wd, top_k=2, held_first=2,
                                  impl="ragged")[0]
        return f(x, wr, wg, wu, wd), jax.grad(
            lambda *a: jnp.sum(jnp.sin(f(*a))), (0, 1, 2, 3, 4))(
                x, wr, wg, wu, wd)

    clean_y, clean_g = run()
    plain = M.grouped_matmul

    @jax.custom_vjp
    def poison(out, past):             # NaN on the way out AND on the way back
        return jnp.where(past, jnp.nan, out)

    poison.defvjp(lambda out, past: (poison(out, past), past),
                  lambda past, g: (jnp.where(past, jnp.nan, g), None))

    def poisoned(lhs, rhs, sizes, impl=None, interpret=None):
        past = (jnp.arange(lhs.shape[0]) >= jnp.sum(sizes))[:, None]
        return poison(plain(lhs, rhs, sizes, impl, interpret), past)

    M.grouped_matmul = poisoned
    try:
        y, g = run()
    finally:
        M.grouped_matmul = plain
    assert bool(jnp.all(jnp.isfinite(y)))
    np.testing.assert_array_equal(y, clean_y)
    for a, b in zip(g, clean_g):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


# ---- the row buffer's passes stop at the held rows ----

SMALL_CHUNK = 32
# sorted slots on experts held, of 64 tokens x 4: none, a few, whole chunks
# to the row, a row over, and every slot
HELD_ROWS = [0, 5, SMALL_CHUNK, SMALL_CHUNK + 1, 3 * SMALL_CHUNK, 256]


def _sorted_slots(n=64, k=4, d=16, seed=12):
    """A row buffer's index vectors as `dropless_moe` makes them, from a
    random order of the slots, and values to move."""
    ks = jax.random.split(jax.random.key(seed), 5)
    order = jax.random.permutation(ks[0], n * k).astype(jnp.int32)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(n * k, dtype=jnp.int32))
    return (order, inverse, jax.random.normal(ks[1], (n, d)),
            jax.random.normal(ks[2], (n * k, d)),
            jax.random.normal(ks[3], (n * k, d)),
            jax.random.uniform(ks[4], (n, k)))


def _plain_unsort(rows, inverse, here):
    """The masked gather: a sorted buffer's rows in slot order, the rows
    of slots not held as zeros."""
    return jnp.where((inverse < here)[:, None], rows[inverse], 0)


def _plain_combine(out, weight, inverse, here):
    n, k = weight.shape
    slots = _plain_unsort(out, inverse, here).reshape(n, k, -1)
    return jnp.sum(slots * weight[..., None], axis=1)


@pytest.mark.parametrize("here", HELD_ROWS)
def test_dispatch_moves_the_held_rows_out_and_sums_them_back(
        monkeypatch, here):
    monkeypatch.setattr(M, "CHUNK", SMALL_CHUNK)
    order, inverse, x, g1, g2, _ = _sorted_slots()
    n, k = x.shape[0], order.shape[0] // x.shape[0]
    plan = M._by_token(inverse, here, n)
    (rows, again), pull = jax.vjp(
        lambda x: M._take_tokens(x, order, here, plan), x)
    assert again is rows or bool(jnp.all(again == rows))
    np.testing.assert_array_equal(rows[:here], x[order // k][:here])
    # back: each token's held slots' rows of both cotangents, and no other
    want = _plain_unsort(g1 + g2, inverse, here).reshape(n, k, -1).sum(1)
    np.testing.assert_allclose(pull((g1, g2))[0], want, atol=2e-6)


@pytest.mark.parametrize("here", HELD_ROWS)
def test_combine_is_the_masked_gather_and_weighted_sum(monkeypatch, here):
    monkeypatch.setattr(M, "CHUNK", SMALL_CHUNK)
    order, inverse, _, out, _, weight = _sorted_slots()
    n = weight.shape[0]
    plan = M._by_token(inverse, here, n)
    # rows past `here` are not defined: NaN there must reach nothing
    out = out.at[here:].set(jnp.nan)

    def mine(out, weight):
        return M._combine(out, weight, order, inverse, here, plan)

    def plain(out, weight):
        return _plain_combine(out, weight, inverse, here)

    y = mine(out, weight)
    np.testing.assert_allclose(y, plain(out, weight), atol=2e-6)
    g = jax.random.normal(jax.random.key(13), y.shape)
    (d_out, d_w), (p_out, p_w) = (
        jax.vjp(f, out, weight)[1](g) for f in (mine, plain))
    np.testing.assert_allclose(d_out[:here], p_out[:here], atol=2e-6)
    assert bool(jnp.all(jnp.isfinite(d_w)))
    np.testing.assert_allclose(d_w, p_w, atol=1e-5)


@pytest.mark.parametrize("here", HELD_ROWS)
def test_the_gated_activation_over_the_held_rows(monkeypatch, here):
    monkeypatch.setattr(M, "CHUNK", SMALL_CHUNK)
    _, _, _, gate, up, _ = _sorted_slots()
    g = jax.random.normal(jax.random.key(14), gate.shape)
    hidden, pull = jax.vjp(
        lambda gate, up: M._gated(jax.nn.silu, gate, up, here), gate, up)
    want, plain_pull = jax.vjp(lambda gate, up: jax.nn.silu(gate) * up,
                               gate, up)
    np.testing.assert_allclose(hidden[:here], want[:here], atol=1e-6)
    for a, b in zip(pull(g), plain_pull(g)):
        np.testing.assert_allclose(a[:here], b[:here], atol=1e-6)


def test_the_tokens_side_index_puts_held_slots_first_and_full_tokens_first():
    order, inverse, *_ = _sorted_slots()
    here, n, k = 70, 64, 4
    plan = M._by_token(inverse, here, n)
    on = np.asarray(inverse < here).reshape(n, k)
    held = on.sum(1)
    assert list(plan.held) == sorted(held, reverse=True)
    assert sorted(np.asarray(plan.rank)) == list(range(n))
    for t in range(n):
        p = int(plan.rank[t])
        assert int(plan.held[p]) == held[t]
        assert int(plan.tokens[p]) == t
        mine = [int(j) for j in plan.col[:held[t], p]]
        assert mine == [j for j in range(k) if on[t, j]]
        assert [int(r) for r in plan.row[:held[t], p]] == [
            int(inverse[t * k + j]) for j in mine]
    # tokens that hold as many keep their order: the sort is stable
    for c in range(k + 1):
        ranks = [int(plan.rank[t]) for t in range(n) if held[t] == c]
        assert ranks == sorted(ranks)


def _plain_dropless(x, wr, wg, wu, wd, *, top_k, held_first=0,
                    token_mask=None):
    """The layer by the formulas it had before its passes were cut to the
    held rows (ISSUE 34): gathers and sums over all N * k slots, plain
    autodiff, `ragged_dot`."""
    n, e, eh = x.shape[0], wr.shape[1], wu.shape[0]
    weight, expert = M.route_topk(x, wr, top_k)
    key = jnp.mod(expert - held_first, e).reshape(-1)
    if token_mask is not None:
        key = jnp.where(jnp.repeat(token_mask > 0, top_k), key, e)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32))
    sizes = jnp.bincount(key, length=e + 1).astype(jnp.int32)[:eh]
    rows = x[order // top_k]
    hidden = jax.nn.silu(jax.lax.ragged_dot(rows, wg, sizes)) * (
        jax.lax.ragged_dot(rows, wu, sizes))
    out = jax.lax.ragged_dot(hidden, wd, sizes)
    return _plain_combine(out, weight, inverse, jnp.sum(sizes)), None


@pytest.mark.parametrize("first,held,masked,rows,tokens", [
    (5, 1, False, 0, 128), (2, 2, False, None, 128), (0, 3, True, None, 128),
    (0, 8, False, 256, 128), (0, 8, True, None, 128),
    (2, 2, False, None, 100)],
    ids=["none-held", "a-few", "a-few-masked", "all-held", "all-masked",
         "chunks-of-25"])
def test_the_layer_at_every_held_share_agrees_with_the_plain_formulas(
        monkeypatch, first, held, masked, rows, tokens):
    """`tokens` 100: 200 slots are no multiple of 32, and a trip takes the
    largest divisor under it, 25 rows."""
    monkeypatch.setattr(M, "CHUNK", SMALL_CHUNK)
    x, wr, wg, wu, wd = _moe_weights()
    x = x[:tokens]
    if rows == 0:                        # expert 5 is nobody's choice
        x, wr = jnp.abs(x) + 0.1, wr.at[:, 5].set(-10.0)
    wg, wu, wd = (w[first:first + held] for w in (wg, wu, wd))
    mask = (jnp.arange(128) % 5 != 0).astype(jnp.float32) if masked else None
    chunk = M._chunk_of(2 * tokens)
    assert chunk == (SMALL_CHUNK if tokens == 128 else 25)

    def run(layer):
        def f(*a):
            return layer(*a, top_k=2, held_first=first, token_mask=mask)
        return f(x, wr, wg, wu, wd), jax.grad(
            lambda *a: jnp.sum(jnp.sin(f(*a)[0])), (0, 1, 2, 3, 4))(
                x, wr, wg, wu, wd)

    ((y, stats), g), ((want, _), want_g) = (
        run(M.dropless_moe), run(_plain_dropless))
    slots, here, _, moved = (float(s) for s in stats)
    # 26 of 128 tokens padded
    assert slots == (204.0 if masked else 2.0 * tokens)
    if rows is not None:
        assert here == rows
    assert here <= moved <= min(here + (2 + 1) * chunk, 2 * tokens)
    assert moved % chunk == 0 and (here > 0 or moved == 0)
    np.testing.assert_allclose(y, want, atol=2e-6)
    for a, b in zip(g, want_g):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-6)


def test_no_pass_of_the_step_is_as_long_as_the_row_buffer():
    """What keeps the buffer-long passes from coming back unnoticed: in the
    whole step (loss and gradients, recomputation on) no float32 [N, k, D]
    array, and outside the chunk loops no gather that gives N * k rows."""
    cfg = tiny_cfg(experts_held_first=2, num_experts=4)
    net = Network(mellum(cfg))
    p = RT.init_params(R.param_spec(cfg), 7)
    feed, _ = batch(cfg)
    n, k, d = 2 * 32, cfg["num_experts_per_tok"], cfg["hidden_size"]
    traced = jax.make_jaxpr(jax.grad(
        lambda p: net.loss_fn(p, feed, train=True)[0]))(p)
    found = {"loops": 0, "row gathers in loops": 0}

    def walk(jaxpr, in_loop):
        for eq in jaxpr.eqns:
            for v in eq.outvars:
                shape, dtype = v.aval.shape, v.aval.dtype
                assert not (shape == (n, k, d) and dtype == jnp.float32), eq
                if (eq.primitive.name == "gather" and len(shape) == 2
                        and shape[1] == d):
                    assert in_loop or shape[0] < n * k, eq
                    found["row gathers in loops"] += in_loop
            loop = eq.primitive.name == "while"
            found["loops"] += loop
            for sub in jax.core.jaxprs_in_params(eq.params):
                walk(sub, in_loop or loop)

    walk(traced.jaxpr, False)
    # 4 layers: the gathers of the dispatch (out, recomputed, back twice)
    # and of the combine (out, back) are all in loops
    assert found["row gathers in loops"] >= 4 * 5 and found["loops"] >= 4 * 9


def test_under_the_bfloat16_policy_the_router_and_the_cost_stay_float32(
        monkeypatch):
    """Network owns the policy: a compute layer's operands are bfloat16 but
    for the parameters it names in `float32_params` (the router's float32
    masters, and a float32 product at the highest precision), and the head,
    a cost layer, is told the compute dtype through `Ctx`."""
    from paddle_tpu.core import flags

    cfg = tiny_cfg(num_hidden_layers=1)
    net = Network(mellum(cfg))
    p = RT.init_params(R.param_spec(cfg), 7)
    feed, _ = batch(cfg)
    seen = {}
    plain_moe, plain_cost = M.dropless_moe, lm_head.chunked_softmax_cost

    def moe_spy(x, router_w, w_gate, *a, **kw):
        seen.update(x=x.dtype, router=router_w.dtype, w_gate=w_gate.dtype)
        return plain_moe(x, router_w, w_gate, *a, **kw)

    def cost_spy(x, w, labels, **kw):
        seen.update(head_x=x.dtype, head=kw["compute_dtype"])
        return plain_cost(x, w, labels, **kw)

    monkeypatch.setattr(M, "dropless_moe", moe_spy)
    monkeypatch.setattr(lm_head, "chunked_softmax_cost", cost_spy)
    jax.make_jaxpr(lambda p: net.loss_fn(p, feed, train=True)[0])(p)
    assert seen == {"x": jnp.float32, "router": jnp.float32,
                    "w_gate": jnp.float32, "head_x": jnp.float32,
                    "head": None}
    was = flags.get_flag("matmul_precision")
    flags.set_flag("matmul_precision", "bfloat16")
    try:
        traced = jax.make_jaxpr(
            lambda p: net.loss_fn(p, feed, train=True)[0])(p)
    finally:
        flags.set_flag("matmul_precision", was)
    assert seen == {"x": jnp.bfloat16, "router": jnp.float32,
                    "w_gate": jnp.bfloat16, "head_x": jnp.float32,
                    "head": jnp.bfloat16}
    # the logits: a float32 product of float32 operands, 8 experts wide

    def dots(jaxpr):
        for eq in jaxpr.eqns:
            if eq.primitive.name == "dot_general":
                yield eq
            for sub in jax.core.jaxprs_in_params(eq.params):
                yield from dots(sub)

    routers = [eq for eq in dots(traced.jaxpr)
               if eq.outvars[0].aval.shape == (64, 8)]
    assert routers
    for eq in routers:
        assert {v.aval.dtype for v in eq.invars} == {jnp.dtype("float32")}
        assert "HIGHEST" in str(eq.params["precision"])


def test_the_top1_capacity_layer_is_still_what_it_was():
    from paddle_tpu import dsl

    with dsl.model() as g:
        inp = dsl.data("x", dim=(16,))
        dsl.moe(inp, num_experts=4, hidden=8, name="old")
    net = Network(g.conf)
    assert sorted(net.param_confs) == ["_old.w0", "_old.w0_in", "_old.w0_out"]
    assert "old@aux" in net.specs and net.stat_outputs == {}


# ---- the head ----

@pytest.mark.parametrize("chunk", [16, 24, 64])
def test_the_chunked_head_equals_the_unchunked_cost(chunk):
    ks = jax.random.split(jax.random.key(11), 3)
    x = jax.random.normal(ks[0], (64, 32))
    w = jax.random.normal(ks[1], (32, 96))
    lab = jax.random.randint(ks[2], (64,), 0, 96)

    def whole(x, w):
        logp = jax.nn.log_softmax(x @ w, axis=-1)
        return -jnp.take_along_axis(logp, lab[:, None], axis=-1)[:, 0]

    got = lm_head.chunked_softmax_cost(x, w, lab, chunk=chunk)
    np.testing.assert_allclose(got, whole(x, w), rtol=1e-5, atol=1e-5)
    g1 = jax.grad(lambda x, w: jnp.sum(
        lm_head.chunked_softmax_cost(x, w, lab, chunk=chunk)), (0, 1))(x, w)
    g2 = jax.grad(lambda x, w: jnp.sum(whole(x, w)), (0, 1))(x, w)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    # chunked, the largest logits tensor of the program is one chunk's
    text = str(jax.make_jaxpr(lambda x, w: lm_head.chunked_softmax_cost(
        x, w, lab, chunk=16))(x, w))
    assert "f32[16,96]" in text and "f32[64,96]" not in text


# ---- through SGD.train: the normal path, Adam, the counters ----

def test_trains_through_sgd_train_and_publishes_its_counters():
    from paddle_tpu.core import flags
    from paddle_tpu.core.config import OptimizationConf
    from paddle_tpu.data import feeder as F
    from paddle_tpu.data.reader import batched
    from paddle_tpu.obs import metrics as om
    from paddle_tpu.trainer import SGD
    from paddle_tpu.trainer.events import EndIteration

    cfg = tiny_cfg(num_experts=4, experts_held_first=2)
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
        trainer = SGD(mellum(cfg), OptimizationConf(
            learning_method="adam", learning_rate=1e-2, adam_beta2=0.95),
            seed=3)
        costs = []
        trainer.train(
            reader=batched(lambda: iter(rows * 4), 2), feeder=feeder,
            num_passes=1, event_handler=lambda e: costs.append(e.cost)
            if isinstance(e, EndIteration) else None)
    finally:
        flags.set_flag("timeline_sample_period", was)
    assert len(costs) == 8 and costs[-1] < costs[0]    # the fixed rows learn
    reg = om.get_registry()
    fenced = 4                           # steps 2, 4, 6, 8 of 8
    for i in range(4):
        layer = f"l{i}_moe"
        assert reg.counter("moe.slots").get(layer=layer) == fenced * 128
        here = reg.counter("moe.slots_here").get(layer=layer)
        assert 0 < here < fenced * 128
        moved = reg.counter("moe.rows_moved").get(layer=layer)
        assert here <= moved <= fenced * 128     # a chunk (of 128) a fence
        assert reg.gauge("moe.load_max_over_mean").get(layer=layer) >= 1.0
    # what `python -m paddle_tpu metrics` prints
    text = reg.render_text()
    for name in ("moe.slots", "moe.slots_here", "moe.rows_moved",
                 "moe.load_max_over_mean"):
        assert name in text
