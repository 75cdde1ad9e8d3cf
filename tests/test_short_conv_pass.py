"""The gated short convolution's mixing in one Pallas pass each way
(`ops/short_conv.py`), interpreted on the CPU, against the plain composition
it replaces on a TPU: values, the gauge and gradients, a row's edges and the
blocks' edges, the rule that picks the path, the counter, and the
`short_conv` layer through both.

Blocks of 128 positions over T = 384 (three blocks a row: what a block reads
before and after it crosses two block edges each way) and pieces of 128 lanes
over D = 256 (two pieces a block).

Tolerances. The two paths do the same float32 arithmetic and round to the
input's dtype at the same places; they differ in the order of float32 sums
in the backward, and the CPU's compiler contracts a product and a sum into
one fused operation in one of them and not the other. With bfloat16 in,
every product of the forward is exact in float32 (8 significant bits times
8, then 8 times 16) and its sums are taken in the same order, so y and the
gauge are equal BIT FOR BIT; the gradients are within one bfloat16 ulp of
each entry. With float32 in, everything is within 1e-6 of the largest entry.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import dsl, obs
from paddle_tpu import ops as OPS
from paddle_tpu.network import Network
from paddle_tpu.ops import short_conv as SC
from tests.test_lfm2 import _seq

B, T, D = 2, 384, 256


def _fresh():
    # the block sizes are read while the pass is traced: drop what was
    # traced at others
    SC._forward.clear_cache()
    SC._backward.clear_cache()


@pytest.fixture
def small_blocks(monkeypatch):
    monkeypatch.setattr(SC, "PASS_ROWS", 128)
    monkeypatch.setattr(SC, "LANE_PIECE", 128)
    _fresh()
    yield
    _fresh()


def _inputs(dtype, taps, seed=3):
    k = jax.random.split(jax.random.key(seed), 3)
    bcx = jax.random.normal(k[0], (B, T, 3 * D)).astype(dtype)
    w = (0.5 * jax.random.normal(k[1], (D, taps))).astype(dtype)
    g = jax.random.normal(k[2], (B, T, D)).astype(dtype)
    return bcx, w, g


def _both_ways(impl, bcx, w, g):
    """(y, max |y|, the gradient of bcx, of w) for the cotangent g of y."""
    (y, peak), back = jax.vjp(lambda a, c: SC.mix(a, c, impl=impl), bcx, w)
    return (y, peak, *back((g, jnp.zeros((), jnp.float32))))


def _within_one_ulp(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    _, exp = np.frexp(np.maximum(np.abs(got), np.abs(want)))
    ulp = np.ldexp(1.0, exp - 8)          # bfloat16: 8 significant bits
    assert np.all(np.abs(got - want) <= ulp), np.max(np.abs(got - want) / ulp)


def _calls(path):
    return obs.get_registry().counter("conv.mix_calls").get(path=path)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("taps", [2, 3, 4])
def test_the_pass_and_its_backward_are_the_plain_composition(small_blocks,
                                                             dtype, taps):
    bcx, w, g = _inputs(dtype, taps)
    got = _both_ways("pass", bcx, w, g)
    want = _both_ways("plain", bcx, w, g)
    shapes = [(B, T, D), (), (B, T, 3 * D), (D, taps)]
    for name, a, b, shape in zip(("y", "max", "dbcx", "dw"), got, want,
                                 shapes):
        assert a.shape == b.shape == shape, name
        assert a.dtype == b.dtype, name
        if dtype == jnp.float32:
            scale = float(jnp.max(jnp.abs(b)))
            np.testing.assert_allclose(a, b, rtol=0, atol=1e-6 * scale,
                                       err_msg=name)
        elif name in ("y", "max"):
            np.testing.assert_array_equal(a, b, err_msg=name)
        else:
            _within_one_ulp(a, b)
    # a max is exact: the gauge reads the same number both ways
    assert got[1].dtype == jnp.float32
    if dtype == jnp.bfloat16:
        assert float(got[1]) == float(want[1])


def _by_position(bcx, w):
    """y from the equations in numpy float64, position by position."""
    bcx, w = np.asarray(bcx, np.float64), np.asarray(w, np.float64)
    b_, c_, x_ = bcx[..., :D], bcx[..., D: 2 * D], bcx[..., 2 * D:]
    z, taps = b_ * x_, w.shape[1]
    s = np.zeros_like(z)
    for t in range(T):
        for j in range(taps):
            if t - (taps - 1) + j >= 0:
                s[:, t] += w[:, j] * z[:, t - (taps - 1) + j]
    return c_ * s


def test_a_rows_first_positions_see_zeros_and_no_row_sees_another(
        small_blocks):
    bcx, w, g = _inputs(jnp.float32, 3)
    y, _, dbcx, _ = _both_ways("pass", bcx, w, g)
    want = _by_position(bcx, w)
    np.testing.assert_allclose(y, want, rtol=0, atol=1e-6 * np.abs(want).max())
    # s_0 = w_2 z_0 and s_1 = w_1 z_0 + w_2 z_1: nothing before a row's start
    z = np.asarray(bcx[..., :D] * bcx[..., 2 * D:], np.float64)
    c_, wn = np.asarray(bcx[..., D: 2 * D], np.float64), np.asarray(w)
    np.testing.assert_allclose(y[:, 0], c_[:, 0] * wn[:, 2] * z[:, 0],
                               rtol=1e-6)
    np.testing.assert_allclose(
        y[:, 1], c_[:, 1] * (wn[:, 1] * z[:, 0] + wn[:, 2] * z[:, 1]),
        rtol=1e-5)
    # row 1 changed: row 0 the same, forward and backward
    other = _both_ways("pass", bcx.at[1].multiply(3.0), w, g.at[1].add(1.0))
    np.testing.assert_array_equal(other[0][0], y[0])
    np.testing.assert_array_equal(other[2][0], dbcx[0])
    # a block's last position changed: the next block's first two positions
    # see it, the positions before it and past those do not
    edge = _both_ways("pass", bcx.at[:, 127].add(1.0), w, g)[0]
    np.testing.assert_array_equal(edge[:, :127], y[:, :127])
    assert np.all(np.any(edge[:, 127:130] != y[:, 127:130], axis=-1))
    np.testing.assert_array_equal(edge[:, 130:], y[:, 130:])
    # the cotangent of a block's first position reaches B and x of the two
    # positions before it, in the block before, and C of its own alone
    back = _both_ways("pass", bcx, w, jnp.zeros_like(g).at[:, 256].set(1.0))[2]
    assert not np.any(back[:, :254]) and not np.any(back[:, 257:])
    for p in (0, 2):
        assert np.all(np.any(back[:, 254:257, p * D: (p + 1) * D] != 0, -1))
    assert not np.any(back[:, 254:256, D: 2 * D])
    assert np.any(back[:, 256, D: 2 * D] != 0)
    # nothing past a row's end: the last position's cotangent reaches only
    # its own and the two before it
    back = _both_ways("pass", bcx, w, jnp.zeros_like(g).at[:, -1].set(1.0))[2]
    assert not np.any(back[:, :T - 3])


def test_which_path_a_call_takes_is_read_off_the_backend_and_the_shapes(
        monkeypatch, small_blocks):
    """The plain path on the CPU; on a TPU the pass where D and T are lane
    multiples and the taps reach no further back than the slab, else plain;
    `conv.mix_calls` counts each by `path`; a pass the shapes do not fit is
    refused by name."""
    assert SC.pass_fits(8192, 2048, 3) and SC.pass_fits(128, 128, 9)
    assert not SC.pass_fits(8192, 2000, 3)
    assert not SC.pass_fits(8100, 2048, 3)
    assert not SC.pass_fits(8192, 2048, 10)
    bcx, w, _ = _inputs(jnp.float32, 3)
    before = _calls("plain"), _calls("pass")
    on_cpu = SC.mix(bcx, w)
    assert (_calls("plain"), _calls("pass")) == (before[0] + 1, before[1])
    for a, b in zip(on_cpu, SC.mix(bcx, w, impl="plain")):
        np.testing.assert_array_equal(a, b)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(OPS, "pallas_interpret", lambda requested=None: True)
    before = _calls("plain"), _calls("pass")
    assert "pallas_call" in str(jax.make_jaxpr(SC.mix)(bcx, w))
    assert (_calls("plain"), _calls("pass")) == (before[0], before[1] + 1)
    for narrow in (bcx[:, :, : 3 * 200], bcx[:, :200]):     # D 200; T 200
        before = _calls("plain"), _calls("pass")
        w_ = w[: narrow.shape[-1] // 3]
        assert "pallas_call" not in str(jax.make_jaxpr(SC.mix)(narrow, w_))
        assert (_calls("plain"), _calls("pass")) == (before[0] + 1, before[1])
        with pytest.raises(ValueError, match="multiples of 128"):
            SC.mix(narrow, w_, impl="pass")
    with pytest.raises(ValueError, match="unknown short convolution impl"):
        SC.mix(bcx, w, impl="fused")


def test_the_blocks_are_lane_multiples_within_the_byte_budget():
    # the cell's shape: 256 positions of 3 x 2,048 bfloat16 lanes (3 MiB),
    # pieces of 512 lanes
    assert SC._rows(8192, 2048, 2) == 256 and SC._piece(2048) == 512
    assert SC._rows(8192, 2048, 4) == 128
    # positions that divide the sequence, up to PASS_ROWS; lanes that divide D
    assert SC._rows(384, 256, 4) == 384 and SC._rows(1280, 128, 2) == 256
    assert SC._piece(384) == 384 and SC._piece(640) == 128


# ---- the layer ----

def _layer(d):
    with dsl.model() as g:
        inp = dsl.data("x", dim=(d,), is_seq=True)
        dsl._add("short_conv", [inp], name="a", size=d, bias=False)
    return Network(g.conf)


def test_the_layer_through_the_pass_is_the_layer(monkeypatch, small_blocks):
    """The layer as a TPU runs it (the pass, interpreted) against the layer
    as the CPU runs it, float32: output, gauge and every parameter's
    gradient; `conv.mix_calls` counts one `pass` a trace, `plain` on the
    CPU."""
    net = _layer(D)
    p = {k: 0.1 * jax.random.normal(jax.random.key(i), tuple(v.dims))
         for i, (k, v) in enumerate(sorted(net.param_confs.items()))}
    x = jax.random.normal(jax.random.key(9), (B, T, D))

    def run(p):
        outs = net.forward(p, _seq(x))[0]
        return outs["a"].value, outs["a@stats"].value

    def both():
        return run(p), jax.grad(lambda p: jnp.sum(jnp.sin(run(p)[0])))(p)

    before = _calls("plain"), _calls("pass")
    (want, want_max), want_g = both()
    assert (_calls("plain"), _calls("pass")) == (before[0] + 2, before[1])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(OPS, "pallas_interpret", lambda requested=None: True)
    before = _calls("plain"), _calls("pass")
    (got, got_max), got_g = both()
    assert (_calls("plain"), _calls("pass")) == (before[0], before[1] + 2)
    np.testing.assert_allclose(got, want, atol=1e-6 * float(jnp.max(
        jnp.abs(want))))
    assert float(got_max[0, 0]) == pytest.approx(float(want_max[0, 0]),
                                                 rel=1e-6)
    assert sorted(got_g) == sorted(want_g) == ["_a.conv_w", "_a.w_in",
                                               "_a.w_out"]
    for name in want_g:
        scale = float(jnp.max(jnp.abs(want_g[name])))
        np.testing.assert_allclose(got_g[name], want_g[name],
                                   atol=2e-6 * scale, err_msg=name)
