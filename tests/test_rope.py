"""q and k from a projection's output to the attention kernel's operand in
ONE pass (ISSUE 40): `ops/rope.to_heads`, its Pallas pass in interpret mode
against the plain composition it replaces (`rope.apply`, the score scale, a
transpose), forward and through its `custom_vjp`; which of the two a call
takes; `ops/gqa_attention.grouped`, the kernel's call alone, against the
wrapper; and the `gqa_attention` layer through both, at small sizes on the
CPU.

Tolerances. The pass and the plain path do the same float32 arithmetic in
the same order and round to x's dtype at the same two places, so on paper
they agree to the bit; the CPU's compiler contracts a product and a sum
into one fused operation in one of them and not the other, which moves a
float32 result by an ulp and, now and then, the bfloat16 it rounds to. So:
nearly every element bit for bit (`SAME`), and none further than an ulp
of bfloat16 at each of the two roundings (`ULP`: two of them, 2**-6 of its
size) plus what two float32 ulps of a product leave where a pair cancels
(`TINY`); in float32, where no rounding hides the contraction, a float32
ulp or two. Both sides are called op by op, not
under one `jit`: inside one the CPU's compiler also drops the rounding
between `apply` and the scale (a float32 -> bfloat16 -> float32 pair),
which the chip's does not."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import obs
from paddle_tpu import ops as OPS
from paddle_tpu.ops import gqa_attention as GA
from paddle_tpu.ops import rope
from tests.test_laguna import _attention_layer, _seq

YARN_HALF = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
             "original_max_position_embeddings": 4096, "beta_slow": 1,
             "beta_fast": 64, "attention_factor": 1.4158883083359672,
             "partial_rotary_factor": 0.5}
YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
PLAIN = {"rope_type": "default", "rope_theta": 10000}
ODD = dict(YARN_HALF, partial_rotary_factor=0.25)

SAME, ULP, TINY = 0.999, 2.0 ** -6, 2e-6
T = 256

# (query heads, KV heads, head, the width that turns, the rotary group): the
# Laguna cell's full and window layers, the Mellum cell's window and full
# layers, and a small odd one
CASES = {
    "laguna-full": (48, 8, 128, 64, YARN_HALF),
    "laguna-window": (64, 8, 128, 128, PLAIN),
    "mellum-window": (32, 4, 128, 128, PLAIN),
    "mellum-full": (32, 4, 128, 128, YARN),
    "odd": (3, 1, 256, 64, ODD),
}


def _rows(n, d, dtype, seed):
    x = jax.random.normal(jax.random.key(seed), (1, T, n * d))
    return x.astype(dtype)


def _bits(a):
    kind = jnp.uint16 if a.dtype == jnp.bfloat16 else jnp.uint32
    return np.asarray(jax.lax.bitcast_convert_type(a, kind))


def _agree(got, want):
    assert got.dtype == want.dtype and got.shape == want.shape
    if got.dtype == jnp.float32:     # nothing rounds a contraction's ulp away
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=TINY)
        return
    assert np.mean(_bits(got) == _bits(want)) >= SAME
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=ULP, atol=TINY)


def _calls(path):
    return obs.get_registry().counter("attn.rope_calls").get(path=path)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("case", CASES)
def test_the_pass_is_the_plain_path_forward_and_backward(case, dtype):
    """q (scaled) and k (not) of a layer: the pass in interpret mode against
    `rope.apply` + scale + transpose, and its `custom_vjp` against `jax.vjp`
    of that, the cotangents in x's dtype as the kernel's are."""
    h, kv, d, r, group = CASES[case]
    assert rope.rotary_width(d, group) == r and rope.pass_fits(T, d, r)
    cos, sin = rope.tables(T, r, group)
    for n, scale, seed in ((h, 1 / math.sqrt(d), 1), (kv, 1.0, 2)):
        x = _rows(n, d, dtype, seed)
        g = _rows(n, d, dtype, seed + 2).reshape(1, n, T, d)
        got, back = jax.vjp(lambda x: rope.to_heads(
            x, cos, sin, n, scale, impl="pass"), x)
        want, plain_back = jax.vjp(lambda x: rope.to_heads(
            x, cos, sin, n, scale, impl="plain"), x)
        assert got.shape == (1, n, T, d)
        _agree(got, want)
        _agree(back(g)[0], plain_back(g)[0])


@pytest.mark.parametrize("case", ["laguna-full", "odd"])
def test_the_lanes_past_the_rotary_width_pass_through_bit_for_bit(case):
    """A partial rotary is a table (1 in C, 0 in S), and `x * 1 + p * 0 +
    p * 0` is x: the lanes r..D of every head come out as they went in, to
    the bit, and go back so."""
    h, _, d, r, group = CASES[case]
    cos, sin = rope.tables(T, r, group)
    x = _rows(h, d, jnp.bfloat16, 3)
    heads = x.reshape(1, T, h, d).transpose(0, 2, 1, 3)
    got, back = jax.vjp(lambda x: rope.to_heads(x, cos, sin, h, impl="pass"),
                        x)
    np.testing.assert_array_equal(_bits(got[..., r:]), _bits(heads[..., r:]))
    assert not np.array_equal(_bits(got[..., :r]), _bits(heads[..., :r]))
    gx = back(heads)[0].reshape(1, T, h, d)
    np.testing.assert_array_equal(_bits(gx[..., r:]),
                                  _bits(x.reshape(1, T, h, d)[..., r:]))
    # scaled: what `(x * scale).astype` gives, the scale as x's dtype holds it
    scale = 1 / math.sqrt(d)
    scaled = rope.to_heads(x, cos, sin, h, scale, impl="pass")
    np.testing.assert_array_equal(
        _bits(scaled[..., r:]), _bits((heads * scale).astype(x.dtype)[..., r:]))


def test_the_tables_hold_the_rotation_a_partial_one_and_its_inverse():
    cos, sin = rope.tables(8, 4, PLAIN)
    c, (s,), shifts = rope.head_tables(cos, sin, 4)
    np.testing.assert_array_equal(c, jnp.concatenate([cos, cos], -1))
    np.testing.assert_array_equal(s, jnp.concatenate([-sin, sin], -1))
    assert shifts == (2,)
    c, (s1, s2), shifts = rope.head_tables(cos, sin, 8)
    one, zero = jnp.ones((8, 4)), jnp.zeros((8, 2))
    np.testing.assert_array_equal(c, jnp.concatenate([cos, cos, one], -1))
    np.testing.assert_array_equal(
        s1, jnp.concatenate([-sin, zero, 0 * one], -1))
    np.testing.assert_array_equal(
        s2, jnp.concatenate([zero, sin, 0 * one], -1))
    # the partner r / 2 lanes up arrives by a rotation of d - r / 2
    assert shifts == (6, 2)


def test_the_pass_takes_whole_position_blocks_and_as_many_heads_as_fit():
    # the cells' shapes: 1,024 positions of 4 heads (1 MiB of bfloat16)
    assert rope._blocks(8192, 48, 128, 2) == (1024, 4)
    assert rope._blocks(8192, 64, 128, 2) == (1024, 4)
    assert rope._blocks(8192, 8, 128, 2) == (1024, 4)
    # heads that divide the count; positions that divide the sequence
    assert rope._blocks(8192, 6, 128, 2) == (1024, 3)
    assert rope._blocks(384, 3, 128, 2) == (384, 3)
    assert rope._blocks(1280, 2, 128, 4) == (640, 2)


def test_which_path_a_call_takes_is_read_off_the_backend_and_the_shapes(
        monkeypatch):
    """The plain path on the CPU, and on a TPU where the head is no lane
    multiple, the sequence no block multiple or the rotary width odd; the
    pass otherwise; and `attn.rope_calls` counts each by `path`."""
    assert rope.pass_fits(8192, 128, 64) and rope.pass_fits(256, 256, 2)
    assert not rope.pass_fits(8192, 64, 64)         # half a lane tile
    assert not rope.pass_fits(8192, 192, 64)
    assert not rope.pass_fits(200, 128, 64)
    assert not rope.pass_fits(256, 128, 6 + 1)
    cos, sin = rope.tables(T, 64, YARN_HALF)
    x = _rows(2, 128, jnp.float32, 5)
    before = _calls("plain"), _calls("pass")
    on_cpu = rope.to_heads(x, cos, sin, 2, 0.5)
    assert (_calls("plain"), _calls("pass")) == (before[0] + 1, before[1])
    np.testing.assert_array_equal(
        on_cpu, rope.to_heads(x, cos, sin, 2, 0.5, impl="plain"))
    np.testing.assert_array_equal(
        on_cpu, (rope.apply(x.reshape(1, T, 2, 128), cos, sin) * 0.5
                 ).transpose(0, 2, 1, 3))
    # as on a TPU, the kernel interpreted
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(OPS, "pallas_interpret", lambda requested=None: True)
    before = _calls("plain"), _calls("pass")
    there = rope.to_heads(x, cos, sin, 2, 0.5)
    assert (_calls("plain"), _calls("pass")) == (before[0], before[1] + 1)
    np.testing.assert_allclose(there, on_cpu, rtol=1e-6, atol=TINY)
    narrow = rope.to_heads(x, cos[:, :16], sin[:, :16], 4)       # heads of 64
    assert narrow.shape == (1, 4, T, 64)
    assert (_calls("plain"), _calls("pass")) == (before[0] + 1, before[1] + 1)
    with pytest.raises(ValueError, match="multiples of 128"):
        rope.to_heads(x, cos[:, :16], sin[:, :16], 4, impl="pass")
    with pytest.raises(ValueError, match="unknown rotary impl"):
        rope.to_heads(x, cos, sin, 2, impl="fused")


# ---- the kernel's call alone ----

def _qkv(h, kv, seed=4):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (1, T, h, 128)),
            jax.random.normal(ks[1], (1, T, kv, 128)),
            jax.random.normal(ks[2], (1, T, kv, 128)))


def _grouped(q, k, v, window):
    """`gqa_attention`'s way into the kernel, spelled out."""
    b, t, h, d = q.shape
    kv = k.shape[2]
    qg = (q * (1.0 / math.sqrt(d))).astype(q.dtype)
    o = GA.grouped(qg.transpose(0, 2, 1, 3).reshape(b, kv, h // kv, t, d),
                   k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                   window=window, block_q=128, block_kv=128)
    assert o.shape == (b, kv, h // kv, t, d)
    return o.reshape(b, h, t, d).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("h,kv,window", [(6, 1, None), (8, 2, 128)])
def test_grouped_is_the_kernel_the_wrapper_calls(h, kv, window):
    """Bit for bit the wrapper's interpreted kernel, forward and gradient,
    and the portable lowering within its tolerance; a window the sequence
    does not reach is no window, as for the wrapper."""
    q, k, v = _qkv(h, kv)
    kernel = dict(impl="pallas", block_q=128, block_kv=128)

    def loss(f):
        return lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v)))

    def mine(q, k, v):
        return _grouped(q, k, v, window)

    def wrapper(q, k, v):
        return GA.gqa_attention(q, k, v, window=window, **kernel)

    np.testing.assert_array_equal(mine(q, k, v), wrapper(q, k, v))
    np.testing.assert_allclose(
        mine(q, k, v),
        GA.gqa_attention(q, k, v, window=window, impl="blocked", block_q=64),
        atol=5e-6)
    got = jax.grad(loss(mine), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(wrapper), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(_grouped(q, k, v, T + 5),
                                  _grouped(q, k, v, None))
    with pytest.raises(ValueError, match="multiples of 128"):
        GA.grouped(q[:, :100].transpose(0, 2, 1, 3)[:, :, None],
                   k[:, :100].transpose(0, 2, 1, 3),
                   v[:, :100].transpose(0, 2, 1, 3))


# ---- the layer through both ----

@pytest.mark.parametrize("kind", ["gated-half-rotary", "window"])
def test_the_layer_through_the_kernels_layout_is_the_layer(monkeypatch, kind):
    """The layer as a TPU runs it (the pass and the kernel, interpreted)
    against the layer as the CPU runs it (`rope.apply`, the portable
    attention), float32: output and every parameter's gradient."""
    attrs = dict(num_heads=6, num_kv_heads=2, head_dim=128)
    if kind == "window":
        attrs.update(window=100, rope=dict(PLAIN))
    else:
        attrs.update(window=None, rope=dict(YARN_HALF), gate="per_head")
    net = _attention_layer(**attrs)
    p = {k: 0.1 * jax.random.normal(jax.random.key(i), tuple(v.dims))
         for i, (k, v) in enumerate(sorted(net.param_confs.items()))}
    x = jax.random.normal(jax.random.key(9), (2, T, 64))

    def run(p):
        return net.forward(p, _seq(x))[0]["a"].value

    def both():
        return run(p), jax.grad(lambda p: jnp.sum(jnp.sin(run(p))))(p)

    before = _calls("plain"), _calls("pass")
    want, want_g = both()
    assert (_calls("plain"), _calls("pass")) == (before[0] + 4, before[1])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(OPS, "pallas_interpret", lambda requested=None: True)
    assert "pallas_call" in str(jax.make_jaxpr(run)(p))
    before = _calls("plain"), _calls("pass")
    got, got_g = both()
    assert (_calls("plain"), _calls("pass")) == (before[0], before[1] + 4)
    np.testing.assert_allclose(got, want, atol=5e-6)
    assert sorted(got_g) == sorted(want_g)
    for name in want_g:
        scale = float(jnp.max(jnp.abs(want_g[name])))
        np.testing.assert_allclose(got_g[name], want_g[name],
                                   atol=2e-5 * scale, err_msg=name)
