"""Gradient + semantics tests for the long-tail layers
(reference: test_LayerGrad.cpp cases for selective_fc, conv_shift,
bilinear_interp, convex_comb, eos_id, power, clip, row_conv,
featmap_expand; ContextProjection via test_LayerGrad projections)."""

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import dsl
from paddle_tpu.core.arg import Arg, id_arg, non_seq, seq
from paddle_tpu.core.config import InputConf, LayerConf
from paddle_tpu.network import Network
from paddle_tpu.testing import check_layer_grad, data_conf, random_arg

RNG = lambda: np.random.default_rng(11)


def feed_for(data_confs, batch=4, max_len=5, vocab=10):
    rng = RNG()
    return {
        dc.name: random_arg(
            rng,
            dc.attrs["dim"],
            batch=batch,
            is_seq=dc.attrs["is_seq"],
            max_len=max_len,
            is_ids=dc.attrs["is_ids"],
            vocab=vocab,
        )
        for dc in data_confs
    }


def test_selective_fc_grad_and_mask():
    dcs = [data_conf("in", 6)]
    lc = LayerConf(
        name="sfc", type="selective_fc", size=5,
        inputs=[InputConf("in")], active_type="tanh",
    )
    check_layer_grad(lc, dcs, feed_for(dcs))


def test_selective_fc_masks_outputs():
    with dsl.model() as g:
        x = dsl.data("x", 4)
        sel = dsl.data("sel", 3)
        dsl.selective_fc(x, sel, size=3, name="out")
    net = Network(g.conf)
    params = net.init_params(jax.random.key(0))
    mask = np.asarray([[1, 0, 1], [0, 1, 0]], np.float32)
    feed = {
        "x": non_seq(jnp.ones((2, 4))),
        "sel": non_seq(jnp.asarray(mask)),
    }
    outs, _ = net.forward(params, feed, outputs=["out"])
    v = np.asarray(outs["out"].value)
    assert (v[mask == 0] == 0).all()
    assert (v[mask == 1] != 0).any()


def test_conv_shift_grad_and_identity():
    dcs = [data_conf("a", 7), data_conf("b", 3)]
    lc = LayerConf(
        name="cs", type="conv_shift", size=0,
        inputs=[InputConf("a"), InputConf("b")], bias=False,
    )
    check_layer_grad(lc, dcs, feed_for(dcs))
    # delta filter at center = identity
    with dsl.model() as g:
        a = dsl.data("a", 5)
        b = dsl.data("b", 3)
        dsl.conv_shift(a, b, name="c")
    net = Network(g.conf)
    params = net.init_params(jax.random.key(0))
    av = jnp.asarray(np.arange(10, dtype=np.float32).reshape(2, 5))
    delta = jnp.asarray([[0.0, 1.0, 0.0]] * 2)
    outs, _ = net.forward(
        params, {"a": non_seq(av), "b": non_seq(delta)}, outputs=["c"]
    )
    np.testing.assert_allclose(np.asarray(outs["c"].value), np.asarray(av))
    # shift-by-one filter rotates circularly
    shift1 = jnp.asarray([[0.0, 0.0, 1.0]] * 2)  # b[+1]: c[i] = a[i+1]
    outs, _ = net.forward(
        params, {"a": non_seq(av), "b": non_seq(shift1)}, outputs=["c"]
    )
    np.testing.assert_allclose(
        np.asarray(outs["c"].value), np.roll(np.asarray(av), -1, axis=1)
    )


def test_bilinear_interp_grad_and_values():
    dcs = [data_conf("img", (4, 4, 2))]
    lc = LayerConf(
        name="bi", type="bilinear_interp", size=0,
        inputs=[InputConf("img")], bias=False,
        attrs={"out_size_x": 8, "out_size_y": 8},
    )
    check_layer_grad(lc, dcs, feed_for(dcs, batch=2))
    # constant image stays constant under resize
    with dsl.model() as g:
        img = dsl.data("img", (4, 4, 1))
        dsl.bilinear_interp(img, 7, 5, name="out")
    net = Network(g.conf)
    params = net.init_params(jax.random.key(0))
    outs, _ = net.forward(
        params, {"img": non_seq(jnp.ones((1, 4, 4, 1)))}, outputs=["out"]
    )
    assert outs["out"].value.shape == (1, 5, 7, 1)
    np.testing.assert_allclose(np.asarray(outs["out"].value), 1.0, rtol=1e-5)
    # align-corners (BilinearInterpLayer.cpp): a ramp keeps exact corner
    # values and interpolates linearly with ratio (in-1)/(out-1)
    with dsl.model() as g2:
        img2 = dsl.data("img", (2, 2, 1))
        dsl.bilinear_interp(img2, 3, 3, name="out")
    net2 = Network(g2.conf)
    p2 = net2.init_params(jax.random.key(0))
    ramp = jnp.asarray([[[[0.0], [1.0]], [[2.0], [3.0]]]])
    outs2, _ = net2.forward(p2, {"img": non_seq(ramp)}, outputs=["out"])
    np.testing.assert_allclose(
        np.asarray(outs2["out"].value)[0, :, :, 0],
        [[0.0, 0.5, 1.0], [1.0, 1.5, 2.0], [2.0, 2.5, 3.0]],
        atol=1e-6,
    )


def test_convex_comb_grad_and_values():
    dcs = [data_conf("w", 3), data_conf("x", 12)]
    lc = LayerConf(
        name="cc", type="convex_comb", size=4,
        inputs=[InputConf("w"), InputConf("x")], bias=False,
    )
    check_layer_grad(lc, dcs, feed_for(dcs))
    with dsl.model() as g:
        w = dsl.data("w", 2)
        x = dsl.data("x", 6)
        dsl.linear_comb(w, x, size=3, name="out")
    net = Network(g.conf)
    params = net.init_params(jax.random.key(0))
    wv = jnp.asarray([[1.0, 0.0]])
    xv = jnp.asarray([[1.0, 2, 3, 4, 5, 6]])
    outs, _ = net.forward(
        params, {"w": non_seq(wv), "x": non_seq(xv)}, outputs=["out"]
    )
    np.testing.assert_allclose(
        np.asarray(outs["out"].value), [[1.0, 2.0, 3.0]]
    )


def test_eos_id():
    with dsl.model() as g:
        ids = dsl.data("ids", 1, is_seq=True, is_ids=True)
        dsl.eos_id(ids, eos_id=2, name="eos")
    net = Network(g.conf)
    params = net.init_params(jax.random.key(0))
    iv = jnp.asarray([[1, 2, 0], [2, 1, 2]], jnp.int32)
    outs, _ = net.forward(
        params,
        {"ids": id_arg(iv, jnp.asarray([3, 3], jnp.int32))},
        outputs=["eos"],
    )
    np.testing.assert_allclose(
        np.asarray(outs["eos"].value)[..., 0],
        [[0, 1, 0], [1, 0, 1]],
    )


def test_power_and_clip():
    dcs = [data_conf("w", 1), data_conf("x", 4)]
    feed = feed_for(dcs)
    feed["x"] = Arg(value=jnp.abs(feed["x"].value) + 0.5)  # positive base
    lc = LayerConf(
        name="pw", type="power", size=0,
        inputs=[InputConf("w"), InputConf("x")], bias=False,
    )
    check_layer_grad(lc, dcs, feed)
    with dsl.model() as g:
        x = dsl.data("x", 3)
        dsl.clip(x, min=-0.5, max=0.5, name="out")
    net = Network(g.conf)
    params = net.init_params(jax.random.key(0))
    outs, _ = net.forward(
        params,
        {"x": non_seq(jnp.asarray([[-2.0, 0.2, 3.0]]))},
        outputs=["out"],
    )
    np.testing.assert_allclose(
        np.asarray(outs["out"].value), [[-0.5, 0.2, 0.5]]
    )


def test_row_conv_grad_and_lookahead():
    dcs = [data_conf("x", 4, is_seq=True)]
    lc = LayerConf(
        name="rc", type="row_conv", size=0, inputs=[InputConf("x")],
        bias=False, attrs={"context_length": 3},
    )
    check_layer_grad(lc, dcs, feed_for(dcs))
    # with identity-ish weight on tap 1 only, y[t] == x[t+1]
    with dsl.model() as g:
        x = dsl.data("x", 2, is_seq=True)
        dsl.row_conv(x, context_length=2, name="out")
    net = Network(g.conf)
    params = net.init_params(jax.random.key(0))
    params = dict(params)
    params["_out.w0"] = jnp.asarray([[0.0, 0.0], [1.0, 1.0]])
    xv = jnp.asarray(np.arange(12, dtype=np.float32).reshape(1, 6, 2))
    outs, _ = net.forward(
        params,
        {"x": seq(xv, jnp.asarray([6], jnp.int32))},
        outputs=["out"],
    )
    got = np.asarray(outs["out"].value)
    np.testing.assert_allclose(got[0, :5], np.asarray(xv)[0, 1:])
    np.testing.assert_allclose(got[0, 5], 0.0)


def test_featmap_expand():
    with dsl.model() as g:
        x = dsl.data("x", 3)
        dsl.featmap_expand(x, num_filters=2, name="out")
    net = Network(g.conf)
    params = net.init_params(jax.random.key(0))
    xv = jnp.asarray([[1.0, 2.0, 3.0]])
    outs, _ = net.forward(params, {"x": non_seq(xv)}, outputs=["out"])
    np.testing.assert_allclose(
        np.asarray(outs["out"].value), [[1, 2, 3, 1, 2, 3]]
    )


def test_selective_fc_softmax_restricts_denominator():
    with dsl.model() as g:
        x = dsl.data("x", 4)
        sel = dsl.data("sel", 3)
        dsl.selective_fc(x, sel, size=3, act="softmax", name="out")
    net = Network(g.conf)
    params = net.init_params(jax.random.key(0))
    mask = np.asarray([[1.0, 0.0, 1.0]], np.float32)
    outs, _ = net.forward(
        params,
        {"x": non_seq(jnp.ones((1, 4))), "sel": non_seq(jnp.asarray(mask))},
        outputs=["out"],
    )
    v = np.asarray(outs["out"].value)[0]
    assert v[1] == 0.0
    np.testing.assert_allclose(v.sum(), 1.0, rtol=1e-5)  # selected-only


def test_row_conv_no_padding_leak():
    # short sequence in a longer batch: lookahead past the sequence's own
    # end must contribute zero even when padding holds garbage
    with dsl.model() as g:
        x = dsl.data("x", 1, is_seq=True)
        dsl.row_conv(x, context_length=2, name="out")
    net = Network(g.conf)
    params = net.init_params(jax.random.key(0))
    params = dict(params)
    params["_out.w0"] = jnp.asarray([[0.0], [1.0]])  # pure lookahead tap
    xv = jnp.asarray([[[1.0], [2.0], [7.0], [7.0]]])  # padding = 7
    outs, _ = net.forward(
        params,
        {"x": seq(xv, jnp.asarray([2], jnp.int32))},
        outputs=["out"],
    )
    got = np.asarray(outs["out"].value)[0, :, 0]
    np.testing.assert_allclose(got, [2.0, 0.0, 0.0, 0.0])


def test_context_projection_no_padding_leak():
    with dsl.model() as g:
        x = dsl.data("x", 2, is_seq=True)
        dsl.mixed(6, [dsl.context_projection(x, 3, -1)], name="out",
                  bias=False)
    net = Network(g.conf)
    params = net.init_params(jax.random.key(0))
    xv = jnp.asarray([[[1.0, 2], [3, 4], [9, 9], [9, 9]]])  # padding = 9
    outs, _ = net.forward(
        params, {"x": seq(xv, jnp.asarray([2], jnp.int32))}, outputs=["out"]
    )
    got = np.asarray(outs["out"].value)[0]
    want = np.asarray(
        [[0, 0, 1, 2, 3, 4], [1, 2, 3, 4, 0, 0], [0] * 6, [0] * 6],
        np.float32,
    )
    np.testing.assert_allclose(got, want)


def test_context_projection_values_and_grad():
    dcs = [data_conf("x", 2, is_seq=True)]
    lc = LayerConf(
        name="mx", type="mixed", size=6, bias=False,
        inputs=[
            InputConf(
                "x",
                attrs={
                    "proj": "context",
                    "context_length": 3,
                    "context_start": -1,
                },
            )
        ],
    )
    check_layer_grad(lc, dcs, feed_for(dcs))
    # ContextProjection.h:28-40 example: L=3, start=-1, zero padding
    with dsl.model() as g:
        x = dsl.data("x", 2, is_seq=True)
        dsl.mixed(6, [dsl.context_projection(x, 3, -1)], name="out",
                  bias=False)
    net = Network(g.conf)
    params = net.init_params(jax.random.key(0))
    xv = jnp.asarray(
        [[[1.0, 2], [3, 4], [5, 6], [7, 8]]]
    )  # a,b,c,d
    outs, _ = net.forward(
        params, {"x": seq(xv, jnp.asarray([4], jnp.int32))}, outputs=["out"]
    )
    got = np.asarray(outs["out"].value)[0]
    want = np.asarray(
        [
            [0, 0, 1, 2, 3, 4],
            [1, 2, 3, 4, 5, 6],
            [3, 4, 5, 6, 7, 8],
            [5, 6, 7, 8, 0, 0],
        ],
        np.float32,
    )
    np.testing.assert_allclose(got, want)


def test_sub_seq_layer():
    with dsl.model() as g:
        x = dsl.data("x", 2, is_seq=True)
        off = dsl.data("off", 1, is_ids=True)
        size = dsl.data("size", 1, is_ids=True)
        dsl.sub_seq(x, off, size, name="out")
    net = Network(g.conf)
    params = net.init_params(jax.random.key(0))
    xv = jnp.asarray(np.arange(16, dtype=np.float32).reshape(1, 8, 2))
    feed = {
        "x": seq(xv, jnp.asarray([6], jnp.int32)),
        "off": id_arg(jnp.asarray([2], jnp.int32)),
        "size": id_arg(jnp.asarray([3], jnp.int32)),
    }
    outs, _ = net.forward(params, feed, outputs=["out"])
    got = outs["out"]
    assert np.asarray(got.seq_lens).tolist() == [3]
    np.testing.assert_allclose(
        np.asarray(got.value)[0, :3], np.asarray(xv)[0, 2:5]
    )
    np.testing.assert_allclose(np.asarray(got.value)[0, 3:], 0.0)
    # span clamped inside the real sequence
    feed["size"] = id_arg(jnp.asarray([99], jnp.int32))
    outs, _ = net.forward(params, feed, outputs=["out"])
    assert np.asarray(outs["out"].seq_lens).tolist() == [4]  # 6 - 2


def test_sub_seq_out_of_range_offset_empty():
    with dsl.model() as g:
        x = dsl.data("x", 2, is_seq=True)
        off = dsl.data("off", 1, is_ids=True)
        size = dsl.data("size", 1, is_ids=True)
        dsl.sub_seq(x, off, size, name="out")
    net = Network(g.conf)
    params = net.init_params(jax.random.key(0))
    xv = jnp.ones((1, 6, 2))
    feed = {
        "x": seq(xv, jnp.asarray([4], jnp.int32)),
        "off": id_arg(jnp.asarray([4], jnp.int32)),  # == seq_len
        "size": id_arg(jnp.asarray([2], jnp.int32)),
    }
    outs, _ = net.forward(params, feed, outputs=["out"])
    assert np.asarray(outs["out"].seq_lens).tolist() == [0]
    np.testing.assert_allclose(np.asarray(outs["out"].value), 0.0)


def test_prelu_layer():
    dcs = [data_conf("x", 5)]
    lc = LayerConf(name="pr", type="prelu", size=0,
                   inputs=[InputConf("x")], bias=False)
    check_layer_grad(lc, dcs, feed_for(dcs))
    with dsl.model() as g:
        x = dsl.data("x", 3)
        dsl.prelu(x, name="out")
    net = Network(g.conf)
    params = dict(net.init_params(jax.random.key(0)))
    params["_out.w0"] = jnp.asarray([0.1, 0.2, 0.5])
    outs, _ = net.forward(
        params, {"x": non_seq(jnp.asarray([[-1.0, -1.0, 2.0]]))},
        outputs=["out"],
    )
    np.testing.assert_allclose(
        np.asarray(outs["out"].value), [[-0.1, -0.2, 2.0]], rtol=1e-6
    )


def test_gated_unit_layer():
    dcs = [data_conf("x", 4)]
    lc = LayerConf(name="gu", type="gated_unit", size=6,
                   inputs=[InputConf("x")], active_type="tanh")
    check_layer_grad(lc, dcs, feed_for(dcs))


def test_repeat_layer():
    with dsl.model() as g:
        x = dsl.data("x", 2)
        dsl.repeat(x, 3, name="out")
    net = Network(g.conf)
    params = net.init_params(jax.random.key(0))
    outs, _ = net.forward(
        params, {"x": non_seq(jnp.asarray([[1.0, 2.0]]))}, outputs=["out"]
    )
    np.testing.assert_allclose(
        np.asarray(outs["out"].value), [[1, 2, 1, 2, 1, 2]]
    )


def test_kmax_seq_score_layer():
    with dsl.model() as g:
        s = dsl.data("s", 1, is_seq=True)
        dsl.kmax_seq_score(s, beam_size=2, name="out")
    net = Network(g.conf)
    params = net.init_params(jax.random.key(0))
    scores = jnp.asarray([[[0.1], [0.9], [0.5], [0.7]]])
    outs, _ = net.forward(
        params,
        {"s": seq(scores, jnp.asarray([3], jnp.int32))},  # pos 3 masked
        outputs=["out"],
    )
    ids = np.asarray(outs["out"].ids)
    assert ids.tolist() == [[1, 2]]  # 0.9 then 0.5; 0.7 beyond seq_len


def test_prelu_conv_feature_map_and_groups():
    # per-element slopes broadcast over an (H,W,C) feature map
    with dsl.model() as g:
        img = dsl.data("img", (4, 4, 2))
        dsl.prelu(img, name="out")
    net = Network(g.conf)
    params = net.init_params(jax.random.key(0))
    x = -jnp.ones((1, 4, 4, 2))
    outs, _ = net.forward(params, {"img": non_seq(x)}, outputs=["out"])
    np.testing.assert_allclose(np.asarray(outs["out"].value), -0.25)
    # grouped slopes: partial_sum=4 on size 8 -> 2 shared slopes
    with dsl.model() as g2:
        v = dsl.data("v", 8)
        dsl.prelu(v, partial_sum=4, name="out")
    net2 = Network(g2.conf)
    p2 = dict(net2.init_params(jax.random.key(0)))
    assert p2["_out.w0"].shape == (2,)
    p2["_out.w0"] = jnp.asarray([0.0, 1.0])
    outs2, _ = net2.forward(
        p2, {"v": non_seq(-jnp.ones((1, 8)))}, outputs=["out"]
    )
    np.testing.assert_allclose(
        np.asarray(outs2["out"].value), [[0, 0, 0, 0, -1, -1, -1, -1]]
    )


def test_kmax_short_sequence_sentinel():
    with dsl.model() as g:
        s = dsl.data("s", 1, is_seq=True)
        dsl.kmax_seq_score(s, beam_size=4, name="out")
    net = Network(g.conf)
    params = net.init_params(jax.random.key(0))
    scores = jnp.asarray([[[0.1], [0.9], [0.5], [0.7]]])
    outs, _ = net.forward(
        params, {"s": seq(scores, jnp.asarray([2], jnp.int32))},
        outputs=["out"],
    )
    assert np.asarray(outs["out"].ids).tolist() == [[1, 0, -1, -1]]
