"""Layer gradient checks — the test_LayerGrad.cpp equivalent
(reference: paddle/gserver/tests/test_LayerGrad.cpp via LayerGradUtil.h)."""

import jax
import numpy as np
import pytest

from paddle_tpu.core.config import InputConf, LayerConf
from paddle_tpu.testing import check_layer_grad, data_conf, random_arg

RNG = lambda: np.random.default_rng(7)


def feed_for(data_confs, batch=4, max_len=5, vocab=10):
    rng = RNG()
    feed = {}
    for dc in data_confs:
        a = dc.attrs
        feed[dc.name] = random_arg(
            rng,
            a["dim"],
            batch=batch,
            is_seq=a["is_seq"],
            max_len=max_len,
            is_ids=a["is_ids"],
            vocab=vocab,
        )
    return feed


@pytest.mark.parametrize("act", ["", "sigmoid", "tanh", "relu", "softmax", "stanh"])
def test_fc_grad(act):
    dcs = [data_conf("in", 8)]
    lc = LayerConf(name="fc", type="fc", size=6, inputs=[InputConf("in")], active_type=act)
    check_layer_grad(lc, dcs, feed_for(dcs))


def test_fc_two_inputs_seq():
    dcs = [data_conf("a", 5, is_seq=True), data_conf("b", 3, is_seq=True)]
    lc = LayerConf(
        name="fc", type="fc", size=4, inputs=[InputConf("a"), InputConf("b")],
        active_type="tanh",
    )
    check_layer_grad(lc, dcs, feed_for(dcs))


def test_embedding_grad():
    dcs = [data_conf("ids", 1, is_seq=True, is_ids=True)]
    lc = LayerConf(
        name="emb", type="embedding", size=6, inputs=[InputConf("ids")],
        attrs={"vocab_size": 10}, bias=False,
    )
    check_layer_grad(lc, dcs, feed_for(dcs))


def test_conv_grad():
    dcs = [data_conf("img", (6, 6, 3))]
    lc = LayerConf(
        name="conv", type="exconv", size=4, inputs=[InputConf("img")],
        active_type="relu",
        attrs={"filter_size": 3, "stride": 1, "padding": 1, "num_filters": 4},
    )
    check_layer_grad(lc, dcs, feed_for(dcs, batch=2))


def test_pool_grad():
    dcs = [data_conf("img", (6, 6, 2))]
    for pt in ["max", "avg"]:
        lc = LayerConf(
            name="pool", type="pool", size=0, inputs=[InputConf("img")],
            attrs={"pool_type": pt, "pool_size": 2, "stride": 2},
        )
        check_layer_grad(lc, dcs, feed_for(dcs, batch=2))


def test_batch_norm_grad():
    dcs = [data_conf("in", 6)]
    lc = LayerConf(name="bn", type="batch_norm", size=6, inputs=[InputConf("in")])
    # train-mode batch norm: batch statistics make per-element numeric
    # grads couple across the batch; loosen tolerance accordingly
    check_layer_grad(lc, dcs, feed_for(dcs, batch=8), train=True, rtol=1e-1, atol=5e-3)


def test_seqpool_grads():
    dcs = [data_conf("s", 5, is_seq=True)]
    for pt in ["sum", "average", "max", "sqrt_average"]:
        lc = LayerConf(
            name="sp", type="seqpool", size=5, inputs=[InputConf("s")],
            attrs={"pool_type": pt},
        )
        check_layer_grad(lc, dcs, feed_for(dcs))


def test_seqlast_first_grad():
    dcs = [data_conf("s", 4, is_seq=True)]
    for sel_first in [False, True]:
        lc = LayerConf(
            name="sl", type="seqlastins", size=4, inputs=[InputConf("s")],
            attrs={"select_first": sel_first},
        )
        check_layer_grad(lc, dcs, feed_for(dcs))


def test_expand_grad():
    dcs = [data_conf("v", 4), data_conf("ref", 3, is_seq=True)]
    lc = LayerConf(name="ex", type="expand", size=4,
                   inputs=[InputConf("v"), InputConf("ref")])
    check_layer_grad(lc, dcs, feed_for(dcs))


def test_recurrent_grad():
    dcs = [data_conf("x", 4, is_seq=True)]
    for rev in [False, True]:
        lc = LayerConf(
            name="rnn", type="recurrent", size=4, inputs=[InputConf("x")],
            active_type="tanh", attrs={"reversed": rev},
        )
        check_layer_grad(lc, dcs, feed_for(dcs, batch=3, max_len=4))


@pytest.mark.parametrize("rev", [False, True])
def test_lstm_grad(rev):
    dcs = [data_conf("x", 12, is_seq=True)]
    lc = LayerConf(name="lstm", type="lstmemory", size=3, inputs=[InputConf("x")],
                   attrs={"reversed": rev})
    check_layer_grad(lc, dcs, feed_for(dcs, batch=3, max_len=4))


@pytest.mark.parametrize("rev", [False, True])
def test_gru_grad(rev):
    dcs = [data_conf("x", 9, is_seq=True)]
    lc = LayerConf(name="gru", type="grumemory", size=3, inputs=[InputConf("x")],
                   attrs={"reversed": rev})
    check_layer_grad(lc, dcs, feed_for(dcs, batch=3, max_len=4))


def test_mixed_projections_grad():
    dcs = [data_conf("a", 4), data_conf("b", 6)]
    lc = LayerConf(
        name="mx", type="mixed", size=6,
        inputs=[
            InputConf("a", attrs={"proj": "full_matrix"}),
            InputConf("b", attrs={"proj": "identity"}),
        ],
        active_type="tanh",
    )
    check_layer_grad(lc, dcs, feed_for(dcs))


def test_tensor_layer_grad():
    dcs = [data_conf("a", 3), data_conf("b", 4)]
    lc = LayerConf(
        name="t", type="tensor", size=2, inputs=[InputConf("a"), InputConf("b")]
    )
    check_layer_grad(lc, dcs, feed_for(dcs))


def test_cos_sim_grad():
    dcs = [data_conf("a", 5), data_conf("b", 5)]
    lc = LayerConf(name="cs", type="cos", size=1,
                   inputs=[InputConf("a"), InputConf("b")], attrs={"scale": 5.0})
    check_layer_grad(lc, dcs, feed_for(dcs))


def test_costs_grad():
    # softmax-with-CE on logits
    dcs = [data_conf("x", 5), data_conf("lbl", 1, is_ids=True)]
    lc = LayerConf(
        name="c", type="classification_cost", size=1,
        inputs=[InputConf("x"), InputConf("lbl")], bias=False,
    )
    check_layer_grad(lc, dcs, feed_for(dcs, vocab=5))

    dcs = [data_conf("x", 5), data_conf("y", 5)]
    for t in ["square_error", "smooth_l1"]:
        lc = LayerConf(name="c", type=t, size=1,
                       inputs=[InputConf("x"), InputConf("y")], bias=False)
        check_layer_grad(lc, dcs, feed_for(dcs))


def test_mdlstm_grad():
    """2-D MDLSTM (gserver/layers/MDLstmLayer.cpp): numeric-vs-analytic
    gradients on a small grid."""
    h = 2
    dcs = [data_conf("x", (3, 3, 5 * h))]
    lc = LayerConf(
        name="md", type="mdlstm", size=h, inputs=[InputConf("x")]
    )
    check_layer_grad(lc, dcs, feed_for(dcs, batch=2))


def test_mdlstm_boundary_and_directions():
    import jax.numpy as jnp
    """Edge cells see zero neighbor state exactly; descending
    directions equal flipping the grid, running ascending, and
    flipping back."""
    import jax

    from paddle_tpu import dsl
    from paddle_tpu.core.arg import non_seq
    from paddle_tpu.network import Network

    h, gh, gw = 3, 4, 5
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, gh, gw, 5 * h)).astype(np.float32)

    def build(directions):
        with dsl.model() as g:
            d = dsl.data("x", (gh, gw, 5 * h))
            dsl.mdlstm(d, size=h, name="md", directions=directions)
        return Network(g.conf)

    net_f = build((True, True))
    net_r = build((False, True))
    params = net_f.init_params(jax.random.key(0))
    yf, _ = net_f.forward(params, {"x": non_seq(jnp.asarray(x))})
    yr, _ = net_r.forward(params, {"x": non_seq(jnp.asarray(x))})
    yf2, _ = net_f.forward(
        params, {"x": non_seq(jnp.asarray(x[:, ::-1].copy()))}
    )
    np.testing.assert_allclose(
        np.asarray(yr["md"].value),
        np.asarray(yf2["md"].value)[:, ::-1],
        atol=1e-5,
    )

    # cell (0,0) has no neighbors: equals the closed-form LSTM cell on
    # zero states
    (w,), (b,) = (
        [v for k, v in params.items() if k.endswith("w0")],
        [v for k, v in params.items() if k.endswith(".wbias") or k.endswith("b")],
    )
    pre = x[:, 0, 0] + np.asarray(b)[: 5 * h]
    sig = lambda v: 1.0 / (1.0 + np.exp(-v))
    ig = sig(pre[:, :h])
    g_ = np.tanh(pre[:, 3 * h : 4 * h])
    c = ig * g_
    o = sig(pre[:, 4 * h :] + c * np.asarray(b)[8 * h : 9 * h])
    want00 = o * np.tanh(c)
    np.testing.assert_allclose(
        np.asarray(yf["md"].value)[:, 0, 0], want00, atol=1e-5
    )
