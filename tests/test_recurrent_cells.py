"""The LSTM and GRU cells (`layers/recurrent.py`, a `lax.scan` over time)
against a plain NumPy float64 step loop written here from the reference's
equations (gserver/layers/LstmLayer.cpp with peepholes, and
GatedRecurrentLayer.cpp), and under bfloat16 activations against float32
weights."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import dsl
from paddle_tpu.core import flags
from paddle_tpu.core.arg import Arg
from paddle_tpu.network import Network

H, D_MULT = 5, {"lstm": 4, "gru": 3}
LENGTHS = {"ragged": [6, 0, 3, 1], "full": [6, 6, 6, 6]}
# bfloat16 keeps 8 bits of mantissa: a 6-step cell of width 5 at O(1)
# inputs lands within 2e-2 of the float32 run (seen 5.3e-3 under the
# bfloat16 policy, 1.5e-3 with the weights left in float32)
BF16_ATOL = 2e-2

_NP_ACTS = {
    "tanh": np.tanh,
    "sigmoid": lambda v: 1.0 / (1.0 + np.exp(-v)),
    "relu": lambda v: np.maximum(v, 0.0),
}


def _net(cell, rev, act="tanh", gate_act="sigmoid", state_act="tanh"):
    with dsl.model() as g:
        x = dsl.data("x", (D_MULT[cell] * H,), is_seq=True)
        if cell == "lstm":
            dsl.lstmemory(x, H, name="rnn", act=act, gate_act=gate_act,
                          state_act=state_act, reversed=rev)
        else:
            dsl.grumemory(x, H, name="rnn", act=act, gate_act=gate_act,
                          reversed=rev)
    return Network(g.conf)


def _random_params(net, seed):
    rng = np.random.default_rng(seed)
    return {k: jnp.asarray(0.5 * rng.standard_normal(tuple(pc.dims)),
                           jnp.float32)
            for k, pc in net.param_confs.items()}


def _feed(cell, lens, seed, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((len(lens), max(lens), D_MULT[cell] * H))
    x = x.astype(np.float32).astype(np.float64)
    return x, {"x": Arg(value=jnp.asarray(x, dtype),
                        seq_lens=jnp.asarray(lens, jnp.int32))}


def _numpy_cells(cell, params, x, lens, rev, act, gate_act, state_act):
    """The step loop in float64, one row at a time; a padded step writes
    zeros and leaves the state alone."""
    p = {k: np.asarray(v, np.float64) for k, v in params.items()}
    act, gate, state = (_NP_ACTS[a] for a in (act, gate_act, state_act))
    y = np.zeros(x.shape[:2] + (H,))
    for b, n in enumerate(lens):
        h, c = np.zeros(H), np.zeros(H)
        for t in (reversed(range(n)) if rev else range(n)):
            if cell == "lstm":
                bias = p["_rnn.wbias"]
                gi, gf, gg, go = np.split(x[b, t] + h @ p["_rnn.w0"]
                                          + bias[:4 * H], 4)
                wci, wcf, wco = np.split(bias[4 * H:], 3)
                i, f = gate(gi + wci * c), gate(gf + wcf * c)
                c = f * c + i * act(gg)
                h = gate(go + wco * c) * state(c)
            else:
                xu, xr, xc = np.split(x[b, t] + p["_rnn.wbias"], 3)
                gur = h @ p["_rnn.w0"]
                u, r = gate(xu + gur[:H]), gate(xr + gur[H:])
                cand = act(xc + (r * h) @ p["_rnn.wc"])
                h = u * h + (1.0 - u) * cand
            y[b, t] = h
    return y


# the three activations are the layer's attrs; the non-default row runs
# what no other test of the cells does
ACTS = {"default": ("tanh", "sigmoid", "tanh"),
        "relu-cand": ("relu", "sigmoid", "sigmoid")}


@pytest.mark.parametrize("cell,rev,lens,acts", [
    *((c, r, n, "default") for c in ("lstm", "gru") for r in (False, True)
      for n in LENGTHS),
    ("lstm", False, "ragged", "relu-cand"),
    ("gru", True, "ragged", "relu-cand"),
])
def test_cells_match_a_float64_step_loop(cell, rev, lens, acts):
    acts = ACTS[acts]
    net = _net(cell, rev, *acts)
    params = _random_params(net, 1)
    x, feed = _feed(cell, LENGTHS[lens], 2)
    with jax.default_matmul_precision("highest"):
        outs, _ = net.forward(params, feed)
    want = _numpy_cells(cell, params, x, LENGTHS[lens], rev, *acts)
    np.testing.assert_allclose(np.asarray(outs["rnn"].value), want,
                               rtol=1e-5, atol=1e-6)


@pytest.fixture
def amp():
    was = flags.get_flag("matmul_precision")
    flags.set_flag("matmul_precision", "bfloat16")
    yield
    flags.set_flag("matmul_precision", was)


@pytest.mark.parametrize("cell", ["lstm", "gru"])
@pytest.mark.parametrize("policy", ["amp", "mixed"])
def test_cells_take_bf16_activations_against_f32_weights(request, cell,
                                                         policy):
    """`amp`: the bfloat16 policy casts the float32 masters for the
    layer; `mixed`: no policy, the float32 weights meet the bfloat16
    activations as they are. Either way the forward keeps within
    BF16_ATOL of the float32 run and the gradient is finite."""
    net = _net(cell, rev=True)
    params = _random_params(net, 3)
    lens = LENGTHS["ragged"]
    _, feed32 = _feed(cell, lens, 4)
    want = np.asarray(net.forward(params, feed32)[0]["rnn"].value)
    if policy == "amp":
        request.getfixturevalue("amp")
    _, feed16 = _feed(cell, lens, 4, jnp.bfloat16)

    def loss(p):
        y = net.forward(p, feed16)[0]["rnn"].value
        return jnp.sum(y.astype(jnp.float32) ** 2), y

    (_, got), grads = jax.value_and_grad(loss, has_aux=True)(params)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               atol=BF16_ATOL, rtol=0)
    for k, g in grads.items():
        assert g.dtype == jnp.float32, k
        assert np.isfinite(np.asarray(g)).all(), k
