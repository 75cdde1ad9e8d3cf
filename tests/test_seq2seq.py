"""seq2seq NMT end-to-end: train attention model on a toy
sequence-reversal task, then beam-search generate with the trained
params (reference: the seqToseq demo + generation tests)."""

import jax
import numpy as np
import pytest

from paddle_tpu.core.arg import id_arg
from paddle_tpu.core.config import OptimizationConf
from paddle_tpu.models.text import (
    seq2seq_attention,
    seq2seq_attention_decoder,
)
from paddle_tpu.network import Network
from paddle_tpu.optimizers import create_optimizer

BOS, EOS = 0, 1
V = 12  # 0=bos, 1=eos, 2.. real tokens
H, E = 32, 16


def make_batch(rng, bs, tmax=5):
    src = np.zeros((bs, tmax), np.int32)
    trg_in = np.zeros((bs, tmax + 1), np.int32)
    trg_out = np.zeros((bs, tmax + 1), np.int32)
    src_l = rng.integers(2, tmax + 1, bs).astype(np.int32)
    trg_l = (src_l + 1).astype(np.int32)
    for i in range(bs):
        toks = rng.integers(2, V, src_l[i])
        src[i, : src_l[i]] = toks
        rev = toks[::-1]
        trg_in[i, 0] = BOS
        trg_in[i, 1 : src_l[i] + 1] = rev
        trg_out[i, : src_l[i]] = rev
        trg_out[i, src_l[i]] = EOS
    return src, src_l, trg_in, trg_out, trg_l


@pytest.mark.slow
def test_seq2seq_train_and_generate():
    conf = seq2seq_attention(src_vocab=V, trg_vocab=V, emb_dim=E, hidden=H)
    net = Network(conf)
    params = net.init_params(jax.random.key(0))
    opt = create_optimizer(
        OptimizationConf(learning_method="adam", learning_rate=0.01),
        net.param_confs,
    )
    ost = opt.init_state(params)
    rng = np.random.default_rng(0)

    @jax.jit
    def step(params, ost, src, src_l, ti, to, tl, i):
        feed = {
            "src": id_arg(src, src_l),
            "trg_in": id_arg(ti, tl),
            "trg_out": id_arg(to, tl),
        }
        (loss, _), g = jax.value_and_grad(net.loss_fn, has_aux=True)(
            params, feed
        )
        params, ost = opt.update(g, params, ost, i)
        return params, ost, loss

    first = last = None
    for i in range(250):
        src, src_l, ti, to, tl = make_batch(rng, 32)
        params, ost, loss = step(params, ost, src, src_l, ti, to, tl, i)
        if first is None:
            first = float(loss)
        last = float(loss)
    assert last < 0.15 * first, (first, last)

    # ---- generation with the trained params ----
    src, src_l, ti, to, tl = make_batch(rng, 8)
    enc_outs, _ = net.forward(
        params, {"src": id_arg(src, src_l)}, outputs=["enc", "dec_boot"]
    )
    dec = seq2seq_attention_decoder(
        trg_vocab=V, emb_dim=E, hidden=H, bos_id=BOS, eos_id=EOS,
        beam_size=4, max_length=8,
    )
    seqs, lens, scores = dec.generate(
        params, statics=[enc_outs["enc"]],
        boots={"dec_state": enc_outs["dec_boot"].value},
    )
    seqs, lens = np.asarray(seqs), np.asarray(lens)
    correct = 0
    for i in range(8):
        want = list(src[i, : src_l[i]][::-1]) + [EOS]
        got = seqs[i, 0, : lens[i, 0]].tolist()
        correct += got == want
    assert correct >= 6, f"only {correct}/8 correct"


def test_dsl_simple_attention_in_group():
    """dsl.simple_attention (networks.py:1298) builds the same additive
    attention the seq2seq model inlines; a decoder step using it trains."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu import dsl
    from paddle_tpu.core.arg import id_arg
    from paddle_tpu.core.config import OptimizationConf
    from paddle_tpu.network import Network
    from paddle_tpu.optimizers import create_optimizer

    H, V = 16, 30
    with dsl.model() as g:
        src = dsl.data("src", (1,), is_seq=True, is_ids=True)
        trg_in = dsl.data("trg_in", (1,), is_seq=True, is_ids=True)
        trg_out = dsl.data("trg_out", (1,), is_seq=True, is_ids=True)
        enc = dsl.simple_gru(
            dsl.embedding(src, size=8, vocab_size=V), H
        )
        enc_proj = dsl.fc(enc, size=H, bias=False, name="enc_proj")

        def step(word, enc_s, enc_p):
            emb = dsl.embedding(word, size=8, vocab_size=V)
            prev = dsl.memory("s", size=H)
            ctxv = dsl.simple_attention(enc_s, enc_p, prev, name="att")
            s = dsl.fc(emb, prev, ctxv, size=H, act="tanh", name="s")
            return dsl.fc(s, size=V, act="softmax", name="prob")

        dec = dsl.recurrent_group(
            step,
            [trg_in, dsl.StaticInput(enc), dsl.StaticInput(enc_proj)],
            name="dec",
        )
        dsl.cross_entropy(dec, trg_out, name="cost")
    net = Network(g.conf)
    params = net.init_params(jax.random.key(0))
    opt = create_optimizer(
        OptimizationConf(learning_method="adam", learning_rate=0.02),
        net.param_confs,
    )
    st = opt.init_state(params)
    rng = np.random.default_rng(0)
    B, T = 8, 6
    lens = jnp.full((B,), T, jnp.int32)
    body = rng.integers(2, V, (B, T)).astype(np.int32)
    feed = {
        "src": id_arg(jnp.asarray(body), lens),
        "trg_in": id_arg(jnp.asarray(np.roll(body, 1, 1)), lens),
        "trg_out": id_arg(jnp.asarray(body), lens),
    }

    @jax.jit
    def train(params, st, i):
        (l, _), grads = jax.value_and_grad(net.loss_fn, has_aux=True)(
            params, feed
        )
        return *opt.update(grads, params, st, i), l

    first = None
    for i in range(40):
        params, st, loss = train(params, st, i)
        if i == 0:
            first = float(loss)
    assert float(loss) < first * 0.8, (first, float(loss))
