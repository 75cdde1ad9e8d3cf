"""chip_smoke.py — the main path, end to end, on one TPU chip.

    python chip_smoke.py             # one chip: phases 1-5
    python chip_smoke.py --chips 4   # four chips: the cross-chip path only

One process, the entry points a user would call, synthetic data from a
fixed seed, full widths (ResNet-50 224x224 bs=256; attention NMT vocab
30k hidden 512 bs=256 T=32; beam-4 decode; the paged LM at d=256 behind
the TCP server; the Pallas kernels a default path reaches). It asserts
first that JAX found a TPU and fails otherwise: it never sets or changes
the platform. Every phase is fatal on failure. Each phase prints one
JSON line with its XLA compile seconds (`compile_s`: compilations or
loads from the persistent cache), its warm run seconds (`run_s`) and
what it checked; the LAST line is the result object

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

`--tiny` is a TEST-ONLY size override (tests/test_chip_smoke.py): the
same phases at toy widths. It changes sizes, never the platform check.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import threading
import time

import numpy as np

SEED = 0
# the contract gives the script 1200 s; a phase that wedges (a host
# callback that never returns, a hung dispatch) must end the process
# with a message instead of holding the chip
DEADLINE_S = 1150.0


@dataclasses.dataclass(frozen=True)
class Sizes:
    # phase 1: ResNet-50
    image: tuple = (224, 224, 3)
    classes: int = 1000
    resnet_batch: int = 256
    # phase 2/3: attention NMT
    vocab: int = 30000
    hidden: int = 512
    emb: int = 512
    nmt_batch: int = 256
    nmt_t: int = 32
    steps: int = 5
    # phase 3: beam decode
    beam: int = 4
    max_len: int = 32
    gen_batch: int = 32
    tokens_per_dispatch: int = 8
    # phase 4: paged LM (bench_lm_train's size, flash prefill)
    lm_vocab: int = 2048
    lm_d: int = 256
    lm_heads: int = 4
    lm_layers: int = 2
    lm_page: int = 16
    lm_pages_per_seq: int = 64          # 1024 slots: the flash bucket
    lm_slots: int = 4
    lm_max_new: int = 16
    lm_prompt_lens: tuple = (1000, 100, 700, 120, 900, 90, 520, 70)
    nmt_prompt_lens: tuple = (32, 5, 17, 9, 28, 12, 3, 21)
    # phase 5: SparseUpdater CTR step (bench_ctr_widedeep_sparse)
    ctr_rows: int = 1 << 20
    ctr_dim: int = 64
    ctr_batch: int = 256
    ctr_t: int = 64


FULL = Sizes()
TINY = Sizes(
    image=(32, 32, 3), classes=10, resnet_batch=4,
    vocab=64, hidden=16, emb=16, nmt_batch=4, nmt_t=8, steps=3,
    beam=2, max_len=6, gen_batch=2, tokens_per_dispatch=3,
    lm_vocab=64, lm_d=32, lm_heads=2, lm_layers=1, lm_page=4,
    lm_pages_per_seq=8, lm_slots=2, lm_max_new=4,
    lm_prompt_lens=(20, 5, 18, 7, 27, 3, 12, 9),
    nmt_prompt_lens=(8, 5, 3, 7, 2, 6, 4, 1),
    ctr_rows=1 << 8, ctr_dim=8, ctr_batch=4, ctr_t=4,
)

# tolerances, stated once; each was set from what the chip showed
# (PERF.md section 6, PR 21) with room for a rounding, none for a fault
LOSS_RTOL_4CHIP = 1e-4     # seen 4e-6; one step's descent is 9e-3
FLASH_TOL = 3e-2           # atol and rtol: kernel dots vs f32-exact dense
FUSED_LOSS_RTOL = 2e-3     # fused vs plain ResNet-50, first loss; seen 5e-4
FUSED_KERNEL_RTOL = 1e-2   # bn_act_conv1x1 vs plain jnp, grads too; seen 8e-4
SPARSE_ATOL = 1e-5         # same f32 arithmetic, row for row
RESCORE_RTOL = 1e-3        # beam score vs training graph; seen 1.8e-4
BEAM_SCORE_RTOL = 5e-3     # best beam, K-token vs one-token; seen 2.4e-3


def say(**fields) -> None:
    print(json.dumps(fields), flush=True)


def compiled() -> tuple:
    """(XLA compile seconds, persistent-cache hits, misses) so far,
    from the program's own compile watch
    (`paddle_tpu.core.compile_cache.watch`, on since `setup()`): a
    cache hit is timed as the load it is."""
    from paddle_tpu.obs.metrics import get_registry

    reg = get_registry()
    return (sum(reg.counter("compile.backend_s").snapshot().values()),
            int(reg.counter("compile.cache_hits").get()),
            int(reg.counter("compile.cache_misses").get()))


@contextlib.contextmanager
def phase(name: str):
    """Time one phase; the body fills `out` (its `run_s` and checks)."""
    import jax

    c0, h0, m0 = compiled()
    t0 = time.perf_counter()
    out: dict = {}
    yield out
    stats = jax.devices()[0].memory_stats() or {}
    wall_s = time.perf_counter() - t0
    c1, h1, m1 = compiled()
    compile_s = c1 - c0
    # a phase that times no warm call of its own (each kernel check
    # runs once) reports what its wall holds besides compiling
    out.setdefault("run_s", round(wall_s - compile_s, 3))
    say(
        phase=name,
        wall_s=round(wall_s, 3),
        compile_s=round(compile_s, 3),
        cache_hits=h1 - h0,
        cache_writes=m1 - m0,
        # the allocator's view since the process started; on the v5e
        # it does not count a running program's temporaries (a step's
        # `compiled.memory_analysis()` does: PERF.md has both)
        peak_bytes_in_use=stats.get("peak_bytes_in_use", "not reported"),
        bytes_limit=stats.get("bytes_limit", "not reported"),
        **out,
    )


def setup():
    """Process settings: bf16 compute over f32
    master params, the rbg PRNG, the persistent compile cache."""
    import jax

    from paddle_tpu.core import compile_cache, flags

    flags.set_flag("matmul_precision", "bfloat16")
    jax.config.update("jax_default_prng_impl", "rbg")
    return compile_cache.enable()


def _compiled_text(jitted, *args) -> str:
    return jitted.lower(*args).compile().as_text()


def _kernel_in(text: str, what: str) -> str:
    """On a TPU the program that ran must hold a Mosaic kernel. Off it
    (only tests call a phase there) the kernels run interpreted."""
    from paddle_tpu import ops

    if ops.pallas_interpret():
        return "interpreted"
    assert "tpu_custom_call" in text, (
        f"{what}: no tpu_custom_call in the compiled program — the "
        "Pallas kernel did not run"
    )
    return "tpu_custom_call"


# ------------------------------------------------------------ phase 1
def _train(trainer, feed, steps: int, out: dict) -> None:
    losses = []
    t_warm = None
    for i in range(steps):
        if i == 1:
            t_warm = time.perf_counter()
        losses.append(trainer.train_batch(feed))
    out["run_s"] = round(time.perf_counter() - t_warm, 3)
    out["steps_timed"] = steps - 1
    out["losses"] = [round(x, 5) for x in losses]
    assert all(np.isfinite(losses)), f"non-finite loss: {losses}"
    assert losses[-1] != losses[0], f"loss did not move: {losses}"


def _image_feed(sz: Sizes):
    from paddle_tpu.core.arg import id_arg, non_seq

    rng = np.random.default_rng(SEED)
    return {
        "image": non_seq(rng.standard_normal(
            (sz.resnet_batch, *sz.image)).astype(np.float32)),
        "label": id_arg(rng.integers(
            0, sz.classes, sz.resnet_batch).astype(np.int32)),
    }


def _resnet_opt():
    from paddle_tpu.core.config import OptimizationConf

    return OptimizationConf(learning_method="momentum",
                            learning_rate=0.001, momentum=0.9)


def phase_train_image(sz: Sizes, out: dict) -> None:
    """ResNet-50, the plain graph, through trainer.SGD."""
    import jax

    from paddle_tpu.models import resnet
    from paddle_tpu.trainer import SGD

    conf = resnet(depth=50, image_shape=sz.image, num_classes=sz.classes)
    trainer = SGD(conf, _resnet_opt(), seed=SEED + 1)
    feed = jax.device_put(_image_feed(sz))
    out["batch"] = sz.resnet_batch
    _train(trainer, feed, sz.steps, out)


# ------------------------------------------------------------ phase 2
def _nmt_conf(sz: Sizes):
    from paddle_tpu.models import seq2seq_attention

    return seq2seq_attention(src_vocab=sz.vocab, trg_vocab=sz.vocab,
                             emb_dim=sz.emb, hidden=sz.hidden)


def _nmt_feed(sz: Sizes):
    """Ragged batch: lengths from T/2 to T, one row at the full T."""
    from paddle_tpu.core.arg import id_arg

    rng = np.random.default_rng(SEED)
    b, t = sz.nmt_batch, sz.nmt_t
    lens = rng.integers(max(t // 2, 1), t + 1, b).astype(np.int32)
    lens[0] = t

    def ids():
        return id_arg(rng.integers(2, sz.vocab, (b, t)).astype(np.int32),
                      lens)

    return {"src": ids(), "trg_in": ids(), "trg_out": ids()}


def _nmt_opt():
    from paddle_tpu.core.config import OptimizationConf

    return OptimizationConf(learning_method="adam", learning_rate=1e-3)


def phase_train_sequence(sz: Sizes, out: dict):
    """Attention NMT: the recurrent_group scan every RNN config uses.
    Returns the trained parameters for the phases that decode."""
    import jax

    from paddle_tpu.trainer import SGD

    trainer = SGD(_nmt_conf(sz), _nmt_opt(), seed=SEED + 1)
    feed = jax.device_put(_nmt_feed(sz))
    out["batch"], out["t"] = sz.nmt_batch, sz.nmt_t
    _train(trainer, feed, sz.steps, out)
    return trainer.params


# ------------------------------------------------------------ phase 3
def _nmt_encoder(sz: Sizes, params):
    """encode(ids [B,T], lens [B]) -> (statics, boots) for the decoder:
    the NMT encoder forward, jitted."""
    import jax

    from paddle_tpu.core.arg import id_arg
    from paddle_tpu.network import Network

    net = Network(_nmt_conf(sz))

    @jax.jit
    def forward(params, ids, lens):
        outs, _ = net.forward(params, {"src": id_arg(ids, lens)},
                              outputs=["enc", "dec_boot"])
        return outs["enc"], outs["dec_boot"].value

    def encode(ids, lens):
        enc, boot = forward(params, np.asarray(ids, np.int32),
                            np.asarray(lens, np.int32))
        return [enc], {"dec_state": boot}

    return encode


def _nmt_decoder(sz: Sizes, k_tok: int = 1, hooks=None):
    from paddle_tpu.models import seq2seq_attention_decoder

    dec = seq2seq_attention_decoder(
        trg_vocab=sz.vocab, emb_dim=sz.emb, hidden=sz.hidden, bos_id=0,
        eos_id=1, beam_size=sz.beam, max_length=sz.max_len,
        tokens_per_dispatch=k_tok,
    )
    if hooks is not None:
        dec.hooks = hooks
    return dec


def _teacher_forced_scorer(sz: Sizes, params, src, src_lens):
    """score(seqs [B, L], lens [B]) -> log p(seqs | src) under the
    TRAINING graph (recurrent_group scan, hoisted projection): an
    independent path to the number the decoder reports for its own
    output. `lens` counts the eos."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core.arg import id_arg
    from paddle_tpu.network import Network

    net = Network(_nmt_conf(sz))

    @jax.jit
    def logp(params, trg_in, trg_out, lens):
        outs, _ = net.forward(params, {
            "src": id_arg(src, src_lens),
            "trg_in": id_arg(trg_in, lens),
            "trg_out": id_arg(trg_out, lens),
        }, outputs=["dec_prob"])
        prob = outs["dec_prob"].value.astype(jnp.float32)
        picked = jnp.take_along_axis(prob, trg_out[..., None], axis=-1)
        live = jnp.arange(trg_out.shape[1])[None, :] < lens[:, None]
        return jnp.sum(jnp.where(live, jnp.log(picked[..., 0]), 0.0), axis=1)

    def score(seqs, lens):
        bos = np.zeros((seqs.shape[0], 1), np.int32)
        trg_in = np.concatenate([bos, seqs[:, :-1]], axis=1)
        return np.asarray(logp(params, trg_in, seqs, lens))

    return score


def _k_token_equals_one_token_in_float32(sz: Sizes, params, src, src_lens):
    """Fresh programs traced with float32 activations: the K-token
    search must return exactly what the one-token search returns."""
    from paddle_tpu.core import flags

    amp = flags.get_flag("matmul_precision")
    flags.set_flag("matmul_precision", "default")
    try:
        statics, boots = _nmt_encoder(sz, params)(src, src_lens)
        one, k_tok = (
            [np.asarray(x) for x in _nmt_decoder(sz, k_tok=k).generate(
                params, statics=statics, boots=boots)]
            for k in (1, sz.tokens_per_dispatch))
    finally:
        flags.set_flag("matmul_precision", amp)
    for what, a, b in zip(("tokens", "lengths", "scores"), one, k_tok):
        assert np.array_equal(a, b), (
            f"float32: K-token and one-token decode differ in {what}")


def phase_generate(sz: Sizes, params, out: dict):
    """Beam decode on the phase-2 weights: the jitted while-loop once
    hook-free, once K tokens per dispatch, once with a host callback
    inside the jitted loop. Returns the K-token decoder for serving.

    Outputs equal: in float32 the K-token program's beams, lengths
    and scores must EQUAL the one-token program's, on the chip as in
    the tests. Under bf16 AMP, which is what serves, they cannot be
    held to that: the chip rounds the K-token program (a scan of
    conds) unlike the one-token loop — its scores sit 2e-4 from the
    training graph's where the other two programs sit 3e-7 from it —
    and on weights five steps from random that flips beams between
    candidates a part in 400 apart (4 of 32 rows stay identical;
    PERF.md section 6, PR 21, has the runs). There, what such a flip
    implies is asserted: every program's best-beam score is the
    training graph's log-prob of its own tokens, the programs' best
    scores agree row by row, and under the training graph neither
    program's best beam is worse than the other's. Identical rows are
    reported."""
    from paddle_tpu.beam_search import BeamHooks

    rng = np.random.default_rng(SEED + 2)
    b = sz.gen_batch
    src = rng.integers(2, sz.vocab, (b, sz.nmt_t)).astype(np.int32)
    src_lens = rng.integers(1, sz.nmt_t + 1, b).astype(np.int32)
    statics, boots = _nmt_encoder(sz, params)(src, src_lens)
    rescore = _teacher_forced_scorer(sz, params, src, src_lens)

    def run(dec):
        """-> best-beam tokens [B, L], their scores [B], and the
        training graph's log-prob of those tokens [B]."""
        s, ln, sc = dec.generate(params, statics=statics, boots=boots)
        s, ln, sc = np.asarray(s), np.asarray(ln), np.asarray(sc)
        assert s.shape == (b, sz.beam, sz.max_len), s.shape
        assert np.all(np.isfinite(sc[:, 0])), "best-beam score not finite"
        ref = rescore(s[:, 0], ln[:, 0])
        np.testing.assert_allclose(sc[:, 0], ref, rtol=RESCORE_RTOL,
                                   err_msg="decoder score vs training "
                                           "graph on the same tokens")
        return s[:, 0], sc[:, 0], ref

    def same_search(got, want, what):
        """-> rows whose best beams are the same tokens; fails unless
        the two searches found beams that score the same."""
        for i, of in ((1, "the decoder's own score"),
                      (2, "the training graph's log-prob")):
            np.testing.assert_allclose(
                got[i], want[i], rtol=BEAM_SCORE_RTOL,
                err_msg=f"{what} vs the one-token program: best beams "
                        f"differ in {of}")
        return int(np.sum(np.all(got[0] == want[0], axis=1)))

    plain = _nmt_decoder(sz)
    one = run(plain)
    t0 = time.perf_counter()
    again = plain.generate(params, statics=statics, boots=boots)
    out["run_s"] = round(time.perf_counter() - t0, 3)
    assert np.array_equal(one[0], np.asarray(again[0])[:, 0]), (
        "one program, one input, two answers")

    chunked = _nmt_decoder(sz, k_tok=sz.tokens_per_dispatch)
    rows_k = same_search(run(chunked), one, "K tokens per dispatch")
    assert chunked.last_chain_depth == -(
        -chunked.last_steps // sz.tokens_per_dispatch)
    _k_token_equals_one_token_in_float32(sz, params, src, src_lens)

    # ROADMAP Design 5's open question, answered by a run: a callback
    # that changes nothing must be CALLED once per step from inside the
    # jitted loop, and the decode must still be right
    calls = []

    def adjust(logp, t):
        calls.append(int(t))
        return logp

    hooked = _nmt_decoder(sz, hooks=BeamHooks(adjust=adjust))
    rows_h = same_search(run(hooked), one, "host callback in the loop")
    assert len(calls) == hooked.last_steps, (len(calls), hooked.last_steps)

    out.update(
        rescore_rtol=RESCORE_RTOL, beam_score_rtol=BEAM_SCORE_RTOL,
        steps=[plain.last_steps, chunked.last_steps, hooked.last_steps],
        chain_depth_k1=plain.last_chain_depth,
        chain_depth_k=chunked.last_chain_depth,
        tokens_per_dispatch=sz.tokens_per_dispatch,
        rows_identical_k_vs_k1=f"{rows_k}/{b}",
        rows_identical_hook_vs_k1=f"{rows_h}/{b}",
        float32_k_equals_k1=True,
        host_callbacks_in_jitted_loop=True,
        callback_calls=len(calls),
    )
    return chunked


# ------------------------------------------------------------ phase 4
def _lm(sz: Sizes):
    import jax

    from paddle_tpu.decoding.kv_cache import PagedKVCache, PagedLM
    from paddle_tpu.models.lm import LMSpec, lm_init_params

    spec = LMSpec(vocab=sz.lm_vocab, d_model=sz.lm_d,
                  num_heads=sz.lm_heads, num_layers=sz.lm_layers,
                  attn_impl="flash")
    params = lm_init_params(spec, jax.random.key(SEED))
    cache = PagedKVCache(
        spec, num_pages=(sz.lm_slots + 1) * sz.lm_pages_per_seq,
        page_size=sz.lm_page, max_pages_per_seq=sz.lm_pages_per_seq,
    )
    return spec, params, PagedLM(spec, params, cache, eos_id=1)


def _until_eos(tokens, eos: int) -> list:
    tokens = [int(t) for t in tokens]
    return tokens[:tokens.index(eos)] if eos in tokens else tokens


def phase_serve(sz: Sizes, nmt_params, decoder, out: dict) -> None:
    """InferenceServer + TCP front end in this process: the phase-3
    generation model and the paged LM (flash prefill), 8 requests of
    mixed length each, answers checked, drained shutdown."""
    from paddle_tpu.models import lm as lmm
    from paddle_tpu.serving.lm_engine import PagedLMModel
    from paddle_tpu.serving.models import GenerationModel
    from paddle_tpu.serving.server import InferenceServer, ServeConfig
    from paddle_tpu.serving.tcp import ServeClient, ServingTCPServer

    spec, lm_params, plm = _lm(sz)
    encode = _nmt_encoder(sz, nmt_params)
    slots = sz.lm_page * sz.lm_pages_per_seq
    server = InferenceServer(ServeConfig(
        max_batch=4, buckets=(sz.nmt_t, slots),
        # a failed jitted dispatch has to fail the request here, not
        # be answered from the host rung
        host_fallback=False,
    ))
    server.add_model("nmt", GenerationModel(decoder, nmt_params,
                                            encode=encode))
    server.add_model("lm", PagedLMModel(plm, slots=sz.lm_slots,
                                        max_new=sz.lm_max_new))
    tcp = ServingTCPServer(server, port=0)
    rng = np.random.default_rng(SEED + 3)
    nmt_reqs = [rng.integers(2, sz.vocab, n).astype(np.int32)
                for n in sz.nmt_prompt_lens]
    lm_reqs = [rng.integers(2, sz.lm_vocab, n).astype(np.int32)
               for n in sz.lm_prompt_lens]
    answers = {"nmt": [], "lm": []}
    run_s, warm, compiled_for = 0.0, 0, set()
    try:
        with ServeClient(f"127.0.0.1:{tcp.port}") as client:
            for name, reqs in (("nmt", nmt_reqs), ("lm", lm_reqs)):
                for i, ids in enumerate(reqs):
                    t0 = time.perf_counter()
                    # the first request of a shape pays its compile:
                    # the deadline covers it
                    resp = client.call(name, ids.tolist(),
                                       deadline_ms=900_000, timeout=900)
                    dt = time.perf_counter() - t0
                    assert resp.get("ok"), (name, i, resp)
                    answers[name].append(resp)
                    shape = (name, plm.cache.bucket_for(len(ids))
                             if name == "lm" else sz.nmt_t)
                    if shape in compiled_for:
                        run_s, warm = run_s + dt, warm + 1
                    compiled_for.add(shape)
    finally:
        tcp.stop_accepting()
        server.shutdown(drain=True)
        tcp.stop(drain=True)
    out["run_s"] = round(run_s, 3)
    out["warm_requests"] = warm
    out["requests"] = {k: len(v) for k, v in answers.items()}

    # NMT answers: the best beam of the same decoder called directly
    for ids, resp in zip(nmt_reqs, answers["nmt"]):
        assert resp["path"] == "jit", resp
        padded = np.zeros((1, sz.nmt_t), np.int32)
        padded[0, :len(ids)] = ids
        statics, boots = encode(padded, np.asarray([len(ids)], np.int32))
        seqs, lens, _ = decoder.generate(nmt_params, statics=statics,
                                         boots=boots)
        want = np.asarray(seqs)[0, 0, :int(np.asarray(lens)[0, 0])]
        assert resp["tokens"] == want.tolist(), (resp["tokens"], want)

    # LM answers: greedy full-recompute through DENSE attention on the
    # same weights — independent of the paged cache and of the kernel
    dense = dataclasses.replace(spec, attn_impl="dense")
    t_max = max(sz.lm_prompt_lens)
    ids = np.zeros((len(lm_reqs), t_max), np.int32)
    for i, r in enumerate(lm_reqs):
        ids[i, :len(r)] = r
    ref, _ = lmm.greedy_decode_recompute(
        dense, lm_params, ids, np.asarray(sz.lm_prompt_lens, np.int32),
        sz.lm_max_new, plm.eos_id,
    )
    for resp, want in zip(answers["lm"], ref):
        assert resp["path"] == "paged", resp
        assert resp["tokens"] == _until_eos(want, plm.eos_id), (
            f"LM tokens differ from greedy_decode_recompute: "
            f"{resp['tokens']} vs {want}")
    out["lm_token_exact"] = f"{len(lm_reqs)}/{len(lm_reqs)}"
    out["lm_prefill_buckets"] = sorted(
        {plm.cache.bucket_for(n) for n in sz.lm_prompt_lens})

    # the prefill program the long requests ran holds the flash kernel
    import jax.numpy as jnp

    pool_k, pool_v = plm.cache.ensure_pool()
    n_pages = sz.lm_pages_per_seq
    text = _compiled_text(
        plm._prefill_program(1, slots), plm.params, pool_k, pool_v,
        jnp.zeros((1, slots), jnp.int32), jnp.full((1,), slots, jnp.int32),
        jnp.arange(n_pages, dtype=jnp.int32).reshape(1, n_pages),
    )
    out["lm_prefill_kernel"] = _kernel_in(text, "LM flash prefill")


# ------------------------------------------------------------ phase 5
def _check_flash(sz: Sizes) -> dict:
    """ring.flash_dense_attention (Pallas on a TPU) against
    ring.dense_attention at the LM's prefill shape, ragged lengths."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import ring

    t = sz.lm_page * sz.lm_pages_per_seq
    hd = sz.lm_d // sz.lm_heads
    rng = np.random.default_rng(SEED + 4)
    q, k, v = (jnp.asarray(rng.standard_normal((2, t, sz.lm_heads, hd)),
                           jnp.float32) for _ in range(3))
    lens = jnp.asarray([t, max(t // 2 + 3, 1)], jnp.int32)
    flash = jax.jit(lambda q, k, v, n: ring.flash_dense_attention(
        q, k, v, causal=True, kv_len=n))
    kind = _kernel_in(_compiled_text(flash, q, k, v, lens),
                      "ring.flash_dense_attention")
    got = np.asarray(flash(q, k, v, lens))
    with jax.default_matmul_precision("highest"):  # the yardstick
        want = np.asarray(jax.jit(lambda q, k, v, n: ring.dense_attention(
            q, k, v, causal=True, kv_len=n))(q, k, v, lens))
    for row, n in enumerate(np.asarray(lens)):
        # padded QUERY rows are garbage by contract; the layer zeroes them
        np.testing.assert_allclose(got[row, :n], want[row, :n],
                                   atol=FLASH_TOL, rtol=FLASH_TOL)
    return {"kernel": kind, "shape": [2, t, sz.lm_heads, hd],
            "atol_rtol": FLASH_TOL}


def _check_sparse(sz: Sizes) -> dict:
    """One wide&deep CTR step as bench_ctr_widedeep_sparse builds it:
    gather from the placed table, tower fwd+bwd, then SparseUpdater
    writes the touched rows in place — against sparse_apply, the plain
    jnp form of the same update."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core import compile_cache
    from paddle_tpu.parallel.sparse import SparseUpdater, sparse_apply

    v, d, bs, t = sz.ctr_rows, sz.ctr_dim, sz.ctr_batch, sz.ctr_t
    rng = np.random.default_rng(SEED + 5)
    table0 = (rng.standard_normal((v, d)) * 0.01).astype(np.float32)
    mom0 = np.zeros((v, d), np.float32)
    w = jnp.asarray(rng.standard_normal((d, 2)) * 0.05, jnp.float32)
    ids = jnp.asarray(rng.integers(0, v, (bs, t)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, 2, bs), jnp.int32)

    def upd(p, g, m):
        m2 = 0.9 * m + g
        return p - 0.01 * m2, m2

    def row_grads(rows):
        def loss(rows):
            logp = jax.nn.log_softmax(jnp.mean(rows, axis=1) @ w)
            return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))

        return jax.grad(loss)(rows).reshape(bs * t, d)

    updater = SparseUpdater(upd)
    table, mom = updater.place(table0), updater.place(mom0)
    # a program fed a placed table is compiled for that table's layout,
    # and such programs stay out of the persistent cache
    # (parallel/sparse._compile_pinned says why)
    with compile_cache.bypassed():
        grows = jax.jit(lambda tbl: row_grads(
            tbl[ids.reshape(-1), 0, :].reshape(bs, t, d)))(table)
    table, (mom,) = updater(table, ids, grows, (mom,))
    (step,) = updater._steps.values()  # the compiled program that ran
    kind = _kernel_in(step.as_text(), "SparseUpdater")
    want_t, (want_m,) = jax.jit(
        lambda p, m, g: sparse_apply(upd, p, ids, g, state=(m,))
    )(jnp.asarray(table0), jnp.asarray(mom0), grows)
    np.testing.assert_allclose(updater.unplace(table), np.asarray(want_t),
                               atol=SPARSE_ATOL, rtol=0)
    np.testing.assert_allclose(updater.unplace(mom), np.asarray(want_m),
                               atol=SPARSE_ATOL, rtol=0)
    touched = int(np.unique(np.asarray(ids)).size)
    assert np.abs(np.asarray(want_m)).sum() > 0, "no row was updated"
    return {"kernel": kind, "rows": v, "touched_rows": touched,
            "atol": SPARSE_ATOL}


def _fused_params_from_plain(fused_params: dict, plain: dict) -> dict:
    """resnet(fused=True) names its bottleneck weights anew; the math is
    the plain graph's, so the plain weights map over by name."""
    import jax.numpy as jnp

    renames = {
        "_a.bng": "_a_bn.w0", "_a.bnb": "_a_bn.wbias",
        "_tail.w0": "_c.w0",
        "_tail.bnig": "_b_bn.w0", "_tail.bnib": "_b_bn.wbias",
        "_tail.bnog": "_c_bn.w0", "_tail.bnob": "_c_bn.wbias",
    }
    out = {}
    for k, like in fused_params.items():
        src = k
        if k not in plain:
            suffix = next(s for s in renames if k.endswith(s))
            src = k[:-len(suffix)] + renames[suffix]
        # a copy: the plain trainer donates its buffers to its step
        out[k] = jnp.array(plain[src], copy=True).reshape(like.shape)
    return out


def _check_fused_resnet(sz: Sizes) -> dict:
    """One train step of resnet(fused=True) — bn_act_conv1x1 forward and
    backward inside the step program — against the plain graph on the
    same weights and batch: the kernels are in the program that ran and
    the two first-step losses agree. That holds the forward kernel;
    `_check_fused_kernel` holds the gradients."""
    import jax

    from paddle_tpu.models import resnet
    from paddle_tpu.network import Network
    from paddle_tpu.trainer import SGD

    feed = jax.device_put(_image_feed(sz))
    plain_conf = resnet(depth=50, image_shape=sz.image,
                        num_classes=sz.classes)
    fused_conf = resnet(depth=50, image_shape=sz.image,
                        num_classes=sz.classes, fused=True)
    plain_params = Network(plain_conf).init_params(jax.random.key(SEED))
    fused_params = _fused_params_from_plain(
        Network(fused_conf).init_params(jax.random.key(SEED)), plain_params)
    # same program as phase 1: the persistent cache hands it back
    loss_plain = SGD(plain_conf, _resnet_opt(), seed=SEED + 1,
                     params=plain_params).train_batch(feed)
    fused = SGD(fused_conf, _resnet_opt(), seed=SEED + 1,
                params=fused_params)
    run, text = fused.step_fn.aot(
        fused.params, fused.opt_state, fused.state, feed, 0,
        jax.random.key(SEED))
    kind = _kernel_in(text, "resnet(fused=True) train step")
    health = np.asarray(run()[3])  # watchdog step: [loss, all_finite]
    loss_fused = float(health[0])
    assert health[1] == 1.0 and np.isfinite(loss_fused), health
    assert abs(loss_fused - loss_plain) <= FUSED_LOSS_RTOL * loss_plain, (
        f"fused ResNet-50 loss {loss_fused} vs plain {loss_plain}"
    )
    return {"kernel": kind, "loss_fused": round(loss_fused, 5),
            "loss_plain": round(loss_plain, 5), "rtol": FUSED_LOSS_RTOL}


def _check_fused_kernel(sz: Sizes) -> list:
    """bn_act_conv1x1 alone, forward and backward (its custom VJP: two
    more kernels), at the three sites where resnet(fused=True) calls
    it, against the plain jnp chain it replaces. Every output and
    every gradient is compared: a loss cannot see a wrong gradient,
    and a bf16 ResNet's gradient at random weights is rounding in all
    but its norm (plain bf16 against plain f32 differ by 130% of it),
    so the whole step cannot either."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_fused import bn_act_conv1x1

    def plain(u, scale, shift, w):
        z = jnp.maximum(u.astype(jnp.float32) * scale + shift, 0.0)
        y = jnp.dot(z.astype(u.dtype), w.astype(u.dtype),
                    preferred_element_type=jnp.float32)
        return y.astype(u.dtype), jnp.sum(y, 0), jnp.sum(y * y, 0)

    def outputs_and_grads(f):
        def run(args, cotangents):
            out, vjp = jax.vjp(f, *args)
            return (*out, *vjp(cotangents))

        return run

    @jax.jit
    def rel_errors(got, want):
        """|got - want| / |want| of each output, flattened (L2)."""
        def f32(x):
            return x.astype(jnp.float32)

        return [jnp.linalg.norm(f32(g) - f32(w)) / jnp.linalg.norm(f32(w))
                for g, w in zip(got, want)]

    normal = jax.random.normal
    sites = []
    rows = sz.resnet_batch * (sz.image[0] // 4) * (sz.image[1] // 4)
    for n, cin, cout in ((rows, 64, 256), (rows // 16, 256, 1024),
                         (rows // 64, 512, 2048)):
        k = jax.random.split(jax.random.key(SEED + 6), 7)
        args = (normal(k[0], (n, cin), jnp.bfloat16),
                jax.random.uniform(k[1], (cin,), minval=0.5, maxval=1.5),
                0.1 * normal(k[2], (cin,)),
                normal(k[3], (cin, cout)) / np.sqrt(cin))
        cotangents = (normal(k[4], (n, cout), jnp.bfloat16),
                      normal(k[5], (cout,)), 0.01 * normal(k[6], (cout,)))
        kernel = jax.jit(outputs_and_grads(bn_act_conv1x1)).lower(
            args, cotangents).compile()
        errors = rel_errors(
            kernel(args, cotangents),
            jax.jit(outputs_and_grads(plain))(args, cotangents))
        errors = dict(zip(
            ("y", "sum", "sum_sq", "du", "dscale", "dshift", "dw"),
            (round(float(e), 6) for e in errors)))
        assert max(errors.values()) <= FUSED_KERNEL_RTOL, (
            f"bn_act_conv1x1 vs plain jnp at {(n, cin, cout)}: {errors}")
        sites.append({"shape": [n, cin, cout], "errors": errors,
                      "kernel": _kernel_in(kernel.as_text(),
                                           "bn_act_conv1x1")})
    return sites


def phase_kernels(sz: Sizes, out: dict) -> None:
    """Every Pallas kernel a default path reaches on a TPU really ran:
    it is in the compiled text of the program that ran, and its output
    agrees with the plain jnp path. (pallas_rnn sits behind a
    deprecated flag and is left out.)"""
    out["flash_attention"] = _check_flash(sz)
    out["sparse_updater"] = _check_sparse(sz)
    out["bn_act_conv1x1"] = {"sites": _check_fused_kernel(sz),
                             "resnet_step": _check_fused_resnet(sz)}


# ------------------------------------------------------- four chips
def run_four_chips(sz: Sizes) -> None:
    """The cross-chip path and what it is compared with, nothing else:
    the NMT step data-parallel over all four devices against the
    one-device run of the same seed, the collectives and placements
    that make it real, and the repo's own 1x2x2 dry run on the chips."""
    import jax

    import __graft_entry__ as graft
    from paddle_tpu.core.mesh import make_mesh
    from paddle_tpu.parallel.dp import assert_collectives, shard_batch
    from paddle_tpu.trainer import SGD

    steps = 3
    feed = _nmt_feed(sz)
    with phase("nmt_one_device") as out:
        one = SGD(_nmt_conf(sz), _nmt_opt(), seed=SEED + 1)
        placed = jax.device_put(feed)
        one_losses = [one.train_batch(placed) for _ in range(steps)]
        out["losses"] = one_losses
    with phase("nmt_four_devices") as out:
        mesh = make_mesh({"data": 4})
        four = SGD(_nmt_conf(sz), _nmt_opt(), seed=SEED + 1, mesh=mesh)
        # every parameter lives on all four chips, not on the first
        shard_bytes = {}
        for name, p in four.params.items():
            devs = {s.device for s in p.addressable_shards}
            assert len(devs) == 4, f"{name} lives on {devs}"
            shard_bytes[name] = p.addressable_shards[0].data.nbytes
        # the batch is split over four distinct devices
        src = shard_batch(feed, mesh)["src"].ids
        devs = {s.device for s in src.addressable_shards}
        rows = {s.data.shape[0] for s in src.addressable_shards}
        assert len(devs) == 4 and rows == {sz.nmt_batch // 4}, (devs, rows)
        _run, hlo = four.step_fn.aot(
            four.params, four.opt_state, four.state, feed, 0,
            jax.random.key(SEED))
        out["collectives"] = assert_collectives(
            hlo, "NMT dp4 train step", require=["all-reduce"])
        four_losses = [four.train_batch(feed) for _ in range(steps)]
        out["losses"] = four_losses
        out["batch_rows_per_device"] = sz.nmt_batch // 4
        out["param_shard_bytes_per_device"] = shard_bytes
    assert all(np.isfinite(one_losses + four_losses))
    np.testing.assert_allclose(four_losses, one_losses,
                               rtol=LOSS_RTOL_4CHIP)
    say(check="one_chip_vs_four_chip_losses", one=one_losses,
        four=four_losses, rtol=LOSS_RTOL_4CHIP)
    with phase("dryrun_multichip_1x2x2") as out:
        graft.dryrun_multichip(4)
        out["checked"] = ("sharded embedding, ring + ulysses attention, "
                          "MoE, pipeline: shards shrink, collectives found")


# ---------------------------------------------------------------- main
def _watchdog():
    def expire():
        print(f"chip_smoke: no result after {DEADLINE_S:.0f} s — a phase "
              "is wedged; giving up", file=sys.stderr, flush=True)
        os._exit(3)

    t = threading.Timer(DEADLINE_S, expire)
    t.daemon = True
    t.start()


def run(chips: int, sz: Sizes) -> None:
    """Every phase, in order, on whatever devices JAX has. `main` has
    checked by now that they are TPU chips."""
    import gc

    import jax
    import jaxlib

    devices = jax.devices()
    cache_dir = setup()
    say(device=devices[0].device_kind, count=len(devices),
        jax=jax.__version__, jaxlib=jaxlib.__version__,
        compile_cache=cache_dir,
        cache_entries_at_start=len(os.listdir(cache_dir))
        if os.path.isdir(cache_dir) else 0,
        sizes="full" if sz == FULL else "test-only override")
    if chips == 4:
        run_four_chips(sz)
        return
    with phase("1_train_image") as out:
        phase_train_image(sz, out)
    with phase("2_train_sequence") as out:
        nmt_params = phase_train_sequence(sz, out)
    with phase("3_generate") as out:
        decoder = phase_generate(sz, nmt_params, out)
    with phase("4_serve") as out:
        phase_serve(sz, nmt_params, decoder, out)
    # the fused ResNet-50 step needs 14 of the chip's 16 GB: nothing
    # the earlier phases left on the device may stay
    del nmt_params, decoder
    gc.collect()
    with phase("5_kernels") as out:
        phase_kernels(sz, out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the cross-chip path, on four chips")
    ap.add_argument("--tiny", action="store_true",
                    help="TEST-ONLY size override: toy widths, same "
                         "phases, same platform check")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found platform {devices[0].platform!r} "
              f"({devices[0].device_kind}), not a TPU; this script runs "
              "on the chip only and does nothing elsewhere",
              file=sys.stderr)
        return 2
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    _watchdog()
    run(args.chips, TINY if args.tiny else FULL)
    say(ok=True, device={"platform": devices[0].platform,
                         "kind": devices[0].device_kind,
                         "count": len(devices)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
