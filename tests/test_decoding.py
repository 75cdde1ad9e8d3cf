"""Pinned exactness tests for the decode dispatch-chain work
(ISSUE 18): multi-token dispatch must be BIT-IDENTICAL to the K=1
reference (greedy and beam, ragged tails, early-finish mid-chunk,
hooks included), the host rung's chunked path must match both, and
speculative greedy decoding must reproduce the target's greedy output
token for token no matter how good or bad the draft is. Chain depths
are asserted against the MEASURED counters, never against config
arithmetic alone."""

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu import dsl
from paddle_tpu.beam_search import BeamHooks, BeamSearchDecoder
from paddle_tpu.core.config import ParameterConf
from paddle_tpu.decoding import (
    SpeculativeGreedyDecoder,
    make_draft_decoder,
)
from paddle_tpu.serving.host_decode import host_generate

V, EOS, BOS = 10, 1, 0


def _bigram_step(pname, vocab=V):
    def step(word):
        emb = dsl.embedding(word, size=vocab, vocab_size=vocab,
                            param=ParameterConf(name=pname))
        return dsl.mixed(vocab, [(emb, "identity")], act="softmax",
                         bias=False, name="prob")

    return step


def _rand_table(seed, scale=3.0, vocab=V):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(vocab, vocab)) * scale).astype(np.float32)


def _peaked_table(vocab=V):
    """Chain 0->2->3->eos with every tie broken by the table itself:
    peaks of different height, eos the runner-up of every row, and
    the losers a whole unit apart by column. The best beam finishes
    at t=3 and the last one ([0,2,3,eos]) at t=4, each top-4 cut by a
    margin of ~1.0 — so the step count is a property of the table,
    not of how a runtime's top_k orders equal candidates."""
    t = np.tile(-5.0 - np.arange(vocab, dtype=np.float32), (vocab, 1))
    t[:, EOS] = 0.0
    t[0, 2] = 5.0
    t[2, 3] = 6.0
    t[3, EOS] = 7.0
    return t


def _dec(pname, beam=4, max_len=13, k_tok=1, hooks=None,
         logprob_fn=None):
    return BeamSearchDecoder(
        _bigram_step(pname), n_static=0, bos_id=BOS, eos_id=EOS,
        beam_size=beam, max_length=max_len, hooks=hooks,
        logprob_fn=logprob_fn, tokens_per_dispatch=k_tok,
    )


def _gen(dec, table, b=3, pname=None):
    params = {pname or "bg": jnp.asarray(table)}
    s, l, sc = dec.generate(params, [], batch_size=b)
    return np.asarray(s), np.asarray(l), np.asarray(sc)


class TestMultiTokenDispatch:
    def test_beam_bit_identical_across_k(self):
        """K in {2,4,5,8,32} (divisor, non-divisor/ragged tail, and
        K > max_len) all reproduce the K=1 beam output bitwise —
        seqs, lens, AND scores — with the measured chain depth
        shrinking to ceil(steps/K)."""
        table = _rand_table(0)
        table[:, EOS] = -50.0  # no eos: deterministic full-length walk
        ref = _gen(_dec("bg"), table)
        ref_steps = 13
        for k_tok in (2, 4, 5, 8, 32):
            dec = _dec("bg", k_tok=k_tok)
            s, l, sc = _gen(dec, table)
            assert np.array_equal(s, ref[0]), k_tok
            assert np.array_equal(l, ref[1]), k_tok
            assert np.array_equal(sc, ref[2]), k_tok
            assert dec.last_steps == ref_steps
            assert dec.last_chain_depth == -(-ref_steps // k_tok)

    def test_greedy_token_for_token(self):
        table = _rand_table(3)
        ref = _gen(_dec("bg_g", beam=1), table, pname="bg_g")
        for k_tok in (3, 4, 16):
            s, l, sc = _gen(_dec("bg_g", beam=1, k_tok=k_tok), table,
                            pname="bg_g")
            assert np.array_equal(s, ref[0])
            assert np.array_equal(l, ref[1])
            assert np.array_equal(sc, ref[2])

    def test_early_finish_mid_chunk(self):
        """All beams finish at t=4 < K=8: the guarded substeps past
        the finish must be full no-ops, leaving output AND chain
        depth (1 chunk, not ceil(max_len/K)) exact."""
        table = _peaked_table()
        ref_dec = _dec("bg_p")
        ref = _gen(ref_dec, table, pname="bg_p")
        dec = _dec("bg_p", k_tok=8)
        s, l, sc = _gen(dec, table, pname="bg_p")
        assert np.array_equal(s, ref[0])
        assert np.array_equal(l, ref[1])
        assert np.array_equal(sc, ref[2])
        assert dec.last_steps == ref_dec.last_steps == 4
        assert dec.last_chain_depth == 1
        assert ref_dec.last_chain_depth == 4

    def test_seq2seq_attention_bit_identical(self):
        """The real conditioned decoder (statics + boot memory +
        attention) through the factory's tokens_per_dispatch knob,
        with a ragged tail (max_len=10, K=4)."""
        import jax

        from paddle_tpu.core.arg import id_arg
        from paddle_tpu.models.text import (
            seq2seq_attention,
            seq2seq_attention_decoder,
        )
        from paddle_tpu.network import Network

        vocab, emb, hidden, bs = 32, 8, 8, 2
        conf = seq2seq_attention(src_vocab=vocab, trg_vocab=vocab,
                                 emb_dim=emb, hidden=hidden)
        net = Network(conf)
        params = net.init_params(jax.random.key(0))
        src = np.array([[2, 3, 4, 5], [6, 7, 8, 9]], np.int32)
        lens = np.full((bs,), 4, np.int32)
        outs, _ = net.forward(params, {"src": id_arg(src, lens)},
                              outputs=["enc", "dec_boot"])
        statics = [outs["enc"]]
        boots = {"dec_state": outs["dec_boot"].value}

        def run(k_tok):
            dec = seq2seq_attention_decoder(
                trg_vocab=vocab, emb_dim=emb, hidden=hidden,
                bos_id=BOS, eos_id=EOS, beam_size=4, max_length=10,
                tokens_per_dispatch=k_tok,
            )
            s, l, sc = dec.generate(params, statics=statics,
                                    boots=boots)
            return (np.asarray(s), np.asarray(l), np.asarray(sc), dec)

        s1, l1, sc1, d1 = run(1)
        s4, l4, sc4, d4 = run(4)
        assert np.array_equal(s4, s1)
        assert np.array_equal(l4, l1)
        assert np.array_equal(sc4, sc1)
        assert d4.last_steps == d1.last_steps
        assert d4.last_chain_depth == -(-d1.last_steps // 4)

    def test_hooks_bit_identical_with_same_call_pattern(self):
        """adjust/drop/stop hooks under K=4 produce the K=1 output
        bitwise AND the hooks fire for the same step sequence — the
        cond guard must skip a done substep's pure_callbacks
        entirely, not run them with frozen state."""
        table = _rand_table(11)
        calls = {"adjust": [], "drop": [], "stop": []}

        def mk_hooks():
            def adjust(logp, t):
                calls["adjust"].append(int(t))
                out = logp.copy()
                out[:, :, 4] = -1e30  # forbid token 4 every step
                return out

            def drop(words, scores, t):
                calls["drop"].append(int(t))
                return scores, words == 5  # truncate beams on token 5

            def stop(finished, scores, t):
                calls["stop"].append(int(t))
                return t >= 6  # end the whole generation at step 6

            return BeamHooks(adjust=adjust, drop=drop, stop=stop)

        ref_dec = _dec("bg_h", hooks=mk_hooks())
        ref = _gen(ref_dec, table, pname="bg_h")
        ref_calls = {k: list(v) for k, v in calls.items()}
        for v in calls.values():
            v.clear()
        dec = _dec("bg_h", k_tok=4, hooks=mk_hooks())
        s, l, sc = _gen(dec, table, pname="bg_h")
        assert np.array_equal(s, ref[0])
        assert np.array_equal(l, ref[1])
        assert np.array_equal(sc, ref[2])
        assert calls == ref_calls
        assert dec.last_steps == ref_dec.last_steps
        assert dec.last_chain_depth == -(-ref_dec.last_steps // 4)

    def test_program_cache_keyed_on_k(self):
        """Mutating tokens_per_dispatch after the first generate()
        must build a fresh program, not reuse the K=1 trace."""
        table = _rand_table(0)
        table[:, EOS] = -50.0
        dec = _dec("bg")
        ref = _gen(dec, table)
        assert dec.last_chain_depth == 13
        dec.tokens_per_dispatch = 4
        s, l, sc = _gen(dec, table)
        assert np.array_equal(s, ref[0])
        assert dec.last_chain_depth == 4
        assert len(dec._decode_cache) == 2


class TestHostChunkedRung:
    def test_chunked_matches_per_token_and_jit(self):
        table = _rand_table(5)
        table[:, EOS] = -50.0  # full-length walk: depths deterministic
        params = {"bg_c": jnp.asarray(table)}
        ref_dec = _dec("bg_c")
        s0, l0, sc0 = _gen(ref_dec, table, pname="bg_c")
        sh, lh, sch = host_generate(ref_dec, params, batch_size=3)
        assert np.array_equal(sh, s0)
        assert np.array_equal(lh, l0)
        assert np.allclose(sch, sc0, atol=1e-5)
        assert ref_dec.last_chain_depth == 13  # one dispatch per token
        dec = _dec("bg_c", k_tok=5)
        sc_, lc_, scc = host_generate(dec, params, batch_size=3)
        assert np.array_equal(sc_, s0)
        assert np.array_equal(lc_, l0)
        assert np.allclose(scc, sc0, atol=1e-5)
        assert dec.last_chain_depth == 3  # ceil(13/5) chunk dispatches
        assert dec.last_steps == 13

    def test_chunked_early_finish_stops_dispatching(self):
        table = _peaked_table()
        params = {"bg_cp": jnp.asarray(table)}
        ref = _gen(_dec("bg_cp"), table, pname="bg_cp")
        dec = _dec("bg_cp", k_tok=3)
        s, l, sc = host_generate(dec, params, batch_size=3)
        assert np.array_equal(s, ref[0])
        assert np.array_equal(l, ref[1])
        # finished inside chunk 2 (t=4 of 13): chunks 3.. never run
        assert dec.last_chain_depth == 2

    def test_empty_hooks_object_still_chunks(self):
        """A named-but-empty BeamHooks (the wire-level 'noop' hook)
        carries no host callbacks, so the chunked path stays
        eligible — only real callbacks force per-token stepping."""
        table = _rand_table(5)
        table[:, EOS] = -50.0
        params = {"bg_c": jnp.asarray(table)}
        ref = _gen(_dec("bg_c"), table, pname="bg_c")
        dec = _dec("bg_c", k_tok=5)
        s, _, _ = host_generate(dec, params, batch_size=3,
                                hooks=BeamHooks())
        assert np.array_equal(s, ref[0])
        assert dec.last_chain_depth == 3

    def test_hooks_force_per_token_semantics_pinned(self):
        """A hook-bearing request on a K>1 decoder must take the
        per-token path (hook call pattern untouched by chunking) and
        still match the jitted K>1 program bit-for-bit."""
        table = _rand_table(11)
        params = {"bg_hh": jnp.asarray(table)}
        seen = []

        def adjust(logp, t):
            seen.append(int(t))
            out = logp.copy()
            out[:, :, 4] = -1e30
            return out

        jit_dec = _dec("bg_hh", k_tok=4,
                       hooks=BeamHooks(adjust=adjust))
        ref = _gen(jit_dec, table, pname="bg_hh")
        jit_calls = list(seen)
        seen.clear()
        host_dec = _dec("bg_hh", k_tok=4)
        s, l, sc = host_generate(host_dec, params, batch_size=3,
                                 hooks=BeamHooks(adjust=adjust))
        assert np.array_equal(s, ref[0])
        assert np.array_equal(l, ref[1])
        assert np.allclose(sc, ref[2], atol=1e-5)
        assert seen == jit_calls
        # per-token: one dispatch per executed step, chunking ignored
        assert host_dec.last_chain_depth == jit_dec.last_steps


class TestSpeculativeGreedy:
    def _target(self, max_len=17):
        return _dec("sp_t", beam=1, max_len=max_len)

    def _ref(self, table, max_len=17, b=4):
        return _gen(self._target(max_len), table, b=b, pname="sp_t")

    def test_token_for_token_any_draft_quality(self):
        """Perturbed, garbage, and perfect drafts all yield the
        target's exact greedy tokens — draft quality may only change
        the chain depth, never one token of output."""
        table = _rand_table(7)
        rng = np.random.default_rng(8)
        drafts = {
            "close": table + rng.normal(size=(V, V)).astype(np.float32),
            "garbage": _rand_table(99),
            "exact": table,
        }
        ref = self._ref(table)
        params = {"sp_t": jnp.asarray(table)}
        for name, dt in drafts.items():
            drf = make_draft_decoder(
                _bigram_step(f"sp_d_{name}"), n_static=0, bos_id=BOS,
                eos_id=EOS, max_length=17,
            )
            dparams = {f"sp_d_{name}": jnp.asarray(dt)}
            for k_prop in (3, 4, 8):
                spec = SpeculativeGreedyDecoder(
                    self._target(), drf, propose_k=k_prop
                )
                s, l, sc = spec.generate(params, dparams, batch_size=4)
                assert np.array_equal(s, ref[0]), (name, k_prop)
                assert np.array_equal(l, ref[1]), (name, k_prop)
                assert np.allclose(sc, ref[2], atol=1e-4), \
                    (name, k_prop)
                assert spec.last_chain_depth >= 2

    def test_eos_mid_proposal_truncates_exactly(self):
        """Greedy chain hits eos at t=3 inside an 8-token proposal:
        tokens past the eos must not leak into the output and the
        row finishes exactly like the reference."""
        table = _peaked_table()
        ref = self._ref(table, b=3)
        drf = make_draft_decoder(_bigram_step("sp_dp"), n_static=0,
                                 bos_id=BOS, eos_id=EOS, max_length=17)
        spec = SpeculativeGreedyDecoder(self._target(), drf,
                                        propose_k=8)
        s, l, sc = spec.generate(
            {"sp_t": jnp.asarray(table)},
            {"sp_dp": jnp.asarray(table)}, batch_size=3,
        )
        assert np.array_equal(s, ref[0])
        assert np.array_equal(l, ref[1])
        assert l[0, 0] == 3  # 2, 3, eos: first eos at t=2 -> len 3
        # one propose + one verify round covered the whole sequence
        assert spec.last_chain_depth == 2

    def test_chain_depth_and_accept_rate_measured(self):
        """Self-draft (same table): full agreement, so max_len=16 at
        K=8 is exactly 2 rounds = 4 dispatches, accept rate 1.0 —
        and the reference K=1 walk would have been 16 dispatches."""
        table = _rand_table(2)
        table[:, EOS] = -50.0  # no eos: full-length walk
        ref = self._ref(table, max_len=16)
        assert ref[1][0, 0] == 16
        drf = make_draft_decoder(_bigram_step("sp_ds"), n_static=0,
                                 bos_id=BOS, eos_id=EOS, max_length=16)
        spec = SpeculativeGreedyDecoder(self._target(max_len=16), drf,
                                        propose_k=8)
        s, l, _ = spec.generate(
            {"sp_t": jnp.asarray(table)},
            {"sp_ds": jnp.asarray(table)}, batch_size=4,
        )
        assert np.array_equal(s, ref[0])
        assert spec.last_chain_depth == 4
        assert spec.last_accept_rate == 1.0
        assert spec.last_steps == 16

    def test_rejects_beam_search_decoders(self):
        with pytest.raises(AssertionError):
            SpeculativeGreedyDecoder(
                _dec("sp_b", beam=4), self._target(), propose_k=4
            )

    def test_serving_spec_path(self):
        """GenerationModel(speculative=...) composes with the
        batcher: hook-free requests take the 'spec' path and return
        the reference greedy tokens; the dispatch-key accounting
        carries tokens_per_dispatch."""
        from paddle_tpu.serving.models import GenerationModel
        from paddle_tpu.serving.server import (
            InferenceServer,
            ServeConfig,
        )

        table = _rand_table(7)
        params = {"sp_t": jnp.asarray(table)}
        ref = self._ref(table, b=1)
        tgt = self._target()
        drf = make_draft_decoder(_bigram_step("sp_dsv"), n_static=0,
                                 bos_id=BOS, eos_id=EOS, max_length=17)
        spec = SpeculativeGreedyDecoder(tgt, drf, propose_k=4)
        model = GenerationModel(
            tgt, params, speculative=spec,
            draft_params={"sp_dsv": jnp.asarray(table)},
        )
        assert model.tokens_per_dispatch == 1
        srv = InferenceServer(ServeConfig(max_queue=8, max_batch=1))
        srv.add_model("gen", model)
        try:
            out = srv.submit("gen", [2, 3],
                             deadline_s=120.0).result(timeout=120)
            assert out["path"] == "spec"
            assert out["tokens"] == \
                ref[0][0, 0, :ref[1][0, 0]].tolist()
        finally:
            srv.shutdown(drain=True)


class TestChainMetricPlumbing:
    def test_decoding_package_is_fenced(self):
        """paddle_tpu/decoding joined the jax-import fence: module
        scope must stay importable with jax blocked (serving reaches
        the constructors; tracing imports jax function-locally)."""
        from paddle_tpu.analysis.ast_lint import JAX_FREE_DIRS

        assert "paddle_tpu/decoding" in JAX_FREE_DIRS
