"""Paged KV-cache decode pins (ISSUE 19).

The tentpole claim is EQUALITY, not similarity: generation through the
page pool (bucketed prefill + fused per-token decode) must reproduce
the full-prefix-recompute decode token for token — greedy, beam (same
expansion rule, canonicalized against float near-ties), and
speculative (any draft). The serving engine's continuous batching is
pinned the same way, including the faults-shard invariant: a request
evicted mid-generation and readmitted later resumes BYTE-IDENTICALLY,
because re-prefilling prompt+emitted re-derives exactly the pool state
the eviction threw away.

Chain depths and cache counters are asserted against MEASURED values
(the ISSUE 18 rule), and the committed prefill/decode captures are
re-audited here against their tools/traces/audit_budgets.json policies
— the donation check on the cache-append (pool) buffers included."""

import json
import os

import numpy as np
import pytest

import jax

from paddle_tpu.core.arg import id_arg
from paddle_tpu.decoding.kv_cache import (
    PagedKVCache,
    PagedLM,
    PoolExhausted,
    SpeculativePagedLM,
)
from paddle_tpu.models import lm as lmm
from paddle_tpu.serving.lm_engine import LMEngine, PagedLMModel

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EOS = 1
SPEC = lmm.LMSpec(vocab=128, d_model=64, num_heads=2, num_layers=2)


@pytest.fixture(scope="module")
def params():
    return lmm.lm_init_params(SPEC, jax.random.key(0))


def _prompts(b=3, t0=11, seed=0, spec=SPEC):
    rng = np.random.default_rng(seed)
    ids = rng.integers(2, spec.vocab, (b, t0)).astype(np.int32)
    lens = np.asarray([t0, t0 - 3, t0 - 5], np.int32)[:b]
    return ids, lens


def _plm(params, spec=SPEC, num_pages=64, page_size=4,
         max_pages_per_seq=16, eos_id=EOS):
    cache = PagedKVCache(spec, num_pages=num_pages,
                         page_size=page_size,
                         max_pages_per_seq=max_pages_per_seq)
    return PagedLM(spec, params, cache, eos_id=eos_id)


def _unemitted_eos(params, ids, lens, max_new):
    """An eos id the greedy walk over these prompts never emits, so
    that every request runs its whole `max_new` whatever the
    initialiser drew (the scheduling tests below need requests that
    stay alive; a seed-0 weight that happens to emit EOS at t=1 under
    one PRNG and not another must not decide them). The probe decodes
    with an out-of-vocabulary eos, which can never match."""
    free, _ = lmm.greedy_decode_recompute(
        SPEC, params, ids, lens, max_new, SPEC.vocab
    )
    return int(np.setdiff1d(np.arange(2, SPEC.vocab), free)[0])


class TestFunctionalForward:
    def test_matches_dsl_graph(self, params):
        """lm_forward is the SAME math as the transformer_lm DSL
        graph — the generation programs consume Network-trained
        params unchanged."""
        from paddle_tpu.network import Network

        ids, lens = _prompts()
        net = Network(lmm.transformer_lm(SPEC))
        outs, _ = net.forward(
            params, {"ids": id_arg(ids, lens)}, outputs=["lm_head"]
        )
        ref = np.asarray(outs["lm_head"].value)
        got = np.asarray(lmm.lm_forward(SPEC, params, ids, lens=lens))
        for r, ln in enumerate(lens):
            np.testing.assert_allclose(
                got[r, :ln], ref[r, :ln], rtol=2e-5, atol=2e-5
            )

    def test_decode_chunk_matches_full_forward(self, params):
        """A chunk of n new tokens against the gathered context gives
        the same logits as running the whole sequence through
        lm_forward — intra-chunk causality included."""
        rng = np.random.default_rng(1)
        b, t0, n = 2, 6, 3
        seq = rng.integers(2, SPEC.vocab, (b, t0 + n)).astype(np.int32)
        lens = np.full((b,), t0 + n, np.int32)
        full, ks, vs = lmm.lm_forward(SPEC, params, seq, lens=lens,
                                      with_kv=True)
        s = t0 + n + 2
        ctx_k = np.zeros((SPEC.num_layers, b, s, SPEC.num_heads,
                          SPEC.head_dim), np.float32)
        ctx_v = np.zeros_like(ctx_k)
        ctx_k[:, :, :t0] = np.asarray(ks)[:, :, :t0]
        ctx_v[:, :, :t0] = np.asarray(vs)[:, :, :t0]
        import jax.numpy as jnp

        start = np.full((b,), t0, np.int32)
        logits, nk, nv = lmm.lm_decode_chunk(
            SPEC, params, seq[:, t0:], start, jnp.asarray(ctx_k),
            jnp.asarray(ctx_v),
        )
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full)[:, t0:],
            rtol=2e-5, atol=2e-5,
        )
        np.testing.assert_allclose(
            np.asarray(nk), np.asarray(ks)[:, :, t0:], rtol=1e-6,
            atol=1e-6,
        )


class TestPagedVsRecompute:
    def test_greedy_token_exact(self, params):
        """The headline pin: paged greedy == full-recompute greedy,
        token for token, ragged prompts included — and the chain
        depth is the MEASURED dispatch count."""
        ids, lens = _prompts()
        max_new = 9
        ref_t, ref_s = lmm.greedy_decode_recompute(
            SPEC, params, ids, lens, max_new, EOS
        )
        plm = _plm(params)
        got_t, got_s = plm.generate(ids, lens, max_new)
        np.testing.assert_array_equal(got_t, ref_t)
        np.testing.assert_allclose(got_s, ref_s, rtol=1e-4,
                                   atol=1e-4)
        assert plm.last_chain_depth == max_new  # prefill + 8 decodes
        tl = plm.last_timeline
        assert tl["dispatch_s"] > 0 and tl["device_s"] >= 0

    def test_pool_pages_all_returned(self, params):
        ids, lens = _prompts()
        plm = _plm(params)
        total = plm.cache.free_page_count()
        plm.generate(ids, lens, 6)
        assert plm.cache.free_page_count() == total
        assert plm.cache.cached_prefix_tokens > 0
        assert plm.cache.appended_tokens > 0

    def test_beam_same_beam_sets(self, params):
        """Paged beam search under the SHARED expansion rule equals
        the full-recompute beams. Chunked vs full-width attention
        differ by float reduction order, so near-tied beams may swap
        ranks — the pin canonicalizes each group (sort by rounded
        score, then token tuple) before comparing."""
        ids, lens = _prompts(b=2)
        k, max_new = 3, 7
        ref_t, ref_s = lmm.beam_decode_recompute(
            SPEC, params, ids, lens, k, max_new, EOS
        )
        plm = _plm(params)
        got_t, got_s = plm.beam_generate(ids, lens, k, max_new)
        assert plm.last_chain_depth == max_new

        def canon(toks, scores, g):
            return sorted(
                (round(float(scores[g, j]), 3),
                 tuple(int(x) for x in toks[g, j]))
                for j in range(k)
            )

        for g in range(ids.shape[0]):
            assert canon(got_t, got_s, g) == canon(ref_t, ref_s, g)

    def test_speculative_token_exact_any_draft(self, params):
        """Satellite 1: speculation THROUGH the pool — draft proposes
        into its own pages, target verifies all K positions in one
        chunked dispatch appending to its pages — and the output is
        the target's greedy KV output no matter the draft."""
        ids, lens = _prompts()
        max_new = 10
        ref_t, ref_s = lmm.greedy_decode_recompute(
            SPEC, params, ids, lens, max_new, EOS
        )
        # a BAD draft: different params (worst case for acceptance)
        draft_params = lmm.lm_init_params(SPEC, jax.random.key(7))
        spec_lm = SpeculativePagedLM(
            _plm(params), _plm(draft_params), propose_k=3
        )
        got_t, got_s = spec_lm.generate(ids, lens, max_new)
        np.testing.assert_array_equal(got_t, ref_t)
        np.testing.assert_allclose(got_s, ref_s, rtol=1e-4,
                                   atol=1e-4)
        assert 0.0 < spec_lm.last_accept_rate <= 1.0

    def test_speculative_self_draft_accepts_everything(self, params):
        """Draft == target: every proposal must be accepted and the
        dispatch chain must be SHORTER than one-per-token."""
        ids, lens = _prompts()
        max_new = 9
        spec_lm = SpeculativePagedLM(
            _plm(params), _plm(params), propose_k=3
        )
        got_t, _ = spec_lm.generate(ids, lens, max_new)
        ref_t, _ = lmm.greedy_decode_recompute(
            SPEC, params, ids, lens, max_new, EOS
        )
        np.testing.assert_array_equal(got_t, ref_t)
        assert spec_lm.last_accept_rate == pytest.approx(1.0)
        assert spec_lm.last_chain_depth < max_new


class TestEngine:
    def test_continuous_batching_matches_reference(self, params):
        """Fewer slots than requests: admissions ride between decode
        dispatches and every request still gets the reference
        output."""
        ids, lens = _prompts()
        max_new = 8
        eos = _unemitted_eos(params, ids, lens, max_new)
        ref_t, _ = lmm.greedy_decode_recompute(
            SPEC, params, ids, lens, max_new, eos
        )
        eng = LMEngine(_plm(params, eos_id=eos), slots=2,
                       max_new=max_new)
        rids = [eng.submit(ids[i, :lens[i]]) for i in range(3)]
        eng.run()
        for i, rid in enumerate(rids):
            res = eng.result(rid)
            assert res["finished"]
            np.testing.assert_array_equal(
                np.asarray(res["tokens"], np.int32), ref_t[i]
            )

    def test_pool_exhaustion_auto_evicts(self, params):
        """A pool too small for all requests at once still converges:
        admission evicts the cheapest live request and the evicted
        one re-enters later, byte-identical."""
        ids, lens = _prompts()
        max_new = 8
        eos = _unemitted_eos(params, ids, lens, max_new)
        ref_t, _ = lmm.greedy_decode_recompute(
            SPEC, params, ids, lens, max_new, eos
        )
        # 12 pages: not enough for all three fully-grown + scratch
        plm = _plm(params, num_pages=12, eos_id=eos)
        eng = LMEngine(plm, slots=3, max_new=max_new)
        rids = [eng.submit(ids[i, :lens[i]]) for i in range(3)]
        eng.run()
        assert plm.cache.evictions > 0
        for i, rid in enumerate(rids):
            np.testing.assert_array_equal(
                np.asarray(eng.result(rid)["tokens"], np.int32),
                ref_t[i],
            )

    def test_serving_model_contract(self, params):
        """PagedLMModel packs batch rows through the engine and
        returns the run_batch row dicts the server expects."""
        ids, lens = _prompts()
        model = PagedLMModel(_plm(params), slots=2, max_new=6)
        rows = model.run_batch(ids, lens, None, host=False)
        ref_t, _ = lmm.greedy_decode_recompute(
            SPEC, params, ids, lens, 6, EOS
        )
        assert len(rows) == 3
        for i, row in enumerate(rows):
            assert row["path"] == "paged"
            want = list(ref_t[i])
            while want and want[-1] == EOS:
                want.pop()
            assert row["tokens"] == want
        assert model.recompile_guards


@pytest.mark.faults
class TestEvictionFaults:
    def test_evict_readmit_byte_identical(self, params):
        """Satellite 3: a request evicted MID-GENERATION (pages freed,
        pool state gone) and readmitted later resumes byte-identically
        — re-prefilling prompt+emitted re-derives the evicted pool
        state exactly."""
        ids, lens = _prompts(b=1)
        max_new = 12
        ref = LMEngine(_plm(params), slots=1, max_new=max_new)
        r0 = ref.submit(ids[0, :lens[0]])
        ref.run()
        want = ref.result(r0)

        plm = _plm(params)
        eng = LMEngine(plm, slots=1, max_new=max_new)
        r1 = eng.submit(ids[0, :lens[0]])
        for _ in range(4):  # emit a few tokens, then pull the rug
            eng.step()
        free_before = plm.cache.free_page_count()
        eng.evict(r1, requeue=False)
        assert plm.cache.free_page_count() > free_before
        assert eng.step() == 0  # nothing live while parked
        eng.readmit(r1)
        eng.run()
        got = eng.result(r1)
        assert got["tokens"] == want["tokens"]
        assert got["score"] == pytest.approx(want["score"], rel=1e-4)
        assert got["prefills"] == 2 and want["prefills"] == 1
        assert plm.cache.evictions == 1
        assert eng.reprefilled_tokens > 0
        assert 0.0 < eng.cache_hit_frac < 1.0
        assert eng.prefix_recompute_bytes_saved > 0

    def test_pool_exhausted_without_auto_evict(self, params):
        plm = _plm(params, num_pages=2)
        with pytest.raises(PoolExhausted):
            plm.cache.alloc(5)


class TestCommittedCaptures:
    def test_lm_captures_pass_their_audit_policies(self):
        """The committed prefill/decode captures re-audit clean
        against tools/traces/audit_budgets.json — including the
        donation check on the two cache-append (pool) buffers and
        the no-[T,T] tripwire on the T=1024 flash prefill."""
        from paddle_tpu.analysis.hlo_audit import audit_capture

        budgets = json.load(
            open(os.path.join(REPO, "tools/traces/audit_budgets.json"))
        )
        for stem in ("lm_prefill_t1024_flash", "lm_decode_b4"):
            policy = budgets[stem]
            assert policy["require_donation"]
            assert policy["min_aliased_buffers"] == 2
            assert policy["host_transfer_budget"] == 0
            rep = audit_capture(
                os.path.join(REPO, f"tools/traces/{stem}.hlo.txt.gz"),
                policy,
            )
            assert rep["ok"], rep["checks"]
            don = next(c for c in rep["checks"]
                       if c["name"] == "donation")
            assert don["aliased_buffers"] >= 2
        prefill = budgets["lm_prefill_t1024_flash"]
        assert prefill["forbid_tt_materialization"]
