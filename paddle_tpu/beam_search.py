"""Beam-search sequence generation.

Reference: RecurrentGradientMachine::generateSequence + beamSearch
(gserver/gradientmachines/RecurrentGradientMachine.h:307,309, .cpp) and
the SWIG SequenceGenerator (api/SequenceGenerator.cpp). There, generation
walks frame nets step-by-step on a dynamically shrinking batch of live
beams. TPU-first: fixed [B, K] beam layout scanned to max_length with
finished-beam masking — one compiled program, no dynamic batch.

The step net is authored with the same DSL as recurrent_group: a data
layer for the previous word id, static links (encoder outputs etc.),
memories for decoder state. Its output layer must produce a probability
distribution [*, V] (softmax output).

User-callback beam hooks (RecurrentGradientMachine.h:92-152
registerBeamSearchControlCallbacks): `BeamHooks` carries plain-Python
callbacks executed HOST-SIDE each step via `jax.pure_callback` —
`adjust` rewrites candidate log-probs before expansion (the
BeamSearchCandidatesAdjustCallback), `drop` truncates/renormalizes
selected beams (NormOrDropNodeCallback/DropCallback), `stop` ends the
whole generation early (stopBeamSearch). A purely-JAX `logprob_fn` is
still available for hooks that don't need host code. Generation runs in
a `lax.while_loop` that exits as soon as every beam has emitted EOS (or
a stop hook fires) — no fixed worst-case step count.

Multi-token dispatch (ISSUE 18): the committed `nmt_beam4_decode_b32`
capture proved decode is dispatch-chain-bound, not byte-bound (~11.8 ms
byte floor vs 91.4 ms measured — a 7.7x gap from the 32-deep sequential
chain). `tokens_per_dispatch=K` makes one while-loop iteration advance
K steps via `lax.scan` over the same step body, cutting the chain from
`max_len` to `ceil(max_len/K)`. Every substep is guarded by a carried
done flag (`lax.cond`), so early-exit-on-all-finished, stop hooks, and
ragged tails stay BIT-IDENTICAL to the K=1 reference — hooks included
(guarded substeps skip their pure_callbacks entirely). The measured
chain depth of the last run is exposed as `last_chain_depth` — bench
rows report it measured-from-the-carried-counter, never assumed.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.arg import Arg
from paddle_tpu.core.config import LayerConf, ModelConf
from paddle_tpu.network import Network

NEG_INF = -1e30


@dataclass
class BeamHooks:
    """Host-side beam-search control callbacks
    (RecurrentGradientMachine.h:92-152). All are optional plain-Python
    functions receiving numpy arrays:

    - adjust(logp [B,K,V] f32, t int) -> [B,K,V] f32 — rewrite the
      step's candidate log-probs before expansion (forbid words, add
      user priors): BeamSearchCandidatesAdjustCallback.
    - drop(words [B,K] i32, scores [B,K] f32, t int) ->
      (scores [B,K] f32, drop_mask [B,K] bool) — renormalize selected
      beams and/or mark beams to truncate (they finish at this step
      with score NEG_INF): NormOrDropNodeCallback + DropCallback.
    - stop(finished [B,K] bool, scores [B,K] f32, t int) -> bool —
      end the whole generation now: stopBeamSearch.
    """

    adjust: Optional[Callable] = None
    drop: Optional[Callable] = None
    stop: Optional[Callable] = None


class BeamSearchDecoder:
    """Built from DSL pieces:

        def step(word, enc):
            emb = dsl.embedding(word, size=E, vocab_size=V, param=...)
            prev = dsl.memory("s", size=H, boot_layer=enc_last)
            s = dsl.fc(emb, prev, size=H, act="tanh", name="s")
            return dsl.fc(s, size=V, act="softmax", name="prob")

        dec = BeamSearchDecoder(step, n_static=1, bos_id=0, eos_id=1,
                                beam_size=4, max_length=20)
        seqs, lens, scores = dec.generate(params, statics=[enc_arg],
                                          boots={"s": enc_last_value})
    """

    def __init__(
        self,
        step: Callable,
        n_static: int,
        bos_id: int,
        eos_id: int,
        beam_size: int,
        max_length: int,
        logprob_fn: Optional[Callable] = None,
        static_sizes: Optional[list] = None,
        hooks: Optional[BeamHooks] = None,
        tokens_per_dispatch: int = 1,
    ):
        """`static_sizes` (optional, one int per static input) stamps
        the static stubs' sizes so size-dependent config helpers (e.g.
        dsl.simple_attention) work inside `step` at generation time the
        same way they do inside a training recurrent_group (whose stubs
        inherit sizes from the parent graph)."""
        from paddle_tpu import dsl

        assert static_sizes is None or len(static_sizes) == n_static, (
            f"static_sizes needs one entry per static input "
            f"({len(static_sizes)} given, n_static={n_static})"
        )
        assert tokens_per_dispatch >= 1, (
            f"tokens_per_dispatch must be >= 1, got {tokens_per_dispatch}"
        )
        self.bos_id, self.eos_id = bos_id, eos_id
        self.k = beam_size
        self.max_length = max_length
        self.logprob_fn = logprob_fn
        self.hooks = hooks or BeamHooks()
        self.tokens_per_dispatch = int(tokens_per_dispatch)
        # measured diagnostics of the LAST generate()/host run: how many
        # sequential dispatch-chain links the decode actually executed
        # (while-loop iterations here; jitted chunk programs on the host
        # rung) and how many token steps they covered
        self.last_chain_depth: Optional[int] = None
        self.last_steps: Optional[int] = None

        with dsl.model() as sub:
            word = sub.add(
                LayerConf(name="@word", type="data", size=1,
                          attrs={"dim": (1,), "is_seq": False,
                                 "is_ids": True})
            )
            statics = []
            for i in range(n_static):
                sz = (static_sizes or [0] * n_static)[i]
                statics.append(
                    sub.add(LayerConf(name=f"@static_{i}", type="data",
                                      size=sz,
                                      attrs={"dim": (sz,),
                                             "is_seq": False,
                                             "is_ids": False}))
                )
            out = step(word, *statics)
        self.step_conf: ModelConf = sub.conf
        self.memories = sub.memories
        self.out_name = out.name
        self.static_links = [f"@static_{i}" for i in range(n_static)]
        self._net: Optional[Network] = None

    def _build(self, statics: list):
        for i, a in enumerate(statics):
            lc = self.step_conf.layer(self.static_links[i])
            v = a.value if a.value is not None else a.ids
            dim = tuple(v.shape[2:] if a.is_seq else v.shape[1:]) or (1,)
            lc.attrs["dim"] = dim
            lc.attrs["is_seq"] = a.is_seq
            lc.attrs["is_ids"] = a.ids is not None
        self._net = Network(self.step_conf)
        return self._net

    def param_confs(self, statics: list):
        """Parameter table of the step net (names shared with training)."""
        return self._build(statics).param_confs

    def prepare(self, statics: list, boots: dict = None,
                batch_size: int = None):
        """Build (static_feed, init_carry_mem, b) — the K-tiled feed
        dict and boot memories both decode paths start from. Shared by
        the jitted while-loop program (generate) and the host-stepped
        per-token path (serving/host_decode.py), so the two rungs of
        the serving degradation ladder see identical inputs."""
        if self._net is None:
            self._build(statics)
        k = self.k
        boots = boots or {}
        if batch_size is not None:
            b = batch_size
        elif statics:
            a0 = statics[0]
            b = (a0.value if a0.value is not None else a0.ids).shape[0]
        elif boots:
            b = next(iter(boots.values())).shape[0]
        else:
            raise ValueError("generate() needs statics, boots, or batch_size")

        def tile(x):
            # [B, ...] -> [B*K, ...]
            return jnp.repeat(x, k, axis=0)

        static_feed = {}
        for i, a in enumerate(statics):
            static_feed[self.static_links[i]] = Arg(
                value=None if a.value is None else tile(a.value),
                ids=None if a.ids is None else tile(a.ids),
                seq_lens=None if a.seq_lens is None else tile(a.seq_lens),
            )

        init_carry_mem = {}
        for m in self.memories:
            if m["layer"] in boots:
                init_carry_mem[m["layer"]] = tile(boots[m["layer"]])
            elif m.get("boot_layer"):
                raise ValueError(
                    f"memory {m['layer']!r} declares boot_layer="
                    f"{m['boot_layer']!r}, but generate() cannot compute "
                    f"parent layers — pass boots={{{m['layer']!r}: value}} "
                    f"with that layer's [B, {m['size']}] output"
                )
            else:
                init_carry_mem[m["layer"]] = jnp.full(
                    (b * k, m["size"]), m.get("boot_value", 0.0), jnp.float32
                )
        return static_feed, init_carry_mem, b

    def generate(self, params: dict, statics: list, boots: dict = None,
                 batch_size: int = None):
        """statics: list[Arg] (batch-major, B rows). boots: memory layer
        name -> [B, size] boot value (overrides zeros/boot_value).
        Returns (seqs [B, K, max_length] int32, lens [B, K], scores [B, K]),
        beams sorted best-first."""
        static_feed, init_carry_mem, b = self.prepare(
            statics, boots, batch_size
        )
        run = self._decode_program()
        t0 = time.perf_counter()
        seqs, lens, scores, t_end, chunks = run(
            params, static_feed, init_carry_mem, b
        )
        t1 = time.perf_counter()
        # the chain depth is MEASURED: `chunks` is a counter carried
        # through the while-loop state, incremented once per executed
        # iteration (= one link of the sequential dependency chain),
        # fetched after the run — never derived from config.
        # The int() fetches BLOCK on the whole jitted while-loop, so
        # they are the device-time window; only the submit window
        # before them is host dispatch work (`last_timeline` is what
        # a caller must read — timing around generate() itself
        # attributes the entire device run to dispatch and reports a
        # nonsense host_overhead_frac of ~1.0)
        self.last_steps = int(t_end)
        self.last_chain_depth = int(chunks)
        t2 = time.perf_counter()
        self.last_timeline = {"dispatch_s": t1 - t0,
                              "device_s": t2 - t1}
        return seqs, lens, scores

    def _decode_program(self):
        """The whole decode (step net + while-loop + backtrace) as ONE
        jitted program, cached on the decoder (keyed by the hook/logprob
        closures; jax.jit handles shape-keyed retraces). Without this,
        every generate() call re-traced the loop and paid seconds of
        host tracing + compile-cache lookups per batch — measured 122
        ms/decode-step at B=32 K=4 V=30k vs ~3 ms jitted."""
        # key on everything _decode_core closes over at trace time —
        # hooks/logprob AND the scalar decode config (k/max_length/
        # eos/bos): mutating decoder attributes after the first
        # generate() must not silently reuse a stale compiled program
        hk = (self.hooks.adjust, self.hooks.drop, self.hooks.stop,
              self.logprob_fn, self.k, self.max_length, self.eos_id,
              self.bos_id, self.tokens_per_dispatch)
        cache = getattr(self, "_decode_cache", None)
        if cache is None:
            cache = self._decode_cache = {}
        guard = getattr(self, "_recompile_guard", None)
        if guard is None:
            from paddle_tpu.analysis.recompile_guard import (
                RecompileGuard,
            )

            guard = self._recompile_guard = RecompileGuard(
                "beam_decode"
            )
        if hk not in cache and len(cache) >= 8:
            # bound the cache: fresh hook lambdas per call would
            # otherwise grow it without limit (hooks should be stable
            # objects; evict oldest insertion when they are not)
            cache.pop(next(iter(cache)))
        if hk not in cache:
            # one jitted program per hook configuration — alternating
            # hook setups keep their compiled traces. NB: jit a fresh
            # closure, NOT the bound method: bound methods of the same
            # instance compare equal, so jit wrappers around them share
            # one trace cache and the second hook config would silently
            # reuse the first config's compiled program.
            def core(params, static_feed, init_carry_mem, b):
                # trace-time only (ISSUE 13): the serving batcher
                # arms this after warmup — a steady-state retrace of
                # a cached decode program is the 122 ms/step cliff
                # this cache exists to prevent
                guard.note(static_feed, init_carry_mem, b=b)
                return self._decode_core(
                    params, static_feed, init_carry_mem, b
                )

            cache[hk] = jax.jit(core, static_argnums=(3,))
        return cache[hk]

    def _expand_step(self, params, static_feed, mems, words, scores,
                     finished, t, b, adjust_fn=None, drop_fn=None):
        """One beam-expansion step: step-net forward, candidate scoring,
        finished-beam eos-extension, top-k, parent-conditioned memory
        carry. Shared by the jitted while-loop program (hook
        pure_callbacks threaded in via adjust_fn/drop_fn) and the host
        rung's chunked K-step program (hook-free) so the two dispatch
        granularities cannot drift semantically."""
        net, k = self._net, self.k
        feed = dict(static_feed)
        feed["@word"] = Arg(ids=words.reshape(b * k))
        for m in self.memories:
            feed[m["link"]] = Arg(value=mems[m["layer"]])
        outs, _ = net.forward(params, feed, train=False)
        prob = outs[self.out_name].value  # [B*K, V]
        v = prob.shape[-1]
        # score math is pinned to f32 regardless of AMP: under bf16
        # matmul precision the step net emits bf16 probs, and letting
        # weak-type promotion decide the carry dtype made the score
        # accumulator backend-dependent (while_loop silently promoted
        # the carry to bf16; lax.scan/cond refuse the same mismatch)
        logp = jnp.log(
            jnp.maximum(prob, 1e-20)
        ).reshape(b, k, v).astype(jnp.float32)
        if self.logprob_fn is not None:
            logp = self.logprob_fn(logp, t)
        if adjust_fn is not None:
            logp = adjust_fn(logp, t)
        # finished beams only extend with eos at no cost
        fin_row = jnp.full((v,), NEG_INF).at[self.eos_id].set(0.0)
        logp = jnp.where(finished[..., None], fin_row[None, None, :], logp)
        cand = scores[..., None] + logp  # [B,K,V]
        flat = cand.reshape(b, k * v)
        top_scores, top_idx = jax.lax.top_k(flat, k)  # [B,K]
        parent = top_idx // v  # [B,K]
        word = (top_idx % v).astype(jnp.int32)
        # reorder memories by parent beam
        new_mems = {}
        for m in self.memories:
            mm = outs[m["layer"]].value.reshape(b, k, -1)
            sel = jnp.take_along_axis(mm, parent[..., None], axis=1)
            prev = mems[m["layer"]].reshape(b, k, -1)
            prev_sel = jnp.take_along_axis(prev, parent[..., None], axis=1)
            was_fin = jnp.take_along_axis(finished, parent, axis=1)
            keep = was_fin[..., None]
            new_mems[m["layer"]] = jnp.where(
                keep, prev_sel, sel
            ).reshape(b * k, -1)
        was_fin = jnp.take_along_axis(finished, parent, axis=1)
        new_fin = was_fin | (word == self.eos_id)
        if drop_fn is not None:
            top_scores, new_fin = drop_fn(word, top_scores, new_fin, t)
        return new_mems, word, parent, top_scores, new_fin

    def _decode_core(self, params, static_feed, init_carry_mem, b):
        k = self.k
        hooks = self.hooks
        t_max = self.max_length
        k_tok = min(self.tokens_per_dispatch, t_max)

        adjust_fn = None
        if hooks.adjust is not None:
            # BeamSearchCandidatesAdjustCallback: host code rewrites
            # the candidate log-probs
            def adjust_fn(logp, t):
                bb, kk, vv = logp.shape
                return jax.pure_callback(
                    lambda lp, tt: np.asarray(
                        hooks.adjust(np.asarray(lp), int(tt)),
                        np.float32,
                    ),
                    jax.ShapeDtypeStruct((bb, kk, vv), jnp.float32),
                    logp, t,
                )

        drop_fn = None
        if hooks.drop is not None:
            # NormOrDropNodeCallback/DropCallback: host code
            # renormalizes selected beams and truncates dropped ones
            def drop_fn(word, top_scores, new_fin, t):
                def _drop(wd, sc, tt):
                    s2, dm = hooks.drop(
                        np.asarray(wd), np.asarray(sc), int(tt)
                    )
                    return (
                        np.asarray(s2, np.float32),
                        np.asarray(dm, bool),
                    )

                top_scores, drop_mask = jax.pure_callback(
                    _drop,
                    (
                        jax.ShapeDtypeStruct((b, k), jnp.float32),
                        jax.ShapeDtypeStruct((b, k), bool),
                    ),
                    word, top_scores, t,
                )
                top_scores = jnp.where(drop_mask, NEG_INF, top_scores)
                return top_scores, new_fin | drop_mask

        def step_once(mems, words, scores, finished, t):
            new_mems, word, parent, top_scores, new_fin = (
                self._expand_step(
                    params, static_feed, mems, words, scores, finished,
                    t, b, adjust_fn=adjust_fn, drop_fn=drop_fn,
                )
            )
            user_stop = jnp.asarray(False)
            if hooks.stop is not None:
                user_stop = jax.pure_callback(
                    lambda f, s, tt: bool(
                        hooks.stop(np.asarray(f), np.asarray(s), int(tt))
                    ),
                    jax.ShapeDtypeStruct((), bool),
                    new_fin, top_scores, t,
                )
            return new_mems, word, parent, top_scores, new_fin, user_stop

        # while-loop with preallocated trace buffers: exits as soon as
        # every beam has finished (or a stop hook fires) instead of
        # always paying max_length steps. Unwritten steps hold
        # (word=eos, parent=identity), which backtraces benignly.
        words0 = jnp.full((b, k), self.bos_id, jnp.int32)
        scores0 = jnp.full(
            (b, k), NEG_INF, jnp.float32
        ).at[:, 0].set(0.0)
        fin0 = jnp.zeros((b, k), bool)
        idk = jnp.broadcast_to(
            jnp.arange(k, dtype=jnp.int32)[None, :], (b, k)
        )
        ws0 = jnp.full((t_max, b, k), self.eos_id, jnp.int32)
        ps0 = jnp.broadcast_to(idk[None], (t_max, b, k))
        state0 = (
            init_carry_mem, words0, scores0, fin0, jnp.int32(0),
            jnp.asarray(False), ws0, ps0, jnp.int32(0),
        )

        def cond(state):
            _, _, _, finished, t, stop, _, _, _ = state
            return (t < t_max) & ~stop & ~jnp.all(finished)

        def run_one(inner):
            mems, words, scores, finished, t, _, ws, ps = inner
            new_mems, word, parent, scores, new_fin, user_stop = (
                step_once(mems, words, scores, finished, t)
            )
            ws = ws.at[t].set(word)
            ps = ps.at[t].set(parent)
            return (
                new_mems, word, scores, new_fin, t + 1, user_stop, ws, ps,
            )

        def body(state):
            # one while-loop iteration = ONE sequential dispatch-chain
            # link; `chunks` counts them so the reported chain depth is
            # measured, not derived from config
            inner, chunks = state[:8], state[8]
            if k_tok == 1:
                inner = run_one(inner)
            else:
                # advance up to k_tok steps inside this iteration. Each
                # substep re-checks the exit condition and no-ops once
                # it holds (lax.cond skips the step net AND any hook
                # pure_callbacks), so early-finish/stop mid-chunk and
                # ragged t_max tails stay bit-identical to K=1.
                def substep(carry, _):
                    _, _, _, finished, t, stop, _, _ = carry
                    done = (
                        stop | (t >= t_max) | jnp.all(finished)
                    )
                    carry = jax.lax.cond(
                        done, lambda c: c, run_one, carry
                    )
                    return carry, None

                inner, _ = jax.lax.scan(
                    substep, inner, None, length=k_tok
                )
            return (*inner, chunks + 1)

        _, _, scores, finished, t_end, _, ws, ps, chunks = (
            jax.lax.while_loop(cond, body, state0)
        )

        # backtrace beam parents to recover sequences
        def back(nxt_parent, step_out):
            w_t, p_t = step_out
            w = jnp.take_along_axis(w_t, nxt_parent, axis=1)
            p = jnp.take_along_axis(p_t, nxt_parent, axis=1)
            return p, w

        _, seq_rev = jax.lax.scan(back, idk, (ws, ps), reverse=True)
        seqs = seq_rev.transpose(1, 2, 0)  # [B,K,T]
        # length = position of first eos + 1 (or max_length)
        is_eos = seqs == self.eos_id
        any_eos = jnp.any(is_eos, axis=-1)
        first_eos = jnp.argmax(is_eos, axis=-1)
        lens = jnp.where(any_eos, first_eos + 1, t_max).astype(jnp.int32)
        return seqs, lens, scores, t_end, chunks

    def _chunk_step_program(self, b: int, n_steps: int):
        """K beam-expansion steps + bookkeeping as ONE jitted program —
        the serving host rung's per-chunk dispatch unit (ISSUE 18).
        Hook-free by construction: host callbacks force the per-token
        path. Each substep is guarded by an all-finished check so an
        early finish mid-chunk no-ops the tail (word=eos,
        parent=identity — the trace-buffer convention the backtrace
        already treats as benign). The carried memories are DONATED:
        they alias the returned memories buffer-for-buffer, which the
        committed capture's audit policy checks via input_output_alias.

        Returns a jitted fn (params, static_feed, mems, words, scores,
        finished, t0) -> (words_stack [n,B,K], parents_stack [n,B,K],
        last_words, scores, finished, new_mems)."""
        cache = getattr(self, "_chunk_cache", None)
        if cache is None:
            cache = self._chunk_cache = {}
        key = (b, self.k, n_steps, self.logprob_fn, self.eos_id,
               self.max_length)
        if key not in cache and len(cache) >= 8:
            cache.pop(next(iter(cache)))
        if key not in cache:
            k, eos = self.k, self.eos_id
            idk = jnp.broadcast_to(
                jnp.arange(k, dtype=jnp.int32)[None, :], (b, k)
            )

            def chunk(params, static_feed, mems, words, scores,
                      finished, t0):
                def substep(carry, j):
                    mems, words, scores, finished = carry
                    t = t0 + j

                    def run(c):
                        mems, words, scores, finished = c
                        new_mems, word, parent, s2, fin2 = (
                            self._expand_step(
                                params, static_feed, mems, words,
                                scores, finished, t, b,
                            )
                        )
                        return (
                            (new_mems, word, s2, fin2), (word, parent)
                        )

                    def skip(c):
                        word = jnp.full((b, k), eos, jnp.int32)
                        return c, (word, idk)

                    return jax.lax.cond(
                        jnp.all(finished), skip, run, carry
                    )

                (mems2, words2, scores2, fin2), (ws, ps) = jax.lax.scan(
                    substep, (mems, words, scores, finished),
                    jnp.arange(n_steps),
                )
                return ws, ps, words2, scores2, fin2, mems2

            cache[key] = jax.jit(chunk, donate_argnums=(2,))
        return cache[key]
