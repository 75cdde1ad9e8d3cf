"""Paged KV-cache pool + prefill/decode disaggregation (ISSUE 19).

The committed `nmt_beam4_decode_b32` capture proved decode is
dispatch-chain bound (7.7x over an ~11.8 ms byte floor — the PR12
verdict), and every model before this one still RECOMPUTES the whole
prefix per emitted token on top of that. This module removes the
recompute: generation splits into two compiled programs over a pool
of fixed-size KV pages —

- **prefill** (one per length bucket): full causal forward over the
  prompt, per-layer K/V scattered into the sequence's pages, and the
  first next-token selection (top-k + score) FUSED into the same
  dispatch. Buckets are page-aligned powers of two so the serving
  program cache stays small (`PagedKVCache.bucket_for`).
- **decode** (one per batch width): ONE dispatch per token that
  gathers the page context, runs the new token through every block,
  appends its K/V into the pool *in place* (the pool buffers are
  donated — `input_output_alias` is audited like the chunk rung's
  memories), selects the next token (argmax / beam top-k), and
  updates the running score. Forward + top-k + cache append + score
  update in one program retires ROADMAP residual 2(c).

Decode cost now scales with NEW tokens: the per-step attention reads
the cached pages ([B, 1, S] scores — no [T, T] anywhere) instead of
re-running a length-T forward. Pages are a host-side free list; a
sequence holds `ceil(len/page_size)` pages (+1 as it grows), so the
serving engine (`paddle_tpu/serving/lm_engine.py`) can evict a
request mid-generation by freeing its pages and re-prefill it later
byte-identically — continuous batching over a bounded pool.

The speculative rung (PR18's draft-proposes/target-verifies scheme)
composes: `SpeculativePagedLM` runs the draft's K-token proposal as
one scan that APPENDS to the draft's own pool, then verifies all K
positions in one chunked dispatch that appends to the target's pool
(`lm_decode_chunk` with n=K). Accepted-prefix bookkeeping stays on
the host; stale entries past the accept point are masked by the
position-based attention mask and overwritten next round.

All math lives in `paddle_tpu/models/lm.py` and is shared with the
full-recompute references, so the pinned tests compare ONLY cache vs
recompute. Module scope is jax-free (ast_lint import fence): jax is
imported function-locally, like every decoding/ module.
"""

from __future__ import annotations

import threading
from typing import Optional

import numpy as np

__all__ = [
    "PoolExhausted", "PagedKVCache", "PagedLM", "SpeculativePagedLM",
]


class PoolExhausted(RuntimeError):
    """The page free list cannot satisfy an allocation — the serving
    engine's cue to evict (or shed) before retrying."""


class PagedKVCache:
    """Fixed-size-page KV pool for one LM: the device-side K/V arrays
    ([L, num_pages, page_size, H, hd] each), a host-side page free
    list, and the counters of what was read from pages and recomputed.

    Slot addressing: absolute position p of a sequence lives in its
    `pages[p // page_size]` at offset `p % page_size`; a gathered
    page-table context therefore has slot s == absolute position s,
    which is what `models.lm.lm_decode_chunk` assumes.
    """

    def __init__(self, spec, num_pages: int, page_size: int = 16,
                 max_pages_per_seq: Optional[int] = None):
        assert page_size >= 1 and num_pages >= 1
        self.spec = spec
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        self.max_pages_per_seq = int(max_pages_per_seq or num_pages)
        self._lock = threading.Lock()
        self._free = list(range(self.num_pages))
        self.pool = None  # (pool_k, pool_v) jax arrays, lazy
        # measured counters (the decode row's cache story)
        self.appended_tokens = 0        # tokens written by decode
        self.prefilled_tokens = 0       # tokens written by prefill
        self.cached_prefix_tokens = 0   # sum of prefix lengths served
        self.evictions = 0              # from the pool per decode row

    @property
    def max_seq_len(self) -> int:
        return self.max_pages_per_seq * self.page_size

    def bucket_for(self, length: int) -> int:
        """Smallest page-aligned power-of-two-pages bucket >= length —
        len-bucketed prefill keeps the compiled-program cache small."""
        assert 1 <= length <= self.max_seq_len, (
            f"length {length} outside pool capacity {self.max_seq_len}"
        )
        pages = 1
        while pages * self.page_size < length:
            pages *= 2
        return min(pages, self.max_pages_per_seq) * self.page_size

    def ensure_pool(self):
        if self.pool is None:
            import jax.numpy as jnp

            s = self.spec
            shape = (s.num_layers, self.num_pages, self.page_size,
                     s.num_heads, s.head_dim)
            self.pool = (jnp.zeros(shape, jnp.float32),
                         jnp.zeros(shape, jnp.float32))
        return self.pool

    def free_page_count(self) -> int:
        with self._lock:
            return len(self._free)

    def alloc(self, n: int) -> list:
        with self._lock:
            if n > len(self._free):
                raise PoolExhausted(
                    f"need {n} pages, {len(self._free)} free"
                )
            pages, self._free = self._free[:n], self._free[n:]
            return pages

    def free(self, pages) -> None:
        with self._lock:
            self._free.extend(pages)

    def pages_for_len(self, length: int) -> int:
        """Pages a sequence of `length` tokens holds, plus the page
        its NEXT append lands in (decode writes at pos == length)."""
        return min(length // self.page_size + 1,
                   self.max_pages_per_seq)


def _page_table(page_lists, maxp):
    """Stack ragged per-row page lists into the [rows, maxp] int32
    table the programs take; unused slots point at page 0 but are
    never read (position mask) nor written (host capacity
    invariant)."""
    tbl = np.zeros((len(page_lists), maxp), np.int32)
    for r, pages in enumerate(page_lists):
        tbl[r, :len(pages)] = pages
    return tbl


class PagedLM:
    """Compiled prefill + fused decode programs for one LM over one
    PagedKVCache. Host-side loops live here for direct generate()
    use; the serving engine drives `prefill()`/`decode_step()` itself
    to interleave admissions and evictions between dispatches.

    Chain depth is MEASURED (dispatches counted into
    `last_chain_depth`), and `last_timeline` splits each generate
    into dispatch-vs-device seconds the honest way: the submission
    window is host/dispatch work, the blocking fetch of the selected
    tokens is device time (the satellite-6 rule)."""

    _MAX_PROGS = 8

    def __init__(self, spec, params, cache: PagedKVCache,
                 eos_id: int = 1):
        assert cache.spec == spec
        self.spec = spec
        self.params = params
        self.cache = cache
        self.eos_id = int(eos_id)
        self._progs = {}
        self._recompile_guard = None
        self.last_chain_depth: Optional[int] = None
        self.last_timeline: Optional[dict] = None

    # -- program cache ----------------------------------------------
    def _guard(self):
        if self._recompile_guard is None:
            from paddle_tpu.analysis.recompile_guard import (
                RecompileGuard,
            )

            self._recompile_guard = RecompileGuard("paged_lm")
        return self._recompile_guard

    @property
    def recompile_guards(self):
        return [self._guard()]

    def _cached(self, key, build):
        if key not in self._progs and len(self._progs) >= \
                self._MAX_PROGS:
            self._progs.pop(next(iter(self._progs)))
        if key not in self._progs:
            self._progs[key] = build()
        return self._progs[key]

    # -- prefill: bucketed full forward + page scatter + first top-k
    def _prefill_program(self, b: int, t: int, beam_k: int = 0):
        """t is the page-aligned bucket length; beam_k=0 builds the
        greedy variant (argmax + score), beam_k>0 the beam-init
        variant (top-k expansion fused into the prefill dispatch)."""
        ps = self.cache.page_size
        assert t % ps == 0
        key = ("prefill", b, t, beam_k)

        def build():
            import jax
            import jax.numpy as jnp

            from paddle_tpu.models import lm as lmm

            spec, guard = self.spec, self._guard()
            n_pages = t // ps

            def prog(params, pool_k, pool_v, ids, lens, pages):
                guard.note(ids, pages, b=b, t=t, beam_k=beam_k,
                           kind="prefill")
                logits, ks, vs = lmm.lm_forward(
                    spec, params, ids, lens=lens, with_kv=True
                )
                shp = (spec.num_layers, b, n_pages, ps,
                       spec.num_heads, spec.head_dim)
                pool_k = pool_k.at[:, pages].set(ks.reshape(shp))
                pool_v = pool_v.at[:, pages].set(vs.reshape(shp))
                last = jnp.take_along_axis(
                    logits, (lens - 1)[:, None, None], axis=1
                )[:, 0, :]
                logp = lmm.lm_logp(last)
                if beam_k:
                    scores, toks = lmm.beam_init_select(logp, beam_k)
                else:
                    toks = jnp.argmax(logp, axis=-1).astype(jnp.int32)
                    scores = jnp.take_along_axis(
                        logp, toks[:, None], axis=1
                    )[:, 0]
                return pool_k, pool_v, toks, scores

            return jax.jit(prog, donate_argnums=(1, 2))

        return self._cached(key, build)

    # -- decode: gather pages -> 1-token forward -> append -> select
    def _decode_program(self, b: int):
        key = ("decode", b)

        def build():
            import jax
            import jax.numpy as jnp

            from paddle_tpu.models import lm as lmm

            spec, guard = self.spec, self._guard()
            ps = self.cache.page_size
            maxp = self.cache.max_pages_per_seq
            eos = self.eos_id

            def prog(params, pool_k, pool_v, tok, pos, page_tbl,
                     scores, finished):
                guard.note(tok, page_tbl, b=b, kind="decode")
                s = maxp * ps
                ctx_k = pool_k[:, page_tbl].reshape(
                    spec.num_layers, b, s, spec.num_heads,
                    spec.head_dim,
                )
                ctx_v = pool_v[:, page_tbl].reshape(
                    spec.num_layers, b, s, spec.num_heads,
                    spec.head_dim,
                )
                logits, nk, nv = lmm.lm_decode_chunk(
                    spec, params, tok[:, None], pos, ctx_k, ctx_v
                )
                pp = jnp.take_along_axis(
                    page_tbl, (pos // ps)[:, None], axis=1
                )[:, 0]
                pool_k = pool_k.at[:, pp, pos % ps].set(nk[:, :, 0])
                pool_v = pool_v.at[:, pp, pos % ps].set(nv[:, :, 0])
                logp = lmm.lm_logp(logits[:, 0])
                nxt = jnp.argmax(logp, axis=-1).astype(jnp.int32)
                nxt = jnp.where(finished, eos, nxt)
                sc = jnp.where(
                    finished, scores,
                    scores + jnp.take_along_axis(
                        logp, nxt[:, None], axis=1
                    )[:, 0],
                )
                fin = finished | (nxt == eos)
                return pool_k, pool_v, nxt, sc, fin

            return jax.jit(prog, donate_argnums=(1, 2))

        return self._cached(key, build)

    # -- beam decode: parent page-copy + flat step + beam select ----
    def _beam_decode_program(self, b: int, k: int):
        key = ("beam_decode", b, k)

        def build():
            import jax
            import jax.numpy as jnp

            from paddle_tpu.models import lm as lmm

            spec, guard = self.spec, self._guard()
            ps = self.cache.page_size
            maxp = self.cache.max_pages_per_seq
            eos = self.eos_id

            def prog(params, pool_k, pool_v, toks, parent, pos,
                     page_tbl, scores, finished):
                # page_tbl [b, k, maxp]; toks/parent/scores/finished
                # [b, k]; pos [b]. Step order: (1) adopt the parent
                # beam's cache by physically copying its page contents
                # into this row's pages (a production pool would COW
                # the page REFERENCES; copying keeps the programs
                # single-dispatch and the tests exact), (2) append
                # toks, (3) select the next expansion.
                guard.note(toks, page_tbl, b=b, k=k, kind="beam")
                pidx = parent[None, :, :, None, None, None, None]
                gk = pool_k[:, page_tbl]
                gv = pool_v[:, page_tbl]
                pool_k = pool_k.at[:, page_tbl].set(
                    jnp.take_along_axis(gk, pidx, axis=2)
                )
                pool_v = pool_v.at[:, page_tbl].set(
                    jnp.take_along_axis(gv, pidx, axis=2)
                )
                r = b * k
                s = maxp * ps
                flat_tbl = page_tbl.reshape(r, maxp)
                ctx_k = pool_k[:, flat_tbl].reshape(
                    spec.num_layers, r, s, spec.num_heads,
                    spec.head_dim,
                )
                ctx_v = pool_v[:, flat_tbl].reshape(
                    spec.num_layers, r, s, spec.num_heads,
                    spec.head_dim,
                )
                start = jnp.repeat(pos, k)
                logits, nk, nv = lmm.lm_decode_chunk(
                    spec, params, toks.reshape(r, 1), start,
                    ctx_k, ctx_v,
                )
                pp = jnp.take_along_axis(
                    flat_tbl, (start // ps)[:, None], axis=1
                )[:, 0]
                pool_k = pool_k.at[:, pp, start % ps].set(nk[:, :, 0])
                pool_v = pool_v.at[:, pp, start % ps].set(nv[:, :, 0])
                logp = lmm.lm_logp(logits[:, 0]).reshape(b, k, -1)
                sc, par, tok, fin = lmm.beam_step_select(
                    scores, logp, finished, eos
                )
                return pool_k, pool_v, tok, par, sc, fin

            return jax.jit(prog, donate_argnums=(1, 2))

        return self._cached(key, build)

    # -- host-side primitives (engine entry points) -----------------
    def prefill(self, ids, lens, page_lists, beam_k: int = 0):
        """Run the bucketed prefill for rows whose pages are already
        allocated (page_lists[r] must hold >= bucket//page_size
        pages). ids [B, bucket] int32. Updates the pool in place and
        returns (toks, scores) as UNFETCHED device arrays — [B]/[B]
        greedy or [B, K] beam — so callers can chain dispatches
        without a host round-trip."""
        import jax.numpy as jnp

        b, t = ids.shape
        ps = self.cache.page_size
        assert t % ps == 0 and t >= int(np.max(lens))
        pages = np.asarray([p[:t // ps] for p in page_lists],
                           np.int32)
        pool_k, pool_v = self.cache.ensure_pool()
        prog = self._prefill_program(b, t, beam_k)
        pool_k, pool_v, toks, scores = prog(
            self.params, pool_k, pool_v, jnp.asarray(ids),
            jnp.asarray(lens), jnp.asarray(pages),
        )
        self.cache.pool = (pool_k, pool_v)
        self.cache.prefilled_tokens += int(np.sum(lens))
        return toks, scores

    def decode_step(self, tok, pos, page_lists, scores, finished):
        """One fused decode dispatch: append `tok` (the pending token
        at absolute position pos[r]) and select the next. `pos` and
        `page_lists` are host-side; tok/scores/finished may stay
        unfetched device arrays so the chain never blocks. Returns
        (next_tok, scores, finished) device arrays."""
        import jax.numpy as jnp

        b = len(tok)
        tbl = _page_table(page_lists, self.cache.max_pages_per_seq)
        pool_k, pool_v = self.cache.ensure_pool()
        prog = self._decode_program(b)
        pool_k, pool_v, nxt, sc, fin = prog(
            self.params, pool_k, pool_v, jnp.asarray(tok),
            jnp.asarray(pos), jnp.asarray(tbl),
            jnp.asarray(scores), jnp.asarray(finished),
        )
        self.cache.pool = (pool_k, pool_v)
        self.cache.appended_tokens += b
        self.cache.cached_prefix_tokens += int(np.sum(pos))
        return nxt, sc, fin

    def _grow(self, page_lists, pos):
        """Allocate the next page for any row whose append position
        crossed its last page boundary."""
        need = 0
        ps = self.cache.page_size
        for r, p in enumerate(page_lists):
            while len(p) * ps <= int(pos[r]):
                p.extend(self.cache.alloc(1))
                need += 1
        return need

    # -- whole-call generation (tests / bench) ----------------------
    def generate(self, ids, lens, max_new: int):
        """Greedy paged generation: bucketed prefill + max_new-1
        fused decode dispatches. Returns (tokens [B, max_new] int32,
        scores [B] f32) — token-for-token equal to
        models.lm.greedy_decode_recompute (pinned)."""
        import time

        b = ids.shape[0]
        lens = np.asarray(lens, np.int32)
        bucket = self.cache.bucket_for(int(lens.max()))
        ps = self.cache.page_size
        padded = np.zeros((b, bucket), np.int32)
        padded[:, :min(bucket, ids.shape[1])] = \
            ids[:, :bucket]
        page_lists = [self.cache.alloc(bucket // ps)
                      for _ in range(b)]
        t0 = time.perf_counter()
        toks, scores = self.prefill(padded, lens, page_lists)
        chain = 1
        # trim: keep the pages the live prefix (and the next append)
        # occupies, return the bucket's tail pages to the pool
        for r, p in enumerate(page_lists):
            keep = self.cache.pages_for_len(int(lens[r]))
            if len(p) > keep:
                self.cache.free(p[keep:])
                del p[keep:]
        # the whole chain runs WITHOUT a host round-trip: each decode
        # feeds the previous dispatch's unfetched token array, and the
        # single blocking fetch at the end is the device-time window
        # (the satellite-6 attribution rule)
        finished = toks == self.eos_id
        step_toks = [toks]
        pos = lens.copy()
        for _ in range(1, max_new):
            self._grow(page_lists, pos)
            toks, scores, finished = self.decode_step(
                toks, pos, page_lists, scores, finished
            )
            chain += 1
            step_toks.append(toks)
            pos += 1
        t1 = time.perf_counter()
        out = np.stack([np.asarray(x) for x in step_toks], axis=1)
        scores = np.asarray(scores, np.float32)
        t2 = time.perf_counter()
        self.last_chain_depth = chain
        self.last_timeline = {"dispatch_s": t1 - t0,
                              "device_s": t2 - t1}
        for p in page_lists:
            self.cache.free(p)
        return out.astype(np.int32), scores

    def beam_generate(self, ids, lens, beam_k: int, max_new: int):
        """Paged beam search. Returns (tokens [B, K, max_new] int32,
        scores [B, K] f32) — equal to beam_decode_recompute under the
        shared expansion rule (pinned)."""
        b = ids.shape[0]
        k = int(beam_k)
        lens = np.asarray(lens, np.int32)
        bucket = self.cache.bucket_for(int(lens.max()))
        ps = self.cache.page_size
        padded = np.zeros((b, bucket), np.int32)
        padded[:, :min(bucket, ids.shape[1])] = ids[:, :bucket]
        # beam row (g, j) owns its own pages; prefill fills row j=0,
        # the first decode's parent=0 copy fans the prefix out
        rows = [[self.cache.alloc(bucket // ps) for _ in range(k)]
                for _ in range(b)]
        import time

        disp_s = dev_s = 0.0
        t0 = time.perf_counter()
        toks_d, scores = self.prefill(
            padded, lens, [r[0] for r in rows], beam_k=k
        )
        t1 = time.perf_counter()
        toks = np.asarray(toks_d)
        t2 = time.perf_counter()
        disp_s += t1 - t0
        dev_s += t2 - t1
        chain = 1
        for g in range(b):
            keep = self.cache.pages_for_len(int(lens[g]))
            for p in rows[g]:
                if len(p) > keep:
                    self.cache.free(p[keep:])
                    del p[keep:]
        hist = np.zeros((b, k, max_new), np.int32)
        hist[:, :, 0] = toks
        finished = toks == self.eos_id
        parent = np.zeros((b, k), np.int32)
        pos = lens.copy()
        gi = np.arange(b)[:, None]
        for t in range(1, max_new):
            flat = [p for g in rows for p in g]
            self._grow(flat, np.repeat(pos, k))
            t0 = time.perf_counter()
            toks_d, par_d, scores, finished = self._beam_step(
                toks, parent, pos, rows, scores, finished
            )
            t1 = time.perf_counter()
            # host reorder of the emitted history needs the parent
            # pointers — this fetch IS the device-time window
            toks = np.asarray(toks_d)
            parent = np.asarray(par_d)
            t2 = time.perf_counter()
            disp_s += t1 - t0
            dev_s += t2 - t1
            chain += 1
            hist = hist[gi, parent]
            hist[:, :, t] = toks
            pos += 1
        self.last_chain_depth = chain
        self.last_timeline = {"dispatch_s": disp_s,
                              "device_s": dev_s}
        for g in rows:
            for p in g:
                self.cache.free(p)
        return hist, np.asarray(scores, np.float32)

    def _beam_step(self, toks, parent, pos, rows, scores, finished):
        import jax.numpy as jnp

        b, k = toks.shape
        maxp = self.cache.max_pages_per_seq
        tbl = np.zeros((b, k, maxp), np.int32)
        for g in range(b):
            for j in range(k):
                tbl[g, j, :len(rows[g][j])] = rows[g][j]
        pool_k, pool_v = self.cache.ensure_pool()
        prog = self._beam_decode_program(b, k)
        pool_k, pool_v, tok, par, sc, fin = prog(
            self.params, pool_k, pool_v, jnp.asarray(toks),
            jnp.asarray(parent), jnp.asarray(pos),
            jnp.asarray(tbl), jnp.asarray(scores),
            jnp.asarray(finished),
        )
        self.cache.pool = (pool_k, pool_v)
        self.cache.appended_tokens += b * k
        self.cache.cached_prefix_tokens += int(np.sum(pos)) * k
        return tok, par, sc, fin


class SpeculativePagedLM:
    """PR18's draft-proposes/target-verifies speculation THROUGH the
    paged pool (satellite 1): the draft's K-token proposal is one
    compiled scan appending to the draft's own pages; the target
    verifies all K positions in one chunked dispatch
    (`lm_decode_chunk` with n=K) appending to the target's pages. The
    host accepts the longest agreeing prefix + the target's corrected
    token, so output is token-for-token the target's greedy KV output
    no matter how bad the draft is — pinned by
    tests/test_lm_kv_cache.py. Stale cache entries past an accept
    point are never read (position mask) and are overwritten by the
    next round's appends."""

    def __init__(self, target: PagedLM, draft: PagedLM,
                 propose_k: int = 4):
        assert propose_k >= 1
        assert target.eos_id == draft.eos_id
        self.target, self.draft = target, draft
        self.propose_k = int(propose_k)
        self._progs = {}
        self.last_chain_depth: Optional[int] = None
        self.last_accept_rate: Optional[float] = None

    def _propose_program(self, b: int, n: int):
        key = (b, n)
        if key not in self._progs and len(self._progs) >= 8:
            self._progs.pop(next(iter(self._progs)))
        if key not in self._progs:
            import jax
            import jax.numpy as jnp

            from paddle_tpu.models import lm as lmm

            drf = self.draft
            spec = drf.spec
            ps = drf.cache.page_size
            maxp = drf.cache.max_pages_per_seq
            s = maxp * ps

            def prog(params, pool_k, pool_v, first, pos, page_tbl):
                drf._guard().note(first, page_tbl, b=b, n=n,
                                  kind="propose")

                def substep(carry, j):
                    pool_k, pool_v, w = carry
                    ctx_k = pool_k[:, page_tbl].reshape(
                        spec.num_layers, b, s, spec.num_heads,
                        spec.head_dim,
                    )
                    ctx_v = pool_v[:, page_tbl].reshape(
                        spec.num_layers, b, s, spec.num_heads,
                        spec.head_dim,
                    )
                    p = pos + j
                    logits, nk, nv = lmm.lm_decode_chunk(
                        spec, params, w[:, None], p, ctx_k, ctx_v
                    )
                    pp = jnp.take_along_axis(
                        page_tbl, (p // ps)[:, None], axis=1
                    )[:, 0]
                    pool_k = pool_k.at[:, pp, p % ps].set(
                        nk[:, :, 0]
                    )
                    pool_v = pool_v.at[:, pp, p % ps].set(
                        nv[:, :, 0]
                    )
                    g = jnp.argmax(
                        lmm.lm_logp(logits[:, 0]), axis=-1
                    ).astype(jnp.int32)
                    return (pool_k, pool_v, g), g

                (pool_k, pool_v, _), props = jax.lax.scan(
                    substep, (pool_k, pool_v, first), jnp.arange(n)
                )
                return pool_k, pool_v, props

            self._progs[key] = jax.jit(prog, donate_argnums=(1, 2))
        return self._progs[key]

    def _verify_program(self, b: int, n: int):
        key = ("verify", b, n)
        if key not in self._progs and len(self._progs) >= 8:
            self._progs.pop(next(iter(self._progs)))
        if key not in self._progs:
            import jax
            import jax.numpy as jnp

            from paddle_tpu.models import lm as lmm

            tgt = self.target
            spec = tgt.spec
            ps = tgt.cache.page_size
            maxp = tgt.cache.max_pages_per_seq
            s = maxp * ps

            def prog(params, pool_k, pool_v, words, pos, page_tbl):
                tgt._guard().note(words, page_tbl, b=b, n=n,
                                  kind="verify")
                ctx_k = pool_k[:, page_tbl].reshape(
                    spec.num_layers, b, s, spec.num_heads,
                    spec.head_dim,
                )
                ctx_v = pool_v[:, page_tbl].reshape(
                    spec.num_layers, b, s, spec.num_heads,
                    spec.head_dim,
                )
                logits, nk, nv = lmm.lm_decode_chunk(
                    spec, params, words, pos, ctx_k, ctx_v
                )
                idx = pos[:, None] + jnp.arange(n)[None, :]
                pp = jnp.take_along_axis(page_tbl, idx // ps, axis=1)
                pool_k = pool_k.at[:, pp, idx % ps].set(nk)
                pool_v = pool_v.at[:, pp, idx % ps].set(nv)
                logp = lmm.lm_logp(logits)  # [b, n, V]
                gs = jnp.argmax(logp, axis=-1).astype(jnp.int32)
                glp = jnp.take_along_axis(
                    logp, gs[..., None], axis=2
                )[..., 0]
                return pool_k, pool_v, gs, glp

            self._progs[key] = jax.jit(prog, donate_argnums=(1, 2))
        return self._progs[key]

    def generate(self, ids, lens, max_new: int):
        """Speculative greedy KV generation. Returns (tokens
        [B, max_new] int32, scores [B] f32) — token-for-token the
        target PagedLM.generate output."""
        import jax.numpy as jnp

        tgt, drf, kp = self.target, self.draft, self.propose_k
        b = ids.shape[0]
        lens = np.asarray(lens, np.int32)
        eos = tgt.eos_id

        def _prefill(plm):
            bucket = plm.cache.bucket_for(int(lens.max()))
            padded = np.zeros((b, bucket), np.int32)
            padded[:, :min(bucket, ids.shape[1])] = ids[:, :bucket]
            pages = [plm.cache.alloc(bucket // plm.cache.page_size)
                     for _ in range(b)]
            toks, scores = plm.prefill(padded, lens, pages)
            for r, p in enumerate(pages):
                keep = plm.cache.pages_for_len(int(lens[r]))
                if len(p) > keep:
                    plm.cache.free(p[keep:])
                    del p[keep:]
            return (pages, np.array(toks, np.int32),
                    np.array(scores, np.float32))

        t_pages, pending, scores = _prefill(tgt)
        d_pages, _d_toks, _d_sc = _prefill(drf)
        dispatches = 2
        proposed = accepted = 0

        out = np.zeros((b, max_new), np.int32)
        out[:, 0] = pending
        emitted = np.ones((b,), np.int64)
        finished = pending == eos
        pos = lens.astype(np.int64).copy()
        rows = np.arange(b)

        while not (finished | (emitted >= max_new)).all():
            live = ~(finished | (emitted >= max_new))
            rem = int((max_new - emitted[live]).max())
            cap = min(tgt.cache.max_seq_len,
                      drf.cache.max_seq_len) - int(pos.max())
            n = max(1, min(kp, rem, cap))
            # grow both pools to cover pos .. pos+n-1
            grow_to = pos + n - 1
            tgt._grow(t_pages, grow_to)
            drf._grow(d_pages, grow_to)
            d_tbl = _page_table(d_pages,
                                drf.cache.max_pages_per_seq)
            t_tbl = _page_table(t_pages,
                                tgt.cache.max_pages_per_seq)
            # 1 dispatch: draft proposes n tokens, appending to its
            # own pool as it goes
            dk, dv = drf.cache.ensure_pool()
            dk, dv, props = self._propose_program(b, n)(
                drf.params, dk, dv, jnp.asarray(pending),
                jnp.asarray(pos.astype(np.int32)),
                jnp.asarray(d_tbl),
            )
            drf.cache.pool = (dk, dv)
            dispatches += 1
            props_np = np.asarray(props)  # [n, B]
            # 1 dispatch: target verifies all n positions as one
            # chunk, appending to its pool
            words = np.concatenate(
                [pending[None, :], props_np[:n - 1]], axis=0
            ).T.astype(np.int32)  # [B, n]
            tk, tv = tgt.cache.ensure_pool()
            tk, tv, gs, glp = self._verify_program(b, n)(
                tgt.params, tk, tv, jnp.asarray(words),
                jnp.asarray(pos.astype(np.int32)),
                jnp.asarray(t_tbl),
            )
            tgt.cache.pool = (tk, tv)
            tgt.cache.appended_tokens += b * n
            tgt.cache.cached_prefix_tokens += int(pos.sum())
            dispatches += 1
            gs_np = np.asarray(gs)    # [B, n]
            glp_np = np.asarray(glp)  # [B, n]

            proposed += n * int(live.sum())
            for r in rows[live]:
                agree = gs_np[r, :n - 1] == props_np[:n - 1, r]
                mism = np.nonzero(~agree)[0]
                n_acc = int(mism[0]) + 1 if mism.size else n
                take = int(min(n_acc, max_new - emitted[r]))
                for j in range(take):
                    t = gs_np[r, j]
                    if finished[r]:
                        t = eos
                    else:
                        scores[r] += glp_np[r, j]
                    out[r, emitted[r]] = t
                    emitted[r] += 1
                    if t == eos:
                        finished[r] = True
                accepted += take
                pos[r] += n_acc
                pending[r] = gs_np[r, n_acc - 1]

        self.last_chain_depth = dispatches
        self.last_accept_rate = (
            accepted / proposed if proposed else None
        )
        for p in t_pages:
            tgt.cache.free(p)
        for p in d_pages:
            drf.cache.free(p)
        # rows that hit eos stop emitting; the greedy reference keeps
        # emitting eos to max_new, so pad the tails to match it
        for r in rows:
            out[r, emitted[r]:] = eos
        return out, np.asarray(scores, np.float32)
