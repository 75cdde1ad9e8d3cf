"""Host-stepped per-token beam decode — the hook-safe serving rung.

The jitted while-loop decode (`beam_search.BeamSearchDecoder`) runs
user beam hooks (`BeamHooks.adjust/drop/stop`, the reference's
registerBeamSearchControlCallbacks) through `jax.pure_callback` — which
a runtime may reject with UNIMPLEMENTED (the one this rung was written
against did), leaving a hook-bearing generation request with no
accelerator path at all. The installed runtime runs callbacks inside
the jitted loop (chip_smoke.py's generate phase checks it on the chip);
whether this rung stays is ROADMAP Design 5.
This module is the degradation ladder's second rung: one
small jitted program per token step (the step net forward — still on
the accelerator), with the beam expansion, hook calls, and bookkeeping
on the host between steps. Semantics match `_decode_core` exactly —
finished-beam eos-extension, parent-conditioned memory carry, drop
truncation with NEG_INF, stop short-circuit, ties broken toward the
lower flat index like `lax.top_k` — so the two rungs are
interchangeable and only differ in dispatch cost (~1 program per token
instead of 1 per request batch).

Chunked dispatch (ISSUE 18): when the decoder carries
`tokens_per_dispatch=K > 1` and the request has no host callbacks
(adjust/drop/stop all None — a purely-JAX `logprob_fn` is fine, it
compiles into the program), this rung dispatches ONE jitted K-step
chunk program per K tokens (`BeamSearchDecoder._chunk_step_program`)
instead of one step-net program per token, cutting the rung's dispatch
chain from `max_len` to `ceil(max_len/K)`. Per-token `decode.token`
spans become per-chunk `decode.chunk` spans carrying a `tokens` label,
so trace_view critical paths and the serve-row span split stay
reconcilable. Hook-bearing requests keep the per-token path unchanged
— chunking never alters hook call semantics. Both paths record the
measured dispatch count on `dec.last_chain_depth`.
"""

from __future__ import annotations

import numpy as np

from paddle_tpu.beam_search import NEG_INF, BeamHooks
from paddle_tpu.obs import tracing as _tracing


def _step_fn(dec, b):
    """Jitted step-net forward for batch b: (params, static_feed, mems,
    words[B,K]) -> (prob [B*K, V], new mems). Cached on the decoder,
    keyed by (b, k) — the shapes the trace specializes on; jax.jit
    handles static-feed shape retraces within one entry."""
    import jax

    from paddle_tpu.core.arg import Arg

    cache = getattr(dec, "_host_step_cache", None)
    if cache is None:
        cache = dec._host_step_cache = {}
    key = (b, dec.k)
    if key not in cache:
        if len(cache) >= 8:  # same bound as the decode-program cache
            cache.pop(next(iter(cache)))
        net, k = dec._net, dec.k
        memories = dec.memories
        out_name = dec.out_name

        @jax.jit
        def step(params, static_feed, mems, words):
            feed = dict(static_feed)
            feed["@word"] = Arg(ids=words.reshape(b * k))
            for m in memories:
                feed[m["link"]] = Arg(value=mems[m["layer"]])
            outs, _ = net.forward(params, feed, train=False)
            prob = outs[out_name].value
            new_mems = {m["layer"]: outs[m["layer"]].value
                        for m in memories}
            return prob, new_mems

        cache[key] = step
    return cache[key]


def _top_k_stable(flat: np.ndarray, k: int):
    """Row-wise top-k, ties broken toward the LOWER index — the
    `lax.top_k` contract the jitted path relies on for beam order."""
    order = np.argsort(-flat, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(flat, order, axis=1), order


def _chunked_generate(dec, params, static_feed, mems, b, n_chunk):
    """Hook-free chunked host decode: one jitted K-step program per
    chunk, beam bookkeeping (expansion, top-k, memory carry, eos
    masking) INSIDE the program, only the per-substep (word, parent)
    trace and the finished flag coming host-side per chunk. The host
    replays the same seqs-reorder the per-token loop does — substeps
    past all-finished arrive as (word=eos, parent=identity), which
    replays as a no-op, exactly the jitted trace-buffer convention."""
    import jax.numpy as jnp

    k, t_max, eos = dec.k, dec.max_length, dec.eos_id
    words = jnp.full((b, k), dec.bos_id, jnp.int32)
    scores = jnp.full((b, k), NEG_INF, jnp.float32).at[:, 0].set(0.0)
    finished = jnp.zeros((b, k), bool)
    seqs = np.full((b, k, t_max), eos, np.int32)
    rows = np.arange(b)[:, None]
    traced = _tracing.current() is not None

    t0, dispatches = 0, 0
    while t0 < t_max:
        n = min(n_chunk, t_max - t0)  # ragged tail: shorter last chunk
        prog = dec._chunk_step_program(b, n)
        if traced:
            with _tracing.span("decode.chunk", t=t0, tokens=n, batch=b):
                ws, ps, words, scores, finished, mems = prog(
                    params, static_feed, mems, words, scores, finished,
                    jnp.int32(t0),
                )
        else:
            ws, ps, words, scores, finished, mems = prog(
                params, static_feed, mems, words, scores, finished,
                jnp.int32(t0),
            )
        dispatches += 1
        ws_np, ps_np = np.asarray(ws), np.asarray(ps)
        for j in range(n):
            seqs = seqs[rows, ps_np[j]]  # reorder history by parent
            seqs[:, :, t0 + j] = ws_np[j]
        t0 += n
        if np.asarray(finished).all():
            break
    dec.last_chain_depth = dispatches
    dec.last_steps = t0

    is_eos = seqs == eos
    any_eos = np.any(is_eos, axis=-1)
    first_eos = np.argmax(is_eos, axis=-1)
    lens = np.where(any_eos, first_eos + 1, t_max).astype(np.int32)
    return seqs, lens, np.asarray(scores)


def host_generate(dec, params, statics=None, boots=None, batch_size=None,
                  hooks: BeamHooks = None, tokens_per_dispatch=None):
    """Decode with the same inputs/outputs as `dec.generate`, stepping
    the loop from the host so `hooks` run as plain Python — no
    pure_callback, hence viable on runtimes that reject host callbacks.
    Returns (seqs [B, K, max_length] int32, lens [B, K] int32,
    scores [B, K] float32), beams sorted best-first; unwritten steps
    hold eos, matching the jitted program's trace buffers.

    `tokens_per_dispatch` (default: the decoder's own setting) selects
    the chunked path when > 1 and no host callbacks are present."""
    statics = statics or []
    hooks = hooks if hooks is not None else dec.hooks
    static_feed, mems_j, b = dec.prepare(statics, boots, batch_size)
    k, t_max, eos = dec.k, dec.max_length, dec.eos_id

    n_chunk = (tokens_per_dispatch if tokens_per_dispatch is not None
               else getattr(dec, "tokens_per_dispatch", 1))
    hookful = (hooks.adjust is not None or hooks.drop is not None
               or hooks.stop is not None)
    if n_chunk > 1 and not hookful:
        return _chunked_generate(dec, params, static_feed, mems_j, b,
                                 min(n_chunk, t_max))

    step = _step_fn(dec, b)

    mems = mems_j  # device-side between steps; only logits come host
    words = np.full((b, k), dec.bos_id, np.int32)
    scores = np.full((b, k), NEG_INF, np.float32)
    scores[:, 0] = 0.0
    finished = np.zeros((b, k), bool)
    seqs = np.full((b, k, t_max), eos, np.int32)

    # per-token tracing (only when a trace context is attached — the
    # serving scheduler attaches its dispatch span around this call):
    # each token step is one span, so the decode rung's time shows up
    # token-by-token in the request's critical path
    traced = _tracing.current() is not None

    dispatches = 0
    for t in range(t_max):
        if traced:
            with _tracing.span("decode.token", t=t, batch=b):
                prob, new_mems = step(params, static_feed, mems, words)
        else:
            prob, new_mems = step(params, static_feed, mems, words)
        dispatches += 1
        prob = np.asarray(prob)
        v = prob.shape[-1]
        logp = np.log(np.maximum(prob, 1e-20)).reshape(b, k, v)
        if dec.logprob_fn is not None:
            logp = np.asarray(dec.logprob_fn(logp, t), np.float32)
        if hooks.adjust is not None:
            logp = np.asarray(hooks.adjust(logp, t), np.float32)
        # finished beams only extend with eos at no cost
        fin_row = np.full((v,), NEG_INF, np.float32)
        fin_row[eos] = 0.0
        logp = np.where(finished[..., None], fin_row[None, None, :], logp)
        cand = scores[..., None] + logp
        top_scores, top_idx = _top_k_stable(cand.reshape(b, k * v), k)
        parent = (top_idx // v).astype(np.int64)
        word = (top_idx % v).astype(np.int32)

        rows = np.arange(b)[:, None]
        was_fin = finished[rows, parent]
        # parent-conditioned memory carry: a finished parent's state
        # rides through unchanged (the jitted path's `keep` select)
        sel_mems = {}
        for m in dec.memories:
            name = m["layer"]
            new = np.asarray(new_mems[name]).reshape(b, k, -1)
            prev = np.asarray(mems[name]).reshape(b, k, -1)
            sel = np.where(
                was_fin[..., None],
                prev[rows, parent],
                new[rows, parent],
            )
            sel_mems[name] = sel.reshape(b * k, -1)
        mems = sel_mems
        seqs = seqs[rows, parent]  # reorder history by parent beam
        seqs[:, :, t] = word
        new_fin = was_fin | (word == eos)
        scores = top_scores.astype(np.float32)
        if hooks.drop is not None:
            s2, drop_mask = hooks.drop(word.copy(), scores.copy(), t)
            scores = np.asarray(s2, np.float32)
            drop_mask = np.asarray(drop_mask, bool)
            scores = np.where(drop_mask, NEG_INF, scores)
            new_fin = new_fin | drop_mask
        finished = new_fin
        words = word
        if hooks.stop is not None and bool(
            hooks.stop(finished.copy(), scores.copy(), t)
        ):
            break
        if finished.all():
            break
    dec.last_chain_depth = dispatches
    dec.last_steps = dispatches

    is_eos = seqs == eos
    any_eos = np.any(is_eos, axis=-1)
    first_eos = np.argmax(is_eos, axis=-1)
    lens = np.where(any_eos, first_eos + 1, t_max).astype(np.int32)
    return seqs, lens, scores
