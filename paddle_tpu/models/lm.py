"""Decoder-only Transformer LM (ISSUE 19 tentpole): the PAGED SERVER'S
model, and the older of the repo's two decoders.

Beside it since PR 28 stands `models/mellum.py`, a present-day block (RMS
norms, grouped-query attention with rotary positions and a sliding window,
top-k routed experts, a chunked head) that TRAINS at a public model's
widths and has no cache path yet. This one is multi-head attention plus one
relu layer, with no normalisation and no positions: it stays because
`decoding/kv_cache.py`, `serving/lm_engine.py` and their tests compile
their prefill and decode programs against its flat parameters. Porting the
server to the new block (fewer KV heads than query heads in the pool, two
kinds of layer in one allocator) is ROADMAP Reach 2 and 5, not done here.

The LM north star ROADMAP item 1 asks for, built from the layer
inventory that already exists: `embedding` -> N causal
`multi_head_attention` blocks with a relu-fc residual (the exact
block `bench.longctx_conf` measures) -> `fc` head ->
`classification_cost`. `transformer_lm()` returns that ModelConf for
the TRAIN path (Network/Trainer/AMP/donation all apply unchanged).

Generation does NOT run the DSL graph per token — that is the
prefix-recompute decode the PR12 capture verdict condemned (7.7x over
the byte floor, all dispatch chain). Instead this module exposes the
LM's math as pure functions over the SAME flat param dict
`Network.init_params` produces (`_lm_emb.w0`, `_lm_att{i}.wq`, ...),
so `paddle_tpu/decoding/kv_cache.py` can compile the two generation
programs (bucketed prefill + fused per-token decode) against trained
parameters directly:

- `lm_forward(..., with_kv=True)` — full causal forward returning
  per-layer K/V for the prefill program to page out.
- `lm_decode_chunk` — n new tokens against a gathered cache context
  (n=1: the per-token decode step; n=propose_k: the speculative
  verify chunk; rows=B*K: the beam step). Slot s in the gathered
  context IS absolute position s, so the chunk scatters its own new
  K/V into the context before attending — intra-chunk causality for
  free.
- `beam_init_select` / `beam_step_select` — the beam expansion rule,
  shared verbatim by the paged and full-recompute paths so the
  pinned token-for-token equality test compares ONLY the logits
  source (cache vs recompute), never divergent beam semantics.
- `greedy_decode_recompute` / `beam_decode_recompute` — the
  full-recompute references those pins compare against (every step
  re-runs the whole prefix through `lm_forward`).

Analytic accounting mirrors the NMT row's `_nmt_train_flops_per_batch`
pattern: `lm_train_flops_per_batch` feeds the train row's MFU;
`lm_prefix_recompute_bytes_saved` turns the serving engine's MEASURED
cached-prefix-token counters into the bytes a recompute decode would
have streamed (the decode row's `prefix_recompute_bytes_saved` field).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from paddle_tpu.core.config import ModelConf
from paddle_tpu.parallel import ring


@dataclasses.dataclass(frozen=True)
class LMSpec:
    """Static LM architecture — everything the functional forward and
    the compiled generation programs need to agree with the DSL conf.
    attn_impl applies to the FULL-sequence paths (train / prefill /
    recompute reference); the per-token decode step always attends
    densely over the gathered page context (its score matrix is
    [B, 1, S] — there is no [T, T] to remove)."""

    vocab: int = 2048
    d_model: int = 128
    num_heads: int = 4
    num_layers: int = 2
    attn_impl: str = "dense"

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.num_heads == 0
        return self.d_model // self.num_heads


def transformer_lm(spec: LMSpec) -> ModelConf:
    """Trainer config from the existing DSL layer inventory. Teacher
    forcing: `ids` is the BOS-prefixed input, `label` the next-token
    target; the causal mask keeps position t blind to t+1 exactly like
    the generation programs."""
    from paddle_tpu import dsl

    d, h = spec.d_model, spec.num_heads
    with dsl.model() as g:
        ids = dsl.data("ids", dim=(), is_ids=True, is_seq=True)
        lbl = dsl.data("label", dim=(), is_ids=True, is_seq=True)
        x = dsl.embedding(ids, size=d, vocab_size=spec.vocab,
                          name="lm_emb")
        for i in range(spec.num_layers):
            att = dsl._add(
                "multi_head_attention", [x], size=d, num_heads=h,
                causal=True, attn_impl=spec.attn_impl,
                name=f"lm_att{i}",
            )
            x = dsl.addto(att, dsl.fc(att, size=d, act="relu",
                                      name=f"lm_ff{i}"),
                          name=f"lm_blk{i}")
        out = dsl.fc(x, size=spec.vocab, act="", name="lm_head")
        dsl.classification_cost(out, lbl, name="lm_cost")
        g.conf.output_layer_names.append("lm_head")
    return g.conf


def lm_init_params(spec: LMSpec, key) -> dict:
    """Flat param dict via the DSL graph's own initializer — the
    generation programs consume Network-trained params unchanged."""
    from paddle_tpu.network import Network

    return Network(transformer_lm(spec)).init_params(key)


# ---- functional forward (same params, same math) -------------------

def _heads(spec: LMSpec, x):
    return x.reshape(x.shape[0], x.shape[1], spec.num_heads,
                     spec.head_dim)


def _block_tail(spec: LMSpec, params, i: int, att):
    """Post-attention half of block i: wo projection + bias, then the
    addto(att, relu-fc(att)) residual — the longctx block shape."""
    d = spec.d_model
    att = att.reshape(att.shape[0], att.shape[1], d)
    att = jnp.dot(att, params[f"_lm_att{i}.wo"])
    att = att + params[f"_lm_att{i}.wbias"]
    ff = jnp.dot(att, params[f"_lm_ff{i}.w0"])
    ff = jax.nn.relu(ff + params[f"_lm_ff{i}.wbias"])
    return att + ff


def _head_logits(spec: LMSpec, params, x):
    return jnp.dot(x, params["_lm_head.w0"]) + params["_lm_head.wbias"]


def lm_forward(spec: LMSpec, params: dict, ids, lens=None,
               with_kv: bool = False):
    """Full causal forward: ids [B, T] int32 -> logits [B, T, vocab].
    Identical math to the DSL graph at every valid position (pinned by
    tests/test_lm_kv_cache.py). with_kv=True additionally returns the
    per-layer pre-attention K/V stacks [L, B, T, H, hd] — what the
    prefill program pages out."""
    x = jnp.take(params["_lm_emb.w0"], ids, axis=0)
    if lens is not None:
        pos = jnp.arange(ids.shape[1])[None, :]
        x = jnp.where((pos < lens[:, None])[..., None], x, 0.0)
    ks, vs = [], []
    for i in range(spec.num_layers):
        q = _heads(spec, jnp.dot(x, params[f"_lm_att{i}.wq"]))
        k = _heads(spec, jnp.dot(x, params[f"_lm_att{i}.wk"]))
        v = _heads(spec, jnp.dot(x, params[f"_lm_att{i}.wv"]))
        if with_kv:
            ks.append(k)
            vs.append(v)
        if spec.attn_impl == "flash":
            att = ring.flash_dense_attention(q, k, v, causal=True,
                                             kv_len=lens)
        else:
            att = ring.dense_attention(q, k, v, causal=True,
                                       kv_len=lens)
        x = _block_tail(spec, params, i, att)
    logits = _head_logits(spec, params, x)
    if with_kv:
        return logits, jnp.stack(ks), jnp.stack(vs)
    return logits


def chunk_attention(q, ctx_k, ctx_v, start):
    """Attention for a chunk of n NEW tokens at absolute positions
    start[b]..start[b]+n-1 over a gathered cache context whose slot s
    is absolute position s (the chunk's own K/V already scattered in).
    q [B, n, H, hd], ctx [B, S, H, hd], start [B] int32. Query j may
    see slots s <= start[b] + j; everything else (unwritten pages,
    stale speculative entries, padding slots) is masked to NEG_INF —
    the same mask/scale/softmax conventions as ring.dense_attention,
    so the paged path is token-identical to the full recompute."""
    hd = q.shape[-1]
    scale = 1.0 / jnp.sqrt(hd).astype(q.dtype)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, ctx_k) * scale
    qpos = start[:, None] + jnp.arange(q.shape[1])[None, :]  # [B, n]
    kpos = jnp.arange(ctx_k.shape[1])  # [S]
    bad = kpos[None, None, :] > qpos[:, :, None]  # [B, n, S]
    s = s + jnp.where(bad[:, None, :, :], ring.NEG_INF, 0.0)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, ctx_v)


def lm_decode_chunk(spec: LMSpec, params: dict, toks, start,
                    ctx_k, ctx_v):
    """Forward n new tokens against a gathered cache context — the
    shared core of the per-token decode step (n=1), the speculative
    verify chunk (n=propose_k), and the beam step (rows flattened to
    B*K). toks [B, n] int32, start [B] int32 (absolute position of
    toks[:, 0]), ctx [L, B, S, H, hd] gathered from the page pool
    BEFORE this chunk's writes. Returns (logits [B, n, vocab],
    new_k [L, B, n, H, hd], new_v) — the caller scatters new_k/new_v
    into the pool at the same absolute slots this function wrote them
    into the context."""
    b, n = toks.shape
    x = jnp.take(params["_lm_emb.w0"], toks, axis=0)
    idx = start[:, None] + jnp.arange(n)[None, :]  # [B, n] abs slots
    rows = jnp.arange(b)[:, None]
    new_ks, new_vs = [], []
    for i in range(spec.num_layers):
        q = _heads(spec, jnp.dot(x, params[f"_lm_att{i}.wq"]))
        kn = _heads(spec, jnp.dot(x, params[f"_lm_att{i}.wk"]))
        vn = _heads(spec, jnp.dot(x, params[f"_lm_att{i}.wv"]))
        new_ks.append(kn)
        new_vs.append(vn)
        ck = ctx_k[i].at[rows, idx].set(kn)
        cv = ctx_v[i].at[rows, idx].set(vn)
        att = chunk_attention(q, ck, cv, start)
        x = _block_tail(spec, params, i, att)
    logits = _head_logits(spec, params, x)
    return logits, jnp.stack(new_ks), jnp.stack(new_vs)


def lm_logp(logits):
    """f32 log-softmax — score math stays f32 regardless of AMP, the
    same pinned-accumulator rule as the beam decoder."""
    return jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)


# ---- beam expansion rule (shared by paged + recompute paths) -------

def beam_init_select(logp0, k: int):
    """First expansion from the prompt's next-token distribution:
    logp0 [B, vocab] -> (scores [B, k], tokens [B, k])."""
    scores, tokens = jax.lax.top_k(logp0, k)
    return scores, tokens.astype(jnp.int32)


def beam_step_select(scores, logp, finished, eos_id: int):
    """One beam expansion: scores [B, K] f32, logp [B, K, vocab] f32,
    finished [B, K] bool -> (scores, parent, token, finished), each
    [B, K]. A finished beam contributes exactly one candidate — eos at
    its frozen score — so it survives top-k without growing."""
    b, k, v = logp.shape
    live = scores[..., None] + logp
    fin = jnp.full_like(logp, ring.NEG_INF).at[..., eos_id].set(
        scores
    )
    cand = jnp.where(finished[..., None], fin, live)
    top, idx = jax.lax.top_k(cand.reshape(b, k * v), k)
    parent = (idx // v).astype(jnp.int32)
    token = (idx % v).astype(jnp.int32)
    was_fin = jnp.take_along_axis(finished, parent, axis=1)
    return top, parent, token, was_fin | (token == eos_id)


# ---- full-recompute references (what the pins compare against) -----

def _last_logp(spec, params, buf, lens):
    logits = lm_forward(spec, params, buf, lens=lens)
    last = jnp.take_along_axis(
        logits, (lens - 1)[:, None, None], axis=1
    )[:, 0, :]
    return lm_logp(last)


# jitting a fresh lambda per decode call would re-trace every call —
# the recompute arm of the bench A/B must be as warm as the paged arm,
# so the step program is cached per spec (bounded; specs are frozen
# dataclasses, hence hashable)
_RECOMPUTE_PROGS: dict = {}
_MAX_RECOMPUTE_PROGS = 8


def _recompute_step(spec):
    fn = _RECOMPUTE_PROGS.get(spec)
    if fn is None:
        if len(_RECOMPUTE_PROGS) >= _MAX_RECOMPUTE_PROGS:
            _RECOMPUTE_PROGS.pop(next(iter(_RECOMPUTE_PROGS)))
        fn = jax.jit(lambda p, bf, ln: _last_logp(spec, p, bf, ln))
        _RECOMPUTE_PROGS[spec] = fn
    return fn


def greedy_decode_recompute(spec: LMSpec, params: dict, ids, lens,
                            max_new: int, eos_id: int):
    """The decode the PR12 verdict condemned: every new token re-runs
    the FULL prefix through lm_forward. ids [B, T0] int32 (padded),
    lens [B] int32. Returns (tokens [B, max_new] int32, scores [B]
    f32) — the token-for-token reference for the paged path."""
    import numpy as np

    b, t0 = ids.shape
    buf = np.zeros((b, t0 + max_new), np.int32)
    buf[:, :t0] = np.asarray(ids)
    lens = np.asarray(lens).astype(np.int32).copy()
    step = _recompute_step(spec)
    out = np.zeros((b, max_new), np.int32)
    scores = np.zeros((b,), np.float32)
    finished = np.zeros((b,), bool)
    for t in range(max_new):
        logp = np.asarray(step(params, jnp.asarray(buf),
                               jnp.asarray(lens)))
        tok = logp.argmax(axis=-1).astype(np.int32)
        tok = np.where(finished, eos_id, tok)
        scores = np.where(
            finished, scores,
            scores + logp[np.arange(b), tok],
        ).astype(np.float32)
        out[:, t] = tok
        buf[np.arange(b), lens] = tok
        lens += 1
        finished |= tok == eos_id
    return out, scores


def beam_decode_recompute(spec: LMSpec, params: dict, ids, lens,
                          beam_k: int, max_new: int, eos_id: int):
    """Full-recompute beam search under the shared expansion rule.
    Returns (tokens [B, K, max_new] int32, scores [B, K] f32)."""
    import numpy as np

    b, t0 = ids.shape
    k = beam_k
    ids_np = np.asarray(ids)
    lens_np = np.asarray(lens).astype(np.int32)
    init = _recompute_step(spec)
    logp0 = np.asarray(init(params, jnp.asarray(ids_np),
                            jnp.asarray(lens_np)))
    sc, tok = beam_init_select(jnp.asarray(logp0), k)
    scores = np.asarray(sc)
    hist = np.zeros((b, k, max_new), np.int32)
    hist[:, :, 0] = np.asarray(tok)
    finished = hist[:, :, 0] == eos_id

    buf = np.zeros((b, k, t0 + max_new), np.int32)
    buf[:, :, :t0] = ids_np[:, None, :]
    rows = np.arange(b)[:, None], np.arange(k)[None, :]
    buf[rows[0], rows[1], lens_np[:, None]] = hist[:, :, 0]
    blens = np.broadcast_to(lens_np[:, None] + 1, (b, k)).copy()

    flat = _recompute_step(spec)
    for t in range(1, max_new):
        logp = np.asarray(flat(
            params, jnp.asarray(buf.reshape(b * k, -1)),
            jnp.asarray(blens.reshape(b * k)),
        )).reshape(b, k, -1)
        sc, parent, tok, fin = beam_step_select(
            jnp.asarray(scores), jnp.asarray(logp),
            jnp.asarray(finished), eos_id,
        )
        scores = np.asarray(sc)
        parent_np = np.asarray(parent)
        tok_np = np.asarray(tok)
        finished = np.asarray(fin)
        gi = np.arange(b)[:, None]
        hist = hist[gi, parent_np]
        buf = buf[gi, parent_np]
        blens = blens[gi, parent_np]
        hist[:, :, t] = tok_np
        buf[rows[0], rows[1], blens] = tok_np
        blens += 1
    return hist, scores


# ---- analytic accounting (the _nmt_train_flops pattern) ------------

def lm_train_flops_per_batch(spec: LMSpec, bs: int, t: int) -> int:
    """Model FLOPs per optimizer step (2/MAC, train ~ 3x fwd — the
    same conventions as _nmt_train_flops_per_batch / _longctx_flops):
    per layer QKVO projections + the [T,T] score/value matmuls (full
    square for both attn impls) + the d->d relu fc, plus the vocab
    head."""
    d, l = spec.d_model, spec.num_layers
    per_layer = (
        4 * 2 * bs * t * d * d          # wq/wk/wv/wo
        + 2 * 2 * bs * t * t * d        # QK^T and attn@V
        + 2 * bs * t * d * d            # residual fc
    )
    head = 2 * bs * t * d * spec.vocab
    return 3 * (l * per_layer + head)


def lm_param_bytes(spec: LMSpec, dtype_bytes: int = 4) -> int:
    d, l, v = spec.d_model, spec.num_layers, spec.vocab
    n = v * d                            # embedding
    n += l * (4 * d * d + d)             # attention (+ bias)
    n += l * (d * d + d)                 # residual fc
    n += d * v + v                       # head
    return n * dtype_bytes


def lm_prefix_token_recompute_bytes(spec: LMSpec,
                                    dtype_bytes: int = 4) -> int:
    """HBM bytes a full-recompute decode streams PER PREFIX TOKEN per
    step that the paged cache avoids: re-embedding plus the per-layer
    activation round trips (x in, q/k/v/att/ff out-and-in) of pushing
    one already-seen token back through every block. Weight streaming
    is excluded on purpose — both paths read the weights once per
    step, so it cancels in the saved-bytes accounting."""
    d, l = spec.d_model, spec.num_layers
    per_layer = 8 * d * dtype_bytes      # x,q,k,v,att,wo-out,ff,res
    return d * dtype_bytes + l * per_layer


def lm_prefix_recompute_bytes_saved(spec: LMSpec,
                                    cached_prefix_tokens: int,
                                    dtype_bytes: int = 4) -> int:
    """Turn the engine's MEASURED counter (sum over decode dispatches
    of the prefix tokens served from the page pool) into the bytes a
    recompute decode would have streamed for those same tokens."""
    return int(cached_prefix_tokens) * lm_prefix_token_recompute_bytes(
        spec, dtype_bytes
    )
