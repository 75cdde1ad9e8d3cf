"""Laguna-XS.2 (poolside, `model_type` `laguna`), one chip's share of it, in
plain jax.numpy: loss and, through `jax.grad`, gradients.

A pre-norm decoder. For block `i` on `x` [T, hidden], every norm an RMS norm
with a weight, RMS(x) = w x / sqrt(mean(x^2) + eps); `H_i` =
`num_attention_heads_per_layer[i]` query heads on `num_key_value_heads` KV
heads of `head_dim`:

    a   = RMS1(x)
    q   = a Wq (H_i heads), k = a Wk, v = a Wv (KV heads);  g = sigmoid(a Wg)
          [T, H_i], float32: ONE scalar a head and token
    rotary on the leading r = `partial_rotary_factor` x head_dim dims of each
      head of q and k, half-split pairs (j, j + r/2), inv_freq_j =
      theta^(-2j/r); the other head_dim - r dims untouched. A group of
      `rope_type: "yarn"` blends the frequencies (`yarn_range` over r) and
      multiplies cos and sin by `attention_factor`
    query head h attends KV head h // (H_i / KV); scores / sqrt(head_dim);
      position t sees u <= t, on a `sliding_attention` layer also t - u <
      `sliding_window` (the query itself included)
    o_h = softmax(s_h) v;  x1 = x + concat_h(g_h * o_h) Wo
    b   = RMS2(x1)
    `mlp_layer_types[i]` "dense":  f = (silu(b Wgate) * (b Wup)) Wdown
    else: p = softmax(b Wr) over ALL routed experts, in float32
          S = the top-k of p;  w_e = scale * p_e / sum_S p
          f = sum over e in S AND held here of w_e E_e(b)  +  E_shared(b),
          E = a gated MLP as above at the experts' width
    x   = x1 + f

then a final RMS, the untied head over the vocabulary slice held here, and
the mean next-token cross-entropy over the real positions.

The share (`model-configs` guide, section 4): the router keeps its published
width and its experts per token; experts `experts_held_first` ..
`+ num_experts` are held, and what the absent experts would add is left out;
attention, the dense layer and the shared expert are what every chip of the
layer computes alike; ids, logits and loss are over the vocabulary slice.
The same function given all the experts and the whole vocabulary is the
uncut model. The per-layer lists of the configuration may be the published
ones, whole: the first `num_hidden_layers` entries are the layers held.

Departures from the published description, each also under `assumed` in the
configuration's file: the gate's form (the config says `gating: true`), no
QK-norm, softmax-then-top-k renormalised and scaled on the expert's output,
an ungated and unscaled shared expert, no auxiliary loss; router logits,
softmax and top-k in float32 whatever the mode.

Float32 throughout, `highest` matmul precision; `mode` is the precision of
matmul operands and of each sub-layer's output (reference/precision.py). So
that float32 fits one chip at 8,192 positions beside a trainer's five copies
of the weights, each layer is rematerialised in the backward pass, attention
goes a head and a block of queries at a time against the one span of keys
its mask can reach, the feed-forward blocks a chunk of rows at a time, the
experts one at a time, the head and its cost in row chunks: that changes
what is stored, not what is computed. Dense masks, a loop over experts, no
kernel. Imports nothing of the program; the parameter names and shapes are
the ones the program's graph gives its layers, since the benchmark hands one
set of seeded weights to both.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference import precision as P
# the plain float32 pieces this decoder shares with its siblings, letter for
# letter: the RMS norm, the gated MLP in row chunks, the head's cost in row
# chunks; YaRN's blend range and frequencies over a width (here the rotary
# width). A reference imports nothing of the PROGRAM
from benchmarks.reference.kimi import gated_mlp, rms, token_costs  # noqa: F401
from benchmarks.reference.mellum import inv_freq, yarn_range  # noqa: F401

QUERY_BLOCK = 1024       # queries of one head scored at once


def n_layers(cfg) -> int:
    return int(cfg["num_hidden_layers"])


def router_width(cfg) -> int:
    """Experts the router chooses among: the published count."""
    return int(cfg.get("router_experts", cfg["num_experts"]))


def is_dense(cfg, i) -> bool:
    return cfg["mlp_layer_types"][i] == "dense"


def heads_of(cfg, i) -> int:
    return int(cfg["num_attention_heads_per_layer"][i])


def window_of(cfg, i):
    """The layer's window, None on a full layer."""
    return (int(cfg["sliding_window"])
            if cfg["layer_types"][i] == "sliding_attention" else None)


def shared_width(cfg) -> int:
    return int(cfg.get("shared_expert_intermediate_size", 0))


def param_spec(cfg) -> dict:
    """name -> (shape, ("normal", std) | ("const", value))."""
    d, hd, kv = (cfg["hidden_size"], cfg["head_dim"],
                 cfg["num_key_value_heads"])
    e, f, v = (cfg["num_experts"], cfg["moe_intermediate_size"],
               cfg["vocab_size"])
    std = cfg["init_std"]
    proj, mlp = ("normal", std["projection"]), ("normal", std["mlp"])
    spec = {"_emb.w0": ((v, d), ("normal", std["embedding"]))}
    for i in range(n_layers(cfg)):
        h = heads_of(cfg, i)
        spec[f"_l{i}_norm1.w0"] = ((d,), ("const", 1.0))
        spec[f"_l{i}_attn.wq"] = ((d, h * hd), proj)
        spec[f"_l{i}_attn.wk"] = ((d, kv * hd), proj)
        spec[f"_l{i}_attn.wv"] = ((d, kv * hd), proj)
        spec[f"_l{i}_attn.wo"] = ((h * hd, d), proj)
        if cfg.get("gating"):
            spec[f"_l{i}_attn.wg"] = ((d, h), ("normal", std["gate"]))
        spec[f"_l{i}_norm2.w0"] = ((d,), ("const", 1.0))
        name, width = (("mlp", cfg["intermediate_size"]) if is_dense(cfg, i)
                       else ("shared", shared_width(cfg)))
        if width:
            spec[f"_l{i}_{name}.w_gate"] = ((d, width), mlp)
            spec[f"_l{i}_{name}.w_up"] = ((d, width), mlp)
            spec[f"_l{i}_{name}.w_down"] = ((width, d), mlp)
        if not is_dense(cfg, i):
            ex = ("normal", std["expert"])
            spec[f"_l{i}_moe.router"] = ((d, router_width(cfg)),
                                         ("normal", std["router"]))
            spec[f"_l{i}_moe.w_gate"] = ((e, d, f), ex)
            spec[f"_l{i}_moe.w_up"] = ((e, d, f), ex)
            spec[f"_l{i}_moe.w_down"] = ((e, f, d), ex)
    spec["_final_norm.w0"] = ((d,), ("const", 1.0))
    spec["_head.w0"] = ((d, v), ("normal", std["head"]))
    return spec


# ---- rotary positions ----

def rotary_width(head_dim, rope) -> int:
    return int(head_dim * rope.get("partial_rotary_factor", 1))


def rotary(x, rope):
    """x [B, T, n, head_dim]: of the leading r dims pair (j, j + r/2) is
    turned by t * freq_j; the rest of the head is passed on as it is."""
    r = rotary_width(x.shape[-1], rope)
    freq, factor = inv_freq(r, rope)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq[None, :]
    cos = (jnp.cos(ang) * factor)[None, :, None, :]
    sin = (jnp.sin(ang) * factor)[None, :, None, :]
    a, b, rest = x[..., : r // 2], x[..., r // 2: r], x[..., r:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1)


# ---- the layer ----

def attend(q, k, v, window, mode, block=QUERY_BLOCK):
    """Causal softmax attention, q [B, T, H, D], k and v [B, T, KV, D] ->
    [B, T, H, D]: a head and a block of queries at a time, under a dense
    mask, against the span of keys the block can reach: all of them on a
    full layer, the block's own positions and the window before them on a
    window layer."""
    b, t, h, d = q.shape
    group = h // k.shape[2]
    block = math.gcd(t, block)
    nq = t // block
    span = t if window is None else min(t, block + window)
    ks, vs = jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)   # [KV, B, T, D]

    def one_head(args):
        qh, at = args                             # [B, T, D], the head
        kh, vh = ks[at // group], vs[at // group]

        @jax.checkpoint
        def one_block(args):
            qb, q0 = args                         # [B, block, D]
            k0 = jnp.clip(q0 + block - span, 0, t - span)
            kb = lax.dynamic_slice_in_dim(kh, k0, span, axis=1)
            vb = lax.dynamic_slice_in_dim(vh, k0, span, axis=1)
            s = jnp.einsum("bid,bjd->bij", P.operand(qb, mode),
                           P.operand(kb, mode),
                           precision=lax.Precision.HIGHEST) / math.sqrt(d)
            at_q = (q0 + jnp.arange(block))[:, None]
            at_k = (k0 + jnp.arange(span))[None, :]
            m = at_k <= at_q
            if window is not None:
                m = m & (at_q - at_k < window)
            w = jax.nn.softmax(jnp.where(m[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("bij,bjd->bid", P.operand(w, mode),
                              P.operand(vb, mode),
                              precision=lax.Precision.HIGHEST)

        qs = jnp.moveaxis(qh.reshape(b, nq, block, d), 1, 0)
        o = lax.map(one_block, (qs, jnp.arange(nq) * block))
        return jnp.moveaxis(o, 0, 1).reshape(b, t, -1)

    o = lax.map(one_head, (jnp.moveaxis(q, 2, 0), jnp.arange(h)))
    return jnp.moveaxis(o, 0, 2)                  # [B, T, H, D]


def gate_of(cfg, p, i, a, mode):
    """[B, T, H_i] float32: sigmoid of a linear map of the block's normed
    input, one scalar a head and token."""
    return jax.nn.sigmoid(P.dot(a, p[f"_l{i}_attn.wg"], mode))


def attention(cfg, p, i, a, mode):
    b, t, _ = a.shape
    name = f"l{i}_attn"
    h, kv, hd = heads_of(cfg, i), cfg["num_key_value_heads"], cfg["head_dim"]
    rope = cfg["rope_parameters"][cfg["layer_types"][i]]
    q = P.act(P.dot(a, p[f"_{name}.wq"], mode), mode).reshape(b, t, h, hd)
    k = P.act(P.dot(a, p[f"_{name}.wk"], mode), mode).reshape(b, t, kv, hd)
    v = P.act(P.dot(a, p[f"_{name}.wv"], mode), mode).reshape(b, t, kv, hd)
    q, k = P.act(rotary(q, rope), mode), P.act(rotary(k, rope), mode)
    o = P.act(attend(q, k, v, window_of(cfg, i), mode), mode)
    if cfg.get("gating"):
        o = P.act(o * gate_of(cfg, p, i, a, mode)[..., None], mode)
    return P.act(P.dot(o.reshape(b, t, h * hd), p[f"_{name}.wo"], mode), mode)


def route(cfg, p, name, x, mode):
    """-> ([N, router width] float32: each token's weight on each expert,
    zero outside its top-k; [N, k] int32: the experts chosen)."""
    logits = P.dot(x, p[f"_{name}.router"], mode).astype(jnp.float32)
    prob = jax.nn.softmax(logits, axis=-1)
    top, idx = lax.top_k(prob, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        top = top / jnp.sum(top, -1, keepdims=True)
    top = top * cfg.get("moe_routed_scaling_factor", 1.0)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(prob).at[rows, idx].set(top), idx


def experts(cfg, p, name, x, mode):
    """The held experts' part of the layer's result for x [N, hidden]."""
    first = int(cfg.get("experts_held_first", 0))
    gates, _ = route(cfg, p, name, x, mode)
    gates = gates[:, first: first + cfg["num_experts"]]

    @jax.checkpoint
    def one_expert(acc, ew):
        wg, wu, wd, g = ew
        hid = P.act(jax.nn.silu(P.dot(x, wg, mode)) * P.dot(x, wu, mode),
                    mode)
        return acc + g[:, None] * P.dot(hid, wd, mode), None

    acc, _ = lax.scan(one_expert, jnp.zeros_like(x),
                      (p[f"_{name}.w_gate"], p[f"_{name}.w_up"],
                       p[f"_{name}.w_down"], gates.T))
    return P.act(acc, mode)


def attention_half(cfg, p, i, x, mode):
    """-> (x1, b): the residual stream after attention, and the
    feed-forward block's input RMS2(x1) as rows [tokens, hidden]."""
    eps = cfg["rms_norm_eps"]
    a = P.act(rms(x, p[f"_l{i}_norm1.w0"], eps), mode)
    x1 = P.act(x + attention(cfg, p, i, a, mode), mode)
    b = P.act(rms(x1, p[f"_l{i}_norm2.w0"], eps), mode)
    return x1, b.reshape(b.shape[0] * b.shape[1], -1)


def feed_forward(cfg, p, i, b, mode):
    if is_dense(cfg, i):
        return gated_mlp(p, f"l{i}_mlp", b, mode)
    y = experts(cfg, p, f"l{i}_moe", b, mode)
    if shared_width(cfg):
        y = y + gated_mlp(p, f"l{i}_shared", b, mode)
    return y


def layer(cfg, p, i, x, mode):
    x1, b = attention_half(cfg, p, i, x, mode)
    return P.act(x1 + feed_forward(cfg, p, i, b, mode).reshape(x1.shape),
                 mode)


def hidden(cfg, p, ids, mode):
    """ids [B, T] -> the final norm's output [B, T, hidden]."""
    x = P.act(p["_emb.w0"][ids], mode)
    for i in range(n_layers(cfg)):
        x = jax.checkpoint(lambda x, i=i: layer(cfg, p, i, x, mode))(x)
    return P.act(rms(x, p["_final_norm.w0"], cfg["rms_norm_eps"]), mode)


def chosen(cfg, p, ids, mode):
    """[expert layers, tokens, top-k] int32, sorted: the experts each
    token's router takes in each expert layer, forward only. For reading how
    many selections another precision flips."""
    out = []
    x = P.act(p["_emb.w0"][ids], mode)
    with jax.default_matmul_precision("highest"):
        for i in range(n_layers(cfg)):
            x1, b = attention_half(cfg, p, i, x, mode)
            if not is_dense(cfg, i):
                out.append(jnp.sort(route(cfg, p, f"l{i}_moe", b, mode)[1],
                                    axis=-1))
            x = P.act(x1 + feed_forward(cfg, p, i, b, mode).reshape(x1.shape),
                      mode)
    return jnp.stack(out).astype(jnp.int32)


def loss(cfg, p, batch, mode="f32"):
    """Mean next-token cross-entropy over the real positions. `batch`: ids
    and label [B, T] int32, lens [B]."""
    ids, labels, lens = batch["ids"], batch["label"], batch["lens"]
    b, t = ids.shape
    with jax.default_matmul_precision("highest"):
        x = hidden(cfg, p, ids, mode)
        per = token_costs(p["_head.w0"], x.reshape(b * t, -1),
                          labels.reshape(b * t), mode)
    real = (jnp.arange(t)[None, :] < lens[:, None]).reshape(b * t)
    return jnp.sum(jnp.where(real, per, 0.0)) / jnp.sum(lens)


# ---- operations, from the configuration and the traffic alone ----

def attended_keys(t, window=None) -> int:
    """Keys a query attends, summed over t positions: the causal triangle,
    cut to the window."""
    w = min(window or t, t)
    return w * (w + 1) // 2 + (t - w) * w


def forward_flops_per_token(cfg, t) -> dict:
    """Forward FLOPs a token, by part, at sequence length t: attention's
    projections (the gate's among them), its scores and values over the keys
    really attended (a window layer its window, not the square), the dense
    layers, the shared experts, the routed experts held (the expected share
    of the top-k that falls on them), the router, the head."""
    d, hd, kv = (cfg["hidden_size"], cfg["head_dim"],
                 cfg["num_key_value_heads"])
    f = cfg["moe_intermediate_size"]
    held = (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / router_width(cfg))
    out = {"projections": 0.0, "attention": 0.0, "dense": 0.0, "shared": 0.0,
           "experts": 0.0, "router": 0.0}
    for i in range(n_layers(cfg)):
        h = heads_of(cfg, i)
        out["projections"] += 2 * d * (2 * h * hd + 2 * kv * hd
                                       + (h if cfg.get("gating") else 0))
        out["attention"] += 4 * h * hd * attended_keys(t, window_of(cfg, i)) / t
        if is_dense(cfg, i):
            out["dense"] += 3 * 2 * d * cfg["intermediate_size"]
        else:
            out["shared"] += 3 * 2 * d * shared_width(cfg)
            out["experts"] += held * 3 * 2 * d * f
            out["router"] += 2 * d * router_width(cfg)
    out["head"] = 2 * d * cfg["vocab_size"]
    return out


def train_flops_per_row(cfg, t) -> float:
    """A row is a token: forward + backward = 3 x forward; recomputation
    is not counted (it is the program's choice, not the model's work)."""
    return 3.0 * sum(forward_flops_per_token(cfg, t).values())
