"""LFM2-8B-A1B (LiquidAI, `model_type` `lfm2_moe`), one chip's share of it, in
plain jax.numpy: loss and, through `jax.grad`, gradients.

A pre-norm hybrid decoder. For the published layer `l` on `u` [T, hidden],
every norm an RMS norm with a weight, RMS(x) = w x / sqrt(mean(x^2) + eps),
eps = `norm_eps`:

    h = u + Mixer_l(RMS1(u));  out = h + FFN_l(RMS2(h))

`Mixer_l`, `layer_types[l]` "conv" (the gated short convolution, length L =
`conv_L_cache`, no bias), on a = RMS1(u):
    [B | C | x] = a W_in, split in that order;  z = B * x
    s_t = sum_{j=0..L-1} w[:, j] z_{t-(L-1)+j}, zero before the row's start
    Mixer = (C * s) W_out

`layer_types[l]` "full_attention": H query heads on KV heads of hd =
hidden / H:
    q = a Wq, k = a Wk, v = a Wv;  each head's q and k through an RMS norm
      over its hd lanes (weights q_norm, k_norm [hd]) BEFORE the rotary
      positions (theta `rope_theta`, the whole head, half-split pairs)
    query head h attends KV head h // (H / KV); scores / sqrt(hd); causal
    Mixer = concat_h(softmax(s_h) v) Wo

`FFN_l`, `l < num_dense_layers`: (silu(b Wg) * (b Wu)) Wd of
`intermediate_size`. After that the routed experts alone (no shared one):
    s = sigmoid(b Wr) over ALL routed experts, in float32
    S = the top-k of s + bias (`use_expert_bias`: the selection bias chooses
        and does not weigh; no gradient reaches it)
    w_e = s_e / (sum_S s + 1e-20) * routed_scaling_factor
    FFN = sum over e in S AND held here of w_e (silu(b Wg_e) * (b Wu_e)) Wd_e

then a final RMS, the head TIED to the embedding (logits = x E^T over the
vocabulary slice held here, one leaf), and the mean next-token cross-entropy
over the real positions.

The share (`model-configs` guide, section 4): the graph holds the published
layers `layers_held`; the router keeps its published width and its experts
per token; experts `experts_held_first` .. `+ num_experts` are held, and what
the absent experts would add is left out; the mixers and the dense layer are
what every chip of the layer computes alike; ids, logits and loss are over
the vocabulary slice. The same function given all the layers, all the
experts and the whole vocabulary is the uncut model.

Departures from the published description, each also under `assumed` in the
configuration's file: the renormalisation's guard is 1e-20 where the family
adds 1e-6 (below 1e-6 relative over four sigmoid scores); rotary pairs are
half-split; the selection bias is a constant (its balancing update has no
key); no auxiliary loss; router logits, scores and top-k in float32 whatever
the mode.

Float32 throughout, `highest` matmul precision; `mode` is the precision of
matmul operands and of each sub-layer's output (reference/precision.py). So
that float32 fits one chip at 8,192 positions beside a trainer's five copies
of the weights, each layer is rematerialised in the backward pass, attention
goes a head and a block of queries at a time, the feed-forward blocks a chunk
of rows at a time, the experts one at a time, the head and its cost in row
chunks: that changes what is stored, not what is computed. Dense masks, a
loop over experts, no kernel. Imports nothing of the program; the parameter
names and shapes are the ones the program's graph gives its layers, since
the benchmark hands one set of seeded weights to both.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference import precision as P
# the plain float32 pieces this decoder shares with its siblings, letter for
# letter: the RMS norm, the gated MLP in row chunks, the head's cost in row
# chunks, whole-head rotary, the sigmoid router with its selection bias, and
# grouped causal attention a head and a block of queries at a time. A
# reference imports nothing of the PROGRAM
from benchmarks.reference.kimi import (gated_mlp, rms, rotary, route,
                                       token_costs)
from benchmarks.reference.laguna import attend


def layers_held(cfg) -> list:
    """The published indices of the layers held, in order."""
    return list(cfg.get("layers_held", range(cfg["num_hidden_layers"])))


def router_width(cfg) -> int:
    """Experts the router chooses among: the published count."""
    return int(cfg.get("router_experts", cfg["num_experts"]))


def is_dense(cfg, l) -> bool:
    return l < int(cfg.get("num_dense_layers", 0))


def is_conv(cfg, l) -> bool:
    return cfg["layer_types"][l] == "conv"


def head_dim(cfg) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def param_spec(cfg) -> dict:
    """name -> (shape, ("normal", std) | ("const", value))."""
    d, h, kv = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    hd, e, f = head_dim(cfg), cfg["num_experts"], cfg["moe_intermediate_size"]
    std = cfg["init_std"]
    proj = ("normal", std["projection"])
    spec = {"_emb.w0": ((cfg["vocab_size"], d),
                        ("normal", std["embedding"]))}
    for l in layers_held(cfg):
        spec[f"_l{l}_norm1.w0"] = ((d,), ("const", 1.0))
        if is_conv(cfg, l):
            spec[f"_l{l}_conv.w_in"] = ((d, 3 * d), proj)
            spec[f"_l{l}_conv.conv_w"] = ((d, cfg["conv_L_cache"]),
                                          ("normal", std["conv"]))
            spec[f"_l{l}_conv.w_out"] = ((d, d), proj)
        else:
            spec[f"_l{l}_attn.wq"] = ((d, h * hd), proj)
            spec[f"_l{l}_attn.wk"] = ((d, kv * hd), proj)
            spec[f"_l{l}_attn.wv"] = ((d, kv * hd), proj)
            spec[f"_l{l}_attn.wo"] = ((h * hd, d), proj)
            spec[f"_l{l}_attn.q_norm"] = ((hd,), ("const", 1.0))
            spec[f"_l{l}_attn.k_norm"] = ((hd,), ("const", 1.0))
        spec[f"_l{l}_norm2.w0"] = ((d,), ("const", 1.0))
        if is_dense(cfg, l):
            mlp, width = ("normal", std["mlp"]), cfg["intermediate_size"]
            spec[f"_l{l}_mlp.w_gate"] = ((d, width), mlp)
            spec[f"_l{l}_mlp.w_up"] = ((d, width), mlp)
            spec[f"_l{l}_mlp.w_down"] = ((width, d), mlp)
        else:
            ex = ("normal", std["expert"])
            spec[f"_l{l}_moe.router"] = ((d, router_width(cfg)),
                                         ("normal", std["router"]))
            if cfg.get("use_expert_bias"):
                spec[f"_l{l}_moe.e_score_correction_bias"] = (
                    (router_width(cfg),), ("normal", std["router_bias"]))
            spec[f"_l{l}_moe.w_gate"] = ((e, d, f), ex)
            spec[f"_l{l}_moe.w_up"] = ((e, d, f), ex)
            spec[f"_l{l}_moe.w_down"] = ((e, f, d), ex)
    spec["_final_norm.w0"] = ((d,), ("const", 1.0))
    return spec


# ---- the mixers ----

def short_conv(cfg, p, l, a, mode):
    """The gated short convolution on a [B, T, hidden], as its equations
    read: s_t a sum over the L positions up to t, zeros before the row."""
    name, d, t = f"_l{l}_conv", a.shape[-1], a.shape[1]
    bcx = P.act(P.dot(a, p[f"{name}.w_in"], mode), mode)
    b_, c_, x_ = bcx[..., :d], bcx[..., d: 2 * d], bcx[..., 2 * d:]
    z = b_ * x_
    w = P.operand(p[f"{name}.conv_w"], mode)
    big_l = w.shape[-1]
    s = jnp.zeros_like(z)
    for j in range(big_l):
        back = big_l - 1 - j                      # z_{t - back}
        shifted = jnp.concatenate(
            [jnp.zeros_like(z[:, :back]), z[:, : t - back]], axis=1)
        s = s + w[:, j] * shifted
    y = P.act(c_ * s, mode)
    return P.act(P.dot(y, p[f"{name}.w_out"], mode), mode)


def qk_norm_attention(cfg, p, l, a, mode):
    b, t, _ = a.shape
    name = f"_l{l}_attn"
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 head_dim(cfg))
    eps = cfg["norm_eps"]
    q = P.act(P.dot(a, p[f"{name}.wq"], mode), mode).reshape(b, t, h, hd)
    k = P.act(P.dot(a, p[f"{name}.wk"], mode), mode).reshape(b, t, kv, hd)
    v = P.act(P.dot(a, p[f"{name}.wv"], mode), mode).reshape(b, t, kv, hd)
    q = P.act(rms(q, p[f"{name}.q_norm"], eps), mode)
    k = P.act(rms(k, p[f"{name}.k_norm"], eps), mode)
    q = P.act(rotary(q, cfg["rope_theta"]), mode)
    k = P.act(rotary(k, cfg["rope_theta"]), mode)
    o = P.act(attend(q, k, v, None, mode), mode)
    return P.act(P.dot(o.reshape(b, t, h * hd), p[f"{name}.wo"], mode), mode)


# ---- the feed-forward blocks ----

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


def mixer_half(cfg, p, l, u, mode):
    """-> (h, b): the residual stream after the mixer, and the feed-forward
    block's input RMS2(h) as rows [tokens, hidden]."""
    eps = cfg["norm_eps"]
    a = P.act(rms(u, p[f"_l{l}_norm1.w0"], eps), mode)
    mix = short_conv if is_conv(cfg, l) else qk_norm_attention
    h = P.act(u + mix(cfg, p, l, a, mode), mode)
    b = P.act(rms(h, p[f"_l{l}_norm2.w0"], eps), mode)
    return h, b.reshape(b.shape[0] * b.shape[1], -1)


def feed_forward(cfg, p, l, b, mode):
    if is_dense(cfg, l):
        return gated_mlp(p, f"l{l}_mlp", b, mode)
    return experts(cfg, p, f"l{l}_moe", b, mode)


def layer(cfg, p, l, u, mode):
    h, b = mixer_half(cfg, p, l, u, mode)
    return P.act(h + feed_forward(cfg, p, l, b, mode).reshape(h.shape), mode)


def hidden(cfg, p, ids, mode):
    """ids [B, T] -> the final norm's output [B, T, hidden]."""
    x = P.act(p["_emb.w0"][ids], mode)
    for l in layers_held(cfg):
        x = jax.checkpoint(lambda x, l=l: layer(cfg, p, l, x, mode))(x)
    return P.act(rms(x, p["_final_norm.w0"], cfg["norm_eps"]), mode)


def chosen(cfg, p, ids, mode):
    """[expert layers, tokens, top-k] int32, sorted: the experts each
    token's router takes in each expert layer, forward only. For reading how
    many selections another precision flips."""
    out = []
    x = P.act(p["_emb.w0"][ids], mode)
    with jax.default_matmul_precision("highest"):
        for l in layers_held(cfg):
            h, b = mixer_half(cfg, p, l, x, mode)
            if not is_dense(cfg, l):
                out.append(jnp.sort(route(cfg, p, f"l{l}_moe", b, mode)[1],
                                    axis=-1))
            x = P.act(h + feed_forward(cfg, p, l, b, mode).reshape(h.shape),
                      mode)
    return jnp.stack(out).astype(jnp.int32)


def loss(cfg, p, batch, mode="f32"):
    """Mean next-token cross-entropy over the real positions. `batch`: ids
    and label [B, T] int32, lens [B]."""
    ids, labels, lens = batch["ids"], batch["label"], batch["lens"]
    b, t = ids.shape
    with jax.default_matmul_precision("highest"):
        x = hidden(cfg, p, ids, mode)
        per = token_costs(p["_emb.w0"].T, x.reshape(b * t, -1),
                          labels.reshape(b * t), mode)
    real = (jnp.arange(t)[None, :] < lens[:, None]).reshape(b * t)
    return jnp.sum(jnp.where(real, per, 0.0)) / jnp.sum(lens)


# ---- operations, from the configuration and the traffic alone ----

def attended_keys(t) -> int:
    """Keys a query attends, summed over t positions: the causal triangle."""
    return t * (t + 1) // 2


def forward_flops_per_token(cfg, t) -> dict:
    """Forward FLOPs a token, by part, at sequence length t: the
    convolutions' two projections, attention's four, its scores and values
    over the keys really attended, the dense layers, the routed experts held
    (the expected share of the top-k that falls on them), the routers, the
    head. The convolution's own mixing (a few element-wise operations a
    channel) and the norms are not counted, as no norm is."""
    d, h, kv = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    hd, f = head_dim(cfg), cfg["moe_intermediate_size"]
    held = (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / router_width(cfg))
    out = {"conv_projections": 0.0, "attn_projections": 0.0,
           "attention": 0.0, "dense": 0.0, "experts": 0.0, "router": 0.0}
    for l in layers_held(cfg):
        if is_conv(cfg, l):
            out["conv_projections"] += 2 * d * (3 * d + d)
        else:
            out["attn_projections"] += 2 * d * (2 * h * hd + 2 * kv * hd)
            out["attention"] += h * 4 * hd * attended_keys(t) / t
        if is_dense(cfg, l):
            out["dense"] += 3 * 2 * d * cfg["intermediate_size"]
        else:
            out["experts"] += held * 3 * 2 * d * f
            out["router"] += 2 * d * router_width(cfg)
    out["head"] = 2 * d * cfg["vocab_size"]
    return out


def train_flops_per_row(cfg, t) -> float:
    """A row is a token: forward + backward = 3 x forward; recomputation
    is not counted (it is the program's choice, not the model's work)."""
    return 3.0 * sum(forward_flops_per_token(cfg, t).values())
