"""The language decoder of Kimi-VL-A3B-Instruct (moonshotai), one chip's
share of it, in plain jax.numpy: loss and, through `jax.grad`, gradients.

A pre-norm decoder. For layer `i` on `x` [T, hidden], every norm an RMS norm
with a weight, RMS(x) = w x / sqrt(mean(x^2) + eps):

    h = x + MLA(RMS1(x));  y = h + FFN_i(RMS2(h))

`MLA(u)` (latent attention), `H` heads:
    q = u Wq, a head [q_nope (nope) | q_pe (rope)]
    c = u Wkva;  c_kv = RMS_kv(c[:rank]);  k_pe = c[rank:], ONE for all heads
    kv = c_kv Wkvb, a head [k_nope (nope) | v (v_head_dim)]
    rotary (plain, theta, half-split pairs) on q_pe and k_pe only
    q_h = [q_nope_h | rot(q_pe_h)], k_h = [k_nope_h | rot(k_pe)]
    o_h = softmax(causal(q_h k_h^T / sqrt(nope + rope))) v_h;  MLA = concat(o_h) Wo

`FFN_i`, `i < first_k_dense_replace`: (silu(u Wg) * (u Wu)) Wd of
`intermediate_size`. After that `Routed(u) + Shared(u)`:
    s = sigmoid(u Wr) over ALL routed experts, in float32
    S = the top-k of s + b (the selection bias chooses and does not weigh;
        no gradient reaches it);  w_e = s_e / (sum_S s + 1e-20) * scale
    Routed = sum over e in S AND held here of w_e (silu(u Wg_e) * (u Wu_e)) Wd_e
    Shared = one gated MLP of n_shared_experts x moe_intermediate_size

then a final RMS, the head over the vocabulary slice held here, and the mean
next-token cross-entropy over the real positions.

The share (`model-configs` guide, section 4): the router keeps its published
width and its experts per token; experts `experts_held_first` ..
`+ n_routed_experts` are held, and what the absent experts would add is left
out; attention, the dense layer and the shared experts are what every chip of
the layer computes alike; ids, logits and loss are over the vocabulary slice.
The same function given all the experts and the whole vocabulary is the
uncut model.

Departures from the published description, each also under `assumed` in the
configuration's file: the image tower and its projector are left out (ids go
in); no sequence-wise auxiliary loss (`seq_aux`: its weight is no key of the
config); the selection bias is a constant (its balancing update has no key
either); rotary pairs are half-split, which on seeded weights is the
family's interleaved pairing up to a fixed permutation of Wq's and Wkva's
rotary columns; router logits, scores and top-k in float32 whatever the mode.

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

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference import precision as P

HEAD_CHUNK = 2048        # rows of logits held at once
MLP_CHUNK = 2048         # rows of a feed-forward block's inner width
QUERY_BLOCK = 1024       # queries of one head scored at once


def n_layers(cfg) -> int:
    return int(cfg["num_hidden_layers"])


def router_width(cfg) -> int:
    """Experts the router chooses among: the published count."""
    return int(cfg.get("router_experts", cfg["n_routed_experts"]))


def is_dense(cfg, i) -> bool:
    return i < int(cfg.get("first_k_dense_replace", 0))


def shared_width(cfg) -> int:
    return int(cfg.get("n_shared_experts", 0)) * cfg["moe_intermediate_size"]


def param_spec(cfg) -> dict:
    """name -> (shape, ("normal", std) | ("const", value))."""
    d, h, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    e, f, v = (cfg["n_routed_experts"], cfg["moe_intermediate_size"],
               cfg["vocab_size"])
    std = cfg["init_std"]
    proj = ("normal", std["projection"])
    spec = {"_emb.w0": ((v, d), ("normal", std["embedding"]))}
    for i in range(n_layers(cfg)):
        spec[f"_l{i}_norm1.w0"] = ((d,), ("const", 1.0))
        spec[f"_l{i}_attn.wq"] = ((d, h * (dn + dr)), proj)
        spec[f"_l{i}_attn.wkva"] = ((d, r + dr), proj)
        spec[f"_l{i}_attn.kv_norm"] = ((r,), ("const", 1.0))
        spec[f"_l{i}_attn.wkvb"] = ((r, h * (dn + dv)), proj)
        spec[f"_l{i}_attn.wo"] = ((h * dv, d), proj)
        spec[f"_l{i}_norm2.w0"] = ((d,), ("const", 1.0))
        name, width = (("mlp", cfg["intermediate_size"]) if is_dense(cfg, i)
                       else ("shared", shared_width(cfg)))
        if width:
            mlp = ("normal", std["mlp"])
            spec[f"_l{i}_{name}.w_gate"] = ((d, width), mlp)
            spec[f"_l{i}_{name}.w_up"] = ((d, width), mlp)
            spec[f"_l{i}_{name}.w_down"] = ((width, d), mlp)
        if not is_dense(cfg, i):
            ex = ("normal", std["expert"])
            spec[f"_l{i}_moe.router"] = ((d, router_width(cfg)),
                                         ("normal", std["router"]))
            spec[f"_l{i}_moe.e_score_correction_bias"] = (
                (router_width(cfg),), ("normal", std["router_bias"]))
            spec[f"_l{i}_moe.w_gate"] = ((e, d, f), ex)
            spec[f"_l{i}_moe.w_up"] = ((e, d, f), ex)
            spec[f"_l{i}_moe.w_down"] = ((e, f, d), ex)
    spec["_final_norm.w0"] = ((d,), ("const", 1.0))
    spec["_head.w0"] = ((d, v), ("normal", std["head"]))
    return spec


# ---- the layer ----

def rms(x, w, eps):
    return w * x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def rotary(x, theta):
    """x [B, T, n, hd]: pair (i, i + hd/2) turned by t * theta^(-2i/hd)."""
    hd = x.shape[-1]
    freq = theta ** (-2.0 * jnp.arange(hd // 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def mla_qkv(cfg, p, name, a, mode):
    """-> q, k [B, T, H, nope + rope], v [B, T, H, v_head_dim]."""
    b, t, _ = a.shape
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    q = P.act(P.dot(a, p[f"_{name}.wq"], mode), mode).reshape(b, t, h, dn + dr)
    c = P.act(P.dot(a, p[f"_{name}.wkva"], mode), mode)
    latent = P.act(rms(c[..., :r], p[f"_{name}.kv_norm"],
                       cfg["rms_norm_eps"]), mode)
    kv = P.act(P.dot(latent, p[f"_{name}.wkvb"], mode), mode).reshape(
        b, t, h, dn + dv)
    q_pe = P.act(rotary(q[..., dn:], cfg["rope_theta"]), mode)
    k_pe = P.act(rotary(c[..., None, r:], cfg["rope_theta"]), mode)
    q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_pe, (b, t, h, dr))], axis=-1)
    return q, k, kv[..., dn:]


def attend(q, k, v, mode, block=QUERY_BLOCK):
    """Causal softmax attention, q and k [B, T, H, D], v [B, T, H, Dv] ->
    [B, T, H, Dv]: a head and a block of queries at a time against all the
    keys under a dense mask."""
    b, t, h, d = q.shape
    block = math.gcd(t, block)
    nq = t // block
    keys = jnp.arange(t)[None, :]

    def one_head(qkv):
        qh, kh, vh = qkv                          # [B, T, D | Dv]

        @jax.checkpoint
        def one_block(args):
            qb, q0 = args                         # [B, block, D]
            s = jnp.einsum("bid,bjd->bij", P.operand(qb, mode),
                           P.operand(kh, mode),
                           precision=lax.Precision.HIGHEST) / math.sqrt(d)
            m = keys <= (q0 + jnp.arange(block))[:, None]
            w = jax.nn.softmax(jnp.where(m[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("bij,bjd->bid", P.operand(w, mode),
                              P.operand(vh, mode),
                              precision=lax.Precision.HIGHEST)

        qs = jnp.moveaxis(qh.reshape(b, nq, block, d), 1, 0)
        o = lax.map(one_block, (qs, jnp.arange(nq) * block))
        return jnp.moveaxis(o, 0, 1).reshape(b, t, -1)

    o = lax.map(one_head, tuple(jnp.moveaxis(x, 2, 0) for x in (q, k, v)))
    return jnp.moveaxis(o, 0, 2)                  # [B, T, H, Dv]


def mla(cfg, p, name, a, mode):
    b, t, _ = a.shape
    q, k, v = mla_qkv(cfg, p, name, a, mode)
    o = P.act(attend(q, k, v, mode).reshape(b, t, -1), mode)
    return P.act(P.dot(o, p[f"_{name}.wo"], mode), mode)


def gated_mlp(p, name, x, mode, chunk=MLP_CHUNK):
    """(silu(x Wg) * (x Wu)) Wd for rows x [N, hidden], a chunk of rows at a
    time."""
    n = x.shape[0]
    chunk = math.gcd(n, chunk)
    wg, wu, wd = (p[f"_{name}.w_{s}"] for s in ("gate", "up", "down"))

    @jax.checkpoint
    def one(xc):
        hid = P.act(jax.nn.silu(P.dot(xc, wg, mode)) * P.dot(xc, wu, mode),
                    mode)
        return P.dot(hid, wd, mode)

    return P.act(lax.map(one, x.reshape(n // chunk, chunk, -1)).reshape(
        n, -1), mode)


def route(cfg, p, name, x, mode):
    """-> ([N, router width] float32: each token's weight on each expert,
    zero outside its top-k; [N, k] int32: the experts chosen)."""
    logits = P.dot(x, p[f"_{name}.router"], mode).astype(jnp.float32)
    score = jax.nn.sigmoid(logits)
    # the bias chooses and does not weigh
    _, idx = lax.top_k(score + p[f"_{name}.e_score_correction_bias"],
                       cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(score, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    top = top * cfg["routed_scaling_factor"]
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(score).at[rows, idx].set(top), idx


def experts(cfg, p, name, x, mode):
    """The held experts' part of the layer's result for x [N, hidden]."""
    first = int(cfg.get("experts_held_first", 0))
    gates, _ = route(cfg, p, name, x, mode)
    gates = gates[:, first: first + cfg["n_routed_experts"]]

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
    """-> (h, u): the residual stream after attention, and the
    feed-forward block's input RMS2(h) as rows [tokens, hidden]."""
    eps = cfg["rms_norm_eps"]
    a = P.act(rms(x, p[f"_l{i}_norm1.w0"], eps), mode)
    h = P.act(x + mla(cfg, p, f"l{i}_attn", a, mode), mode)
    u = P.act(rms(h, p[f"_l{i}_norm2.w0"], eps), mode)
    return h, u.reshape(u.shape[0] * u.shape[1], -1)


def feed_forward(cfg, p, i, u, mode):
    if is_dense(cfg, i):
        return gated_mlp(p, f"l{i}_mlp", u, mode)
    y = experts(cfg, p, f"l{i}_moe", u, mode)
    if shared_width(cfg):
        y = y + gated_mlp(p, f"l{i}_shared", u, mode)
    return y


def layer(cfg, p, i, x, mode):
    h, u = attention_half(cfg, p, i, x, mode)
    return P.act(h + feed_forward(cfg, p, i, u, mode).reshape(h.shape), mode)


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
            h, u = attention_half(cfg, p, i, x, mode)
            if not is_dense(cfg, i):
                out.append(jnp.sort(route(cfg, p, f"l{i}_moe", u, mode)[1],
                                    axis=-1))
            x = P.act(h + feed_forward(cfg, p, i, u, mode).reshape(h.shape),
                      mode)
    return jnp.stack(out).astype(jnp.int32)


def token_costs(w, x, labels, mode, chunk=HEAD_CHUNK):
    """-log softmax(x w)[label] for rows x [N, hidden], float32 logits, a
    chunk of rows at a time."""
    n = x.shape[0]
    chunk = math.gcd(n, chunk)

    @jax.checkpoint
    def one(xl):
        xc, lc = xl
        logits = P.dot(xc, w, mode)
        picked = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked

    return lax.map(one, (x.reshape(n // chunk, chunk, -1),
                         labels.reshape(n // chunk, chunk))).reshape(n)


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

def attended_keys(t) -> int:
    """Keys a query attends, summed over t positions: the causal triangle."""
    return t * (t + 1) // 2


def forward_flops_per_token(cfg, t) -> dict:
    """Forward FLOPs a token, by part, at sequence length t: the latent
    attention's projections, its scores (nope + rope wide) and values
    (v_head_dim wide) over the keys really attended, the dense layers, the
    shared experts, the routed experts held (the expected share of the top-k
    that falls on them), the router, the head."""
    d, h, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    f = cfg["moe_intermediate_size"]
    held = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / router_width(cfg))
    out = {"projections": 0.0, "attention": 0.0, "dense": 0.0, "shared": 0.0,
           "experts": 0.0, "router": 0.0}
    for i in range(n_layers(cfg)):
        out["projections"] += 2 * (d * h * (dn + dr) + d * (r + dr)
                                   + r * h * (dn + dv) + h * dv * d)
        out["attention"] += h * 2 * ((dn + dr) + dv) * attended_keys(t) / t
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
