"""Mellum2-12B-A2.5B-Instruct (JetBrains, `model_type` `mellum`), one chip's
share of it, in plain jax.numpy: loss and, through `jax.grad`, gradients.

A pre-norm decoder. For layer `l` on `x` [T, hidden]:

    a  = RMS1(x);  q = a Wq (heads x head_dim), k = a Wk, v = a Wv (KV heads)
    rotary on q, k over the whole head, half-split pairs, inv_freq_i =
      theta^(-2i/head_dim); a `full_attention` layer scales them by YaRN
      (rope.yarn below) and multiplies cos and sin by `attention_factor`
    query head h attends KV head h // (heads / KV heads); scores / sqrt(head_dim);
      position i sees j <= i, on a `sliding_attention` layer also i - j < window
    h1 = x + concat(heads) Wo
    b  = RMS2(h1);  p = softmax(b Wr) over ALL routed experts, in float32
    S  = the top-k of p;  g_e = p_e / sum_S p   (`norm_topk_prob`)
    moe = sum over e in S AND held here of g_e (silu(b Wg_e) * (b Wu_e)) Wd_e
    y  = h1 + moe

then a final RMS, the head over the vocabulary slice held here, and the mean
next-token cross-entropy over the real positions. RMS(x) = w x /
sqrt(mean(x^2) + eps).

The share (`model-configs` guide, section 4): the router keeps its published
width and its experts per token; experts `experts_held_first` ..
`+ num_experts` are held, and what the absent experts would add is left
out; ids, logits and loss are over the vocabulary slice. The same function
given all the experts and the whole vocabulary is the uncut model.

Departures from the published description, each also under `assumed` in the
configuration's file: no QK-norm and no auxiliary router loss (the config has
no key for either); the multi-token head that the catalog's summary mentions is
left out (no `num_nextn_predict_layers`); router logits, softmax and top-k in
float32 whatever the mode.

Float32 throughout, `highest` matmul precision; `mode` is the precision of
matmul operands and of each sub-layer's output (reference/precision.py). So
that float32 fits one chip at 8,192 positions, each layer is rematerialised
in the backward pass, heads and experts are walked one at a time, and the
head and its cost go in row chunks: that changes what is stored, not what is
computed. Dense masks, a loop over experts, no kernel. Imports nothing of the
program; the parameter names and shapes are the ones the program's graph
gives its layers, since the benchmark hands one set of seeded weights to both.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference import precision as P

HEAD_CHUNK = 2048        # rows of logits held at once


def n_layers(cfg) -> int:
    return int(cfg["num_hidden_layers"])


def router_width(cfg) -> int:
    """Experts the router chooses among: the published count."""
    return int(cfg.get("router_experts", cfg["num_experts"]))


def param_spec(cfg) -> dict:
    """name -> (shape, ("normal", std) | ("const", value))."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    e, f, v = cfg["num_experts"], cfg["moe_intermediate_size"], cfg["vocab_size"]
    std = cfg["init_std"]
    spec = {"_emb.w0": ((v, d), ("normal", std["embedding"]))}
    for i in range(n_layers(cfg)):
        spec[f"_l{i}_norm1.w0"] = ((d,), ("const", 1.0))
        spec[f"_l{i}_attn.wq"] = ((d, h * hd), ("normal", std["projection"]))
        spec[f"_l{i}_attn.wk"] = ((d, kv * hd), ("normal", std["projection"]))
        spec[f"_l{i}_attn.wv"] = ((d, kv * hd), ("normal", std["projection"]))
        spec[f"_l{i}_attn.wo"] = ((h * hd, d), ("normal", std["projection"]))
        spec[f"_l{i}_norm2.w0"] = ((d,), ("const", 1.0))
        spec[f"_l{i}_moe.router"] = ((d, router_width(cfg)),
                                     ("normal", std["router"]))
        spec[f"_l{i}_moe.w_gate"] = ((e, d, f), ("normal", std["expert"]))
        spec[f"_l{i}_moe.w_up"] = ((e, d, f), ("normal", std["expert"]))
        spec[f"_l{i}_moe.w_down"] = ((e, f, d), ("normal", std["expert"]))
    spec["_final_norm.w0"] = ((d,), ("const", 1.0))
    spec["_head.w0"] = ((d, v), ("normal", std["head"]))
    return spec


# ---- rotary positions ----

def yarn_range(head_dim, theta, original, beta_fast, beta_slow):
    """(lo, hi): the pair indices between which YaRN blends: below `lo` a
    pair turns more than `beta_fast` times in `original` positions and is
    left alone, above `hi` fewer than `beta_slow` times and is divided by
    the factor."""
    def pair_at(rotations):
        return (head_dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(pair_at(beta_fast)), 0)
    hi = min(math.ceil(pair_at(beta_slow)), head_dim - 1)
    return lo, hi


def inv_freq(head_dim, rope):
    """[head_dim / 2] float32, and the factor on cos and sin."""
    i = jnp.arange(head_dim // 2, dtype=jnp.float32)
    freq = rope["rope_theta"] ** (-2.0 * i / head_dim)
    if rope.get("rope_type", "default") != "yarn":
        return freq, 1.0
    lo, hi = yarn_range(head_dim, rope["rope_theta"],
                        rope["original_max_position_embeddings"],
                        rope["beta_fast"], rope["beta_slow"])
    keep = 1.0 - jnp.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    freq = freq / rope["factor"] * (1.0 - keep) + freq * keep
    return freq, float(rope["attention_factor"])


def rotary(x, rope):
    """x [B, T, n, head_dim]: pair (i, i + head_dim/2) turned by t*freq_i."""
    hd = x.shape[-1]
    freq, factor = inv_freq(hd, rope)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq[None, :]
    cos = (jnp.cos(ang) * factor)[None, :, None, :]
    sin = (jnp.sin(ang) * factor)[None, :, None, :]
    a, b = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


# ---- the layer ----

def rms(x, w, eps):
    return w * x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def mask(t, window):
    """[T, T] bool: i sees j <= i, and with a window also i - j < window."""
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    m = j <= i
    return m & (i - j < window) if window else m


def attention(cfg, p, name, a, kind, mode):
    b, t, _ = a.shape
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    rope = cfg["rope_parameters"][kind]
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    q = P.act(P.dot(a, p[f"_{name}.wq"], mode), mode).reshape(b, t, h, hd)
    k = P.act(P.dot(a, p[f"_{name}.wk"], mode), mode).reshape(b, t, kv, hd)
    v = P.act(P.dot(a, p[f"_{name}.wv"], mode), mode).reshape(b, t, kv, hd)
    q, k = P.act(rotary(q, rope), mode), P.act(rotary(k, rope), mode)
    m = mask(t, window)

    @jax.checkpoint
    def one_head(qkv):
        qh, kh, vh = qkv                          # [B, T, hd] each
        s = jnp.einsum("bid,bjd->bij", P.operand(qh, mode),
                       P.operand(kh, mode),
                       precision=lax.Precision.HIGHEST) / math.sqrt(hd)
        w = jax.nn.softmax(jnp.where(m[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("bij,bjd->bid", P.operand(w, mode),
                          P.operand(vh, mode),
                          precision=lax.Precision.HIGHEST)

    group = h // kv
    qs = jnp.moveaxis(q, 2, 0)                    # [h, B, T, hd]
    ks = jnp.repeat(jnp.moveaxis(k, 2, 0), group, axis=0)
    vs = jnp.repeat(jnp.moveaxis(v, 2, 0), group, axis=0)
    o = lax.map(one_head, (qs, ks, vs))           # [h, B, T, hd]
    o = P.act(jnp.moveaxis(o, 0, 2).reshape(b, t, h * hd), mode)
    return P.act(P.dot(o, p[f"_{name}.wo"], mode), mode)


def route(cfg, router_w, x, mode):
    """-> [N, router width] float32: each token's weight on each expert,
    zero outside its top-k."""
    logits = P.dot(x, router_w, mode)
    prob = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top, idx = lax.top_k(prob, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        top = top / jnp.sum(top, -1, keepdims=True)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(prob).at[rows, idx].set(top)


def experts(cfg, p, name, x, mode):
    """The held experts' part of the layer's result for x [N, hidden]."""
    first = int(cfg.get("experts_held_first", 0))
    gates = route(cfg, p[f"_{name}.router"], x, mode)
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
    """-> (h1, b): the residual stream after attention, and the expert
    layer's input RMS2(h1) as rows [tokens, hidden]."""
    eps = cfg["rms_norm_eps"]
    kind = cfg["layer_types"][i]
    a = P.act(rms(x, p[f"_l{i}_norm1.w0"], eps), mode)
    h1 = P.act(x + attention(cfg, p, f"l{i}_attn", a, kind, mode), mode)
    b = P.act(rms(h1, p[f"_l{i}_norm2.w0"], eps), mode)
    return h1, b.reshape(b.shape[0] * b.shape[1], -1)


def layer(cfg, p, i, x, mode):
    h1, b = attention_half(cfg, p, i, x, mode)
    moe = experts(cfg, p, f"l{i}_moe", b, mode)
    return P.act(h1 + moe.reshape(h1.shape), mode)


def hidden(cfg, p, ids, mode):
    """ids [B, T] -> the final norm's output [B, T, hidden]."""
    x = P.act(p["_emb.w0"][ids], mode)
    for i in range(n_layers(cfg)):
        x = jax.checkpoint(lambda x, i=i: layer(cfg, p, i, x, mode))(x)
    return P.act(rms(x, p["_final_norm.w0"], cfg["rms_norm_eps"]), mode)


def chosen(cfg, p, ids, mode):
    """[layers, tokens, top-k] int32, sorted: the experts each token's
    router takes in each layer, forward only. For reading how many
    selections another precision flips."""
    out = []
    x = P.act(p["_emb.w0"][ids], mode)
    with jax.default_matmul_precision("highest"):
        for i in range(n_layers(cfg)):
            h1, b = attention_half(cfg, p, i, x, mode)
            gates = route(cfg, p[f"_l{i}_moe.router"], b, mode)
            out.append(jnp.sort(lax.top_k(
                gates, cfg["num_experts_per_tok"])[1], axis=-1))
            moe = experts(cfg, p, f"l{i}_moe", b, mode)
            x = P.act(h1 + moe.reshape(h1.shape), mode)
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


# ---- operations and bytes, from the configuration and the traffic alone ----

def attended_keys(t, window) -> int:
    """Keys a query attends, summed over t positions: the causal triangle,
    cut to the window."""
    return sum(min(i + 1, window or t) for i in range(t))


def forward_flops_per_token(cfg, t) -> dict:
    """Forward FLOPs a token, by part, at sequence length t: projections,
    attention scores and values over the keys really attended (a window
    layer its window, not the square), the experts held (the expected
    share of the top-k that falls on them), the router, the head."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f = cfg["moe_intermediate_size"]
    proj = 2 * d * (h * hd + 2 * kv * hd) + 2 * h * hd * d
    out = {"projections": 0.0, "attention": 0.0, "experts": 0.0,
           "router": 0.0}
    held = (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / router_width(cfg))
    for kind in cfg["layer_types"][: n_layers(cfg)]:
        window = cfg["sliding_window"] if kind == "sliding_attention" else None
        out["projections"] += proj
        out["attention"] += 4 * h * hd * attended_keys(t, window) / t
        out["experts"] += held * 3 * 2 * d * f
        out["router"] += 2 * d * router_width(cfg)
    out["head"] = 2 * d * cfg["vocab_size"]
    return out


def train_flops_per_row(cfg, t) -> float:
    """A row is a token: forward + backward = 3 x forward; recomputation
    is not counted (it is the program's choice, not the model's work)."""
    return 3.0 * sum(forward_flops_per_token(cfg, t).values())
