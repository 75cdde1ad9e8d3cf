"""One pipeline stage of Phi-4-mini-flash-reasoning (microsoft; `model_type`
phi4flash), in plain jax.numpy: loss and, through `jax.grad`, gradients.

A pre-norm decoder whose mixers alternate between a Mamba layer and
differential attention. For the published layer `l` on `x` [T, hidden], every
norm a LayerNorm with a weight and a bias, LN(x) = w (x - mean) / sqrt(var +
eps) + b:

    h = x + Mixer_l(LN1(x));  y = h + MLP(LN2(h))
    MLP(u) = (silu(u Wg) * (u Wu)) Wd

Of `n` published layers, `l` is a Mamba layer if `l % mb_per_layer == 0`, else
differential attention: over a window of `sliding_window` if `l < n / 2`, full
causal at `l == n / 2 + 1`. From `l >= n / 2 + 2` the second decoder's kinds
follow (gated memory units, cross-attention over layer `n / 2 + 1`'s keys and
values); this reference has neither, and says so (`kind_of`).

`Mamba(u)`, `d_inner = expand x hidden`, `d_state` states a channel:
    [xr | z] = u W_in
    x_t = silu(b_c + sum_j w_c[:, j] xr_{t - (d_conv - 1) + j}), zeros before
          the row's start (a causal depthwise convolution over time)
    [r | B | C] = x W_x;  dt = softplus(r W_dt + b_dt), float32
    A = -exp(A_log)
    h_t = exp(dt_t A) h_{t-1} + (dt_t x_t) B_t, h_0 = 0, float32, a channel's
          state [d_state];  s_t = <h_t, C_t> + D x_t
    Mamba = (s * silu(z)) W_out

`DiffAttn_l(u)`, `H` query heads on `KV` key heads of `hd` (40 on 20 of 64):
    [q | k | v] = u W_qkv + b_qkv
    key heads 2j and 2j+1 (pair j) share ONE value v_j = [v[2j] | v[2j+1]],
    2 hd wide;  A_h = softmax(mask(q_h k_{h//2}^T / sqrt(hd))) v_{h//4}
    differential head i = 2j + a takes A_{4j+a} - lam A_{4j+2+a}:
    o_i = (1 - lam0) RMSNorm_{2hd}(A_{4j+a} - lam A_{4j+2+a}), one weight g
    lam = exp(<lq1, lk1>) - exp(<lq2, lk2>) + lam0, lam0 = 0.8 - 0.6 exp(-0.3 l)
    DiffAttn = concat_i(o_i) W_o + b_o

then a final LayerNorm, the head over the vocabulary slice held here, which is
the embedding itself (`tie_word_embeddings`: logits = LN_f(y) E^T, one leaf
`_emb.w0`), and the mean next-token cross-entropy over the real positions.
Ids are embedded by E with no scale and no positional signal of any kind.

The stage (`model-configs` guide, section 4): `first_layer` is the published
index of the first layer held, `num_hidden_layers` how many follow it,
`published_layers` the whole model's count, `vocab_size` the slice of the
vocabulary held. The same functions given `first_layer` 0 and layers up to
`n / 2 + 1` are the model's first decoder uncut.

Float32 throughout, `highest` matmul precision; `mode` is the precision of
matmul operands and of each sub-layer's output (reference/precision.py); dt,
A, D and the recurrence stay float32 in every mode, as the program keeps
them. So that float32 fits one chip at 8,192 positions beside a trainer's five
copies of the weights, each layer is rematerialised in the backward pass, the
scan is a `lax.scan` over time inside checkpointed chunks (the carry of every
step of a row would be 2.7 GB; chunk boundaries are 21 MB), attention goes a
head and a block of queries at a time, the feed-forward block a chunk of rows
at a time, the head and its cost in row chunks: that changes what is stored,
not what is computed. Dense masks, a step-by-step recurrence, no kernel.
Imports nothing of the program; the parameter names and shapes are the ones
the program's graph gives its layers, since the benchmark hands one set of
seeded weights to both.
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
SCAN_CHUNK = 256         # positions between two saved states


# ---- which published layer is which ----

def published_layers(cfg) -> int:
    return int(cfg.get("published_layers", cfg["num_hidden_layers"]))


def layers_held(cfg) -> list:
    first = int(cfg.get("first_layer", 0))
    return list(range(first, first + int(cfg["num_hidden_layers"])))


def kind_of(cfg, l: int) -> str:
    """"mamba", "window" or "full"; raises for a layer of the second
    decoder, naming what it would need."""
    n = published_layers(cfg)
    if l >= n // 2 + 2:
        what = ("a gated memory unit (a gate over layer "
                f"{n // 2}'s scan output)" if l % cfg["mb_per_layer"] == 0
                else f"cross-attention over layer {n // 2 + 1}'s keys and "
                "values")
        raise NotImplementedError(
            f"published layer {l} of {n} is {what}: the second decoder's "
            "layers are not in this reference")
    if l % cfg["mb_per_layer"] == 0:
        return "mamba"
    return "window" if l < n // 2 else "full"


def lambda_init(l: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * l)


def mamba_sizes(cfg) -> tuple:
    """-> (d_inner, d_state, d_conv, dt_rank)."""
    d = cfg["hidden_size"]
    rank = cfg["mamba_dt_rank"]
    if rank == "auto":
        rank = -(-d // 16)
    return (cfg["mamba_expand"] * d, cfg["mamba_d_state"],
            cfg["mamba_d_conv"], int(rank))


def param_spec(cfg) -> dict:
    """name -> (shape, ("normal", std) | ("const", value))."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    di, n, dc, r = mamba_sizes(cfg)
    std = cfg["init_std"]
    proj, mlp = ("normal", std["projection"]), ("normal", std["mlp"])
    one, zero = ("const", 1.0), ("const", 0.0)
    spec = {"_emb.w0": ((v, d), ("normal", std["embedding"]))}
    for l in layers_held(cfg):
        for norm in ("norm1", "norm2"):
            spec[f"_l{l}_{norm}.w0"] = ((d,), one)
            spec[f"_l{l}_{norm}.b0"] = ((d,), zero)
        if kind_of(cfg, l) == "mamba":
            m = f"_l{l}_mamba."
            spec[m + "w_in"] = ((d, 2 * di), proj)
            spec[m + "conv_w"] = ((di, dc), ("normal", std["conv"]))
            spec[m + "conv_b"] = ((di,), zero)
            spec[m + "w_x"] = ((di, r + 2 * n), proj)
            spec[m + "w_dt"] = ((r, di), ("normal", std["dt"]))
            spec[m + "b_dt"] = ((di,), ("const", cfg["init_dt_bias"]))
            spec[m + "a_log"] = ((di, n), ("normal", std["a_log"]))
            spec[m + "d"] = ((di,), one)
            spec[m + "w_out"] = ((di, d), proj)
        else:
            a = f"_l{l}_attn."
            spec[a + "wqkv"] = ((d, (h + 2 * kv) * hd), proj)
            spec[a + "bqkv"] = (((h + 2 * kv) * hd,), zero)
            spec[a + "wo"] = ((h * hd, d), proj)
            spec[a + "bo"] = ((d,), zero)
            for lam in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
                spec[a + lam] = ((hd,), ("normal", std["lambda"]))
            spec[a + "subln"] = ((2 * hd,), one)
        spec[f"_l{l}_mlp.w_gate"] = ((d, f), mlp)
        spec[f"_l{l}_mlp.w_up"] = ((d, f), mlp)
        spec[f"_l{l}_mlp.w_down"] = ((f, d), mlp)
    spec["_final_norm.w0"] = ((d,), one)
    spec["_final_norm.b0"] = ((d,), zero)
    return spec


# ---- the layers ----

def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * w + b


def causal_conv(xr, w, b):
    """xr [B, T, C], w [C, K], b [C]: y_t = b + sum_j w[:, j] xr_{t-(K-1)+j},
    zeros before the row's start."""
    k = w.shape[1]
    t = xr.shape[1]
    padded = jnp.pad(xr, ((0, 0), (k - 1, 0), (0, 0)))
    return b + sum(w[:, j] * padded[:, j: j + t] for j in range(k))


def scan(x, dt, a, bm, cm, d, chunk=SCAN_CHUNK):
    """The selective scan, step by step. x, dt [B, T, C]; a [C, N]; bm, cm
    [B, T, N]; d [C] -> s [B, T, C]. The state is held [B, N, C] (channels
    last), float32, zero at a row's start."""
    b, t, c = x.shape
    n = a.shape[1]
    chunk = math.gcd(t, chunk)
    at = a.T[None]                                   # [1, N, C]

    def step(h, xs):
        x_t, dt_t, b_t, c_t = xs                     # [B, C] | [B, N]
        h = (jnp.exp(dt_t[:, None, :] * at) * h
             + (dt_t * x_t)[:, None, :] * b_t[:, :, None])
        return h, jnp.sum(h * c_t[:, :, None], axis=1)

    @jax.checkpoint
    def one_chunk(h, xs):
        return lax.scan(step, h, xs)

    def chunks(v):                                   # [B, T, W] -> [k, chunk, B, W]
        return jnp.moveaxis(v, 1, 0).reshape(t // chunk, chunk, b, -1)

    _, y = lax.scan(one_chunk, jnp.zeros((b, n, c), jnp.float32),
                    (chunks(x), chunks(dt), chunks(bm), chunks(cm)))
    return jnp.moveaxis(y.reshape(t, b, c), 0, 1) + d * x


def mamba(cfg, p, name, u, mode):
    di, n, _, r = mamba_sizes(cfg)
    w = lambda s: p[f"_{name}.{s}"]
    xz = P.act(P.dot(u, w("w_in"), mode), mode)
    xr, z = xz[..., :di], xz[..., di:]
    x = P.act(jax.nn.silu(causal_conv(xr, P.operand(w("conv_w"), mode),
                                      w("conv_b"))), mode)
    rbc = P.act(P.dot(x, w("w_x"), mode), mode)
    dt = jax.nn.softplus(P.dot(rbc[..., :r], w("w_dt"), mode) + w("b_dt"))
    s = scan(x, dt, -jnp.exp(w("a_log")), rbc[..., r: r + n],
             rbc[..., r + n:], w("d"))
    g = P.act(s * jax.nn.silu(z), mode)
    return P.act(P.dot(g, w("w_out"), mode), mode)


def attend(q, k, v, window, mode, block=QUERY_BLOCK):
    """Causal softmax attention, q and k [B, T, H, D], v [B, T, H, Dv] ->
    [B, T, H, Dv]: a head and a block of queries at a time against all the
    keys under a dense mask; with `window` a query sees the last `window`
    positions, itself included."""
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
            qi = (q0 + jnp.arange(block))[:, None]
            m = keys <= qi
            if window is not None:
                m = m & (qi - keys < window)
            w = jax.nn.softmax(jnp.where(m[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("bij,bjd->bid", P.operand(w, mode),
                              P.operand(vh, mode),
                              precision=lax.Precision.HIGHEST)

        qs = jnp.moveaxis(qh.reshape(b, nq, block, d), 1, 0)
        o = lax.map(one_block, (qs, jnp.arange(nq) * block))
        return jnp.moveaxis(o, 0, 1).reshape(b, t, -1)

    o = lax.map(one_head, tuple(jnp.moveaxis(x, 2, 0) for x in (q, k, v)))
    return jnp.moveaxis(o, 0, 2)                  # [B, T, H, Dv]


def diff_heads(h: int) -> tuple:
    """-> (the query head of each differential head's positive map, of its
    negative map): head i = 2j + a reads 4j + a and 4j + 2 + a."""
    pos = [4 * (i // 2) + i % 2 for i in range(h // 2)]
    return pos, [q + 2 for q in pos]


def diff_attention(cfg, p, name, l, u, mode):
    b, t, d = u.shape
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    w = lambda s: p[f"_{name}.{s}"]
    qkv = P.act(P.dot(u, w("wqkv"), mode) + w("bqkv"), mode)
    q = qkv[..., : h * hd].reshape(b, t, h, hd)
    k = qkv[..., h * hd: (h + kv) * hd].reshape(b, t, kv, hd)
    v = qkv[..., (h + kv) * hd:].reshape(b, t, kv // 2, 2 * hd)
    window = cfg["sliding_window"] if kind_of(cfg, l) == "window" else None
    maps = P.act(attend(q, jnp.repeat(k, h // kv, axis=2),
                        jnp.repeat(v, 2 * h // kv, axis=2), window, mode),
                 mode)
    lam0 = lambda_init(l)
    lam = (jnp.exp(jnp.sum(w("lambda_q1") * w("lambda_k1")))
           - jnp.exp(jnp.sum(w("lambda_q2") * w("lambda_k2"))) + lam0)
    pos, neg = diff_heads(h)
    x = maps[:, :, jnp.asarray(pos)] - lam * maps[:, :, jnp.asarray(neg)]
    x = x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                      + cfg["layer_norm_eps"]) * w("subln")
    o = P.act((1.0 - lam0) * x, mode).reshape(b, t, d)
    return P.act(P.dot(o, w("wo"), mode) + w("bo"), mode)


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


def layer(cfg, p, l, x, mode):
    eps = cfg["layer_norm_eps"]
    a = P.act(layer_norm(x, p[f"_l{l}_norm1.w0"], p[f"_l{l}_norm1.b0"], eps),
              mode)
    if kind_of(cfg, l) == "mamba":
        mixed = mamba(cfg, p, f"l{l}_mamba", a, mode)
    else:
        mixed = diff_attention(cfg, p, f"l{l}_attn", l, a, mode)
    h = P.act(x + mixed, mode)
    u = P.act(layer_norm(h, p[f"_l{l}_norm2.w0"], p[f"_l{l}_norm2.b0"], eps),
              mode)
    y = gated_mlp(p, f"l{l}_mlp", u.reshape(-1, u.shape[-1]), mode)
    return P.act(h + y.reshape(h.shape), mode)


def hidden(cfg, p, ids, mode):
    """ids [B, T] -> the final norm's output [B, T, hidden]."""
    x = P.act(p["_emb.w0"][ids], mode)
    for l in layers_held(cfg):
        x = jax.checkpoint(lambda x, l=l: layer(cfg, p, l, x, mode))(x)
    return P.act(layer_norm(x, p["_final_norm.w0"], p["_final_norm.b0"],
                            cfg["layer_norm_eps"]), mode)


def token_costs(emb, x, labels, mode, chunk=HEAD_CHUNK):
    """-log softmax(x emb^T)[label] for rows x [N, hidden] over the tied
    matrix emb [V, hidden], float32 logits, a chunk of rows at a time."""
    n = x.shape[0]
    chunk = math.gcd(n, chunk)

    @jax.checkpoint
    def one(xl):
        xc, lc = xl
        logits = P.dot(xc, emb.T, mode)
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
        per = token_costs(p["_emb.w0"], x.reshape(b * t, -1),
                          labels.reshape(b * t), mode)
    real = (jnp.arange(t)[None, :] < lens[:, None]).reshape(b * t)
    return jnp.sum(jnp.where(real, per, 0.0)) / jnp.sum(lens)


# ---- operations, from the configuration and the traffic alone ----

def attended_keys(t, window=None) -> int:
    """Keys a query attends, summed over t positions: the causal triangle,
    or under a window its first `window` rows and `window` a row after."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def forward_flops_per_token(cfg, t) -> dict:
    """Forward FLOPs a token, by part, at sequence length t: the mixers'
    projections (the convolution with them), the attention maps (a score
    head_dim wide, a value 2 head_dim wide, over the keys really attended,
    for every query head), the scan (9 operations a position, channel and
    state), the feed-forward blocks, the head."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    di, n, dc, r = mamba_sizes(cfg)
    out = {"projections": 0.0, "attention": 0.0, "scan": 0.0, "mlp": 0.0}
    for l in layers_held(cfg):
        kind = kind_of(cfg, l)
        if kind == "mamba":
            out["projections"] += 2 * (d * 2 * di + di * dc + di * (r + 2 * n)
                                       + r * di + di * d)
            out["scan"] += 9 * di * n
        else:
            window = cfg["sliding_window"] if kind == "window" else None
            out["projections"] += 2 * (d * (h + 2 * kv) * hd + h * hd * d)
            out["attention"] += (h * 2 * (hd + 2 * hd)
                                 * attended_keys(t, window) / t)
        out["mlp"] += 3 * 2 * d * f
    out["head"] = 2 * d * cfg["vocab_size"]
    return out


def train_flops_per_row(cfg, t) -> float:
    """A row is a token: forward + backward = 3 x forward; recomputation
    is not counted (it is the program's choice, not the model's work)."""
    return 3.0 * sum(forward_flops_per_token(cfg, t).values())
