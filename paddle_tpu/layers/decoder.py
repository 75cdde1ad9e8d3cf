"""The layers of a present-day pre-norm decoder block, beside the `moe`
layer (layers/moe.py): `rms_norm`, `layer_norm`, `gqa_attention`,
`mla_attention`, `diff_attention`, `mamba`, `short_conv`, `gated_mlp`,
`lm_head_cost`.

`models/mellum.py`, `models/kimi.py`, `models/phi4flash.py`,
`models/laguna.py` and `models/lfm2.py` build decoders from them through the
DSL; their parameter names (`_<layer>.w0`,
`.wq` ...) are what a plain reference's `param_spec` names too, so one set
of seeded weights serves both.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.core.arg import Arg
from paddle_tpu.core.registry import LAYERS
from paddle_tpu.layers.base import Ctx, Layer, Spec
from paddle_tpu.layers.cost import CostLayerBase
from paddle_tpu.ops import activations
from paddle_tpu.ops import gqa_attention as _attn
from paddle_tpu.ops import lm_head as _head
from paddle_tpu.ops import rope as _rope
from paddle_tpu.ops import selective_scan as _scan
from paddle_tpu.ops import short_conv as _conv


def _named(layer, slots) -> dict:
    """slot -> a ParameterConf `_<layer>.<slot>` of the slot's dims; a
    1-D slot without a user's init starts at its (strategy, value)."""
    pcs = {}
    for slot, dims, *init in slots:
        pc = layer.weight_conf(0, dims)
        pc.name = f"_{layer.name}.{slot}"
        if init and pc.initial_std is None:
            pc.initial_strategy, pc.initial_value = "constant", init[0]
        pcs[slot] = pc
    return pcs


def _rms(v, w, eps):
    """w * v / sqrt(mean(v^2) + eps) over the last axis: float32 inside,
    v's dtype out."""
    x = v.astype(jnp.float32)
    y = x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    y = y * w.astype(jnp.float32)
    return y.astype(v.dtype)


@LAYERS.register("rms_norm")
class RMSNormLayer(Layer):
    """w * x / sqrt(mean(x^2) + epsilon) over the last axis, float32
    inside. attrs: epsilon (1e-6). Param w0 [D], 1 at the start."""

    def build(self, in_specs):
        (s,) = in_specs
        pc = self.weight_conf(0, (s.dim[-1],))
        if pc.initial_std is None:
            pc.initial_strategy, pc.initial_value = "constant", 1.0
        return s, {"w0": pc}

    def forward(self, params, inputs, ctx: Ctx):
        (arg,) = inputs
        eps = self.conf.attrs.get("epsilon", 1e-6)
        return arg.with_value(_rms(arg.value, params["w0"], eps))


@LAYERS.register("layer_norm")
class LayerNormLayer(Layer):
    """w * (x - mean) / sqrt(var + epsilon) + b over the last axis, float32
    inside. attrs: epsilon (1e-5). Params w0 [D] (1 at the start), b0 [D]
    (0)."""

    def build(self, in_specs):
        (s,) = in_specs
        d = s.dim[-1]
        return s, _named(self, (("w0", (d,), 1.0), ("b0", (d,), 0.0)))

    def forward(self, params, inputs, ctx: Ctx):
        (arg,) = inputs
        eps = self.conf.attrs.get("epsilon", 1e-5)
        x = arg.value.astype(jnp.float32)
        mu = jnp.mean(x, -1, keepdims=True)
        xc = x - mu
        y = xc * lax.rsqrt(jnp.mean(jnp.square(xc), -1, keepdims=True) + eps)
        y = (y * params["w0"].astype(jnp.float32)
             + params["b0"].astype(jnp.float32))
        return arg.with_value(y.astype(arg.value.dtype))


class _Gauge:
    """A layer that publishes ONE float a step as a gauge by `layer`: the
    extra output `<name>@stats` (`Network.stat_outputs`), read at the
    trainer's fence. Subclasses name it (`gauge`) and set it in forward
    (`_set_gauge`)."""

    gauge = None

    def extra_output_specs(self):
        return {f"{self.name}@stats": Spec(dim=(1,))}

    @property
    def stats_output(self):
        return f"{self.name}@stats"

    def _set_gauge(self, value) -> None:
        self._extra_outs = {f"{self.name}@stats": Arg(
            value=lax.stop_gradient(value).astype(jnp.float32).reshape(1, 1))}

    def publish_stats(self, values, registry) -> None:
        registry.gauge(self.gauge).set(float(values[0]), layer=self.name)


@LAYERS.register("gqa_attention")
class GQAAttentionLayer(_Gauge, Layer):
    """Causal self-attention with grouped query heads and rotary positions.

    attrs: num_heads, num_kv_heads, head_dim; window (a query sees the
    last `window` positions, itself included) or None; rope (the model
    config's group: rope_theta, and for `rope_type: "yarn"` factor,
    original_max_position_embeddings, beta_fast, beta_slow,
    attention_factor; with `partial_rotary_factor` below 1 only that
    leading part of a head turns); gate: None, or "per_head": each head's
    output is scaled, a token, by sigmoid(x wg) before `wo` (the logits a
    float32 accumulation, the sigmoid float32; the gate then multiplies the
    kernel's output in THAT output's dtype, so that no float32 copy of the
    [B, T, H, hd] output is written on the way out or back), and the
    layer publishes the mean gate as the gauge `attn.gate_mean`; qk_norm
    (False): an RMS norm over each head's hd lanes of q and of k, with the
    float32 weights q_norm and k_norm [hd] (1 at the start) and `epsilon`
    (1e-6), after the projections and before the rotary positions. size =
    the model width. Params wq [D, H*hd], wk, wv [D, KV*hd], wo [H*hd, D],
    with a gate wg [D, H]; no bias. Sequences are taken as packed to their
    full length: positions past `seq_lens` are computed like any other
    (causality keeps them out of the real ones) and masked by the cost."""

    gauge = "attn.gate_mean"
    float32_params = ("q_norm", "k_norm")

    def _gated(self) -> bool:
        gate = self.conf.attrs.get("gate")
        assert gate in (None, "per_head"), f"unknown attention gate {gate!r}"
        return gate is not None

    # a layer without a gate has no gauge and no extra output
    def extra_output_specs(self):
        return super().extra_output_specs() if self._gated() else {}

    @property
    def stats_output(self):
        return f"{self.name}@stats" if self._gated() else None

    def build(self, in_specs):
        (s,) = in_specs
        assert s.is_seq, "gqa_attention needs a sequence input"
        a = self.conf.attrs
        d, hd = s.size, a["head_dim"]
        h, kv = a["num_heads"], a["num_kv_heads"]
        assert h % kv == 0, f"{h} heads do not divide over {kv} KV heads"
        return Spec(dim=(d,), is_seq=True), _named(self, (
            ("wq", (d, h * hd)), ("wk", (d, kv * hd)),
            ("wv", (d, kv * hd)), ("wo", (h * hd, d)),
            *((("wg", (d, h)),) if self._gated() else ()),
            *((("q_norm", (hd,), 1.0), ("k_norm", (hd,), 1.0))
              if a.get("qk_norm") else ())))

    def forward(self, params, inputs, ctx: Ctx):
        (arg,) = inputs
        a = self.conf.attrs
        hd, h, kv = a["head_dim"], a["num_heads"], a["num_kv_heads"]
        x = arg.value
        b, t, _ = x.shape
        r = _rope.rotary_width(hd, a["rope"])
        # the rotary pass has no norm inside: a QK-normed layer goes plain
        if not a.get("qk_norm") and _rope.picks_pass(t, hd, r):
            o = self._through_the_kernels_layout(params, x, r)
        else:
            q = jnp.dot(x, params["wq"]).reshape(b, t, h, hd)
            k = jnp.dot(x, params["wk"]).reshape(b, t, kv, hd)
            v = jnp.dot(x, params["wv"]).reshape(b, t, kv, hd)
            if a.get("qk_norm"):
                with jax.named_scope("attn.qk_norm"):
                    eps = a.get("epsilon", 1e-6)
                    q = _rms(q, params["q_norm"], eps)
                    k = _rms(k, params["k_norm"], eps)
            with jax.named_scope("attn.rope"):
                cos, sin = _rope.tables(t, r, a["rope"])
                q, k = _rope.apply(q, cos, sin), _rope.apply(k, cos, sin)
                _rope.note_path("plain", 2)
            with jax.named_scope("attn.core"):
                o = _attn.gqa_attention(q, k, v, window=a.get("window"))
        if self._gated():
            with jax.named_scope("attn.gate"):
                g = jax.nn.sigmoid(jnp.dot(
                    x, params["wg"], preferred_element_type=jnp.float32))
                self._set_gauge(jnp.mean(g))
                o = o * g.astype(o.dtype)[..., None]
        y = jnp.dot(o.reshape(b, t, h * hd), params["wo"])
        return Arg(value=y, seq_lens=arg.seq_lens)


    def _through_the_kernels_layout(self, params, x, r):
        """[B, T, H, hd], as `gqa_attention` on the turned q and k gives it,
        where the rotary pass and the kernel both fit: q and k go from the
        projections' outputs to the kernel's operands in ONE pass each
        (`ops/rope.to_heads`: turned, q scaled, `[B, n, T, hd]`), with no
        `[B, T, n, hd]` array, float32 half or transposed copy between."""
        a = self.conf.attrs
        hd, h, kv = a["head_dim"], a["num_heads"], a["num_kv_heads"]
        b, t, _ = x.shape
        q, k = jnp.dot(x, params["wq"]), jnp.dot(x, params["wk"])
        v = jnp.dot(x, params["wv"]).reshape(b, t, kv, hd)
        with jax.named_scope("attn.rope"):
            cos, sin = _rope.tables(t, r, a["rope"])
            q = _rope.to_heads(q, cos, sin, h, 1.0 / math.sqrt(hd))
            k = _rope.to_heads(k, cos, sin, kv)
        with jax.named_scope("attn.core"):
            o = _attn.grouped(q.reshape(b, kv, h // kv, t, hd), k,
                              v.transpose(0, 2, 1, 3),
                              window=a.get("window"))
        return o.reshape(b, h, t, hd).transpose(0, 2, 1, 3)


@LAYERS.register("mla_attention")
class MLAAttentionLayer(Layer):
    """Causal self-attention whose keys and values come up from one
    low-rank latent a position (multi-head latent attention), with a
    rotary part that all heads share.

    attrs, by the model config's own names: num_heads, kv_lora_rank,
    qk_nope_head_dim, qk_rope_head_dim, v_head_dim, rope_theta, epsilon
    (the latent norm's). size = the model width D. Params, no bias:
    wq [D, H * (nope + rope)]; wkva [D, rank + rope]: the latent and the
    ONE rotary key of a position; kv_norm [rank], an RMS norm's weight on
    the latent; wkvb [rank, H * (nope + v)]: a head's rotary-free key and
    its value; wo [H * v, D]. A head's query and key are [nope | rotary]
    wide (128 + 64 = 192), its value v wide (128); the rotary positions
    (plain, half-split pairs) turn the rotary part alone; scores are
    scaled by 1/sqrt(nope + rope). Sequences are taken as packed to their
    full length, as `gqa_attention` takes them."""

    def build(self, in_specs):
        (s,) = in_specs
        assert s.is_seq, "mla_attention needs a sequence input"
        a = self.conf.attrs
        d, h, r = s.size, a["num_heads"], a["kv_lora_rank"]
        dn, dr, dv = (a["qk_nope_head_dim"], a["qk_rope_head_dim"],
                      a["v_head_dim"])
        return Spec(dim=(d,), is_seq=True), _named(self, (
            ("wq", (d, h * (dn + dr))), ("wkva", (d, r + dr)),
            ("kv_norm", (r,), 1.0), ("wkvb", (r, h * (dn + dv))),
            ("wo", (h * dv, d))))

    def forward(self, params, inputs, ctx: Ctx):
        (arg,) = inputs
        a = self.conf.attrs
        h, r = a["num_heads"], a["kv_lora_rank"]
        dn, dr, dv = (a["qk_nope_head_dim"], a["qk_rope_head_dim"],
                      a["v_head_dim"])
        x = arg.value
        b, t, _ = x.shape
        with jax.named_scope("attn.q"):
            q = jnp.dot(x, params["wq"]).reshape(b, t, h, dn + dr)
        with jax.named_scope("attn.latent"):
            c = jnp.dot(x, params["wkva"])
            latent = _rms(c[..., :r], params["kv_norm"],
                          a.get("epsilon", 1e-6))
            kv = jnp.dot(latent, params["wkvb"]).reshape(b, t, h, dn + dv)
        with jax.named_scope("attn.rope"):
            cos, sin = _rope.tables(t, dr, {"rope_theta": a["rope_theta"]})
            q_pe = _rope.apply(q[..., dn:], cos, sin)
            k_pe = _rope.apply(c[..., None, r:], cos, sin)    # one a position
            q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(k_pe, (b, t, h, dr))],
                axis=-1)
        with jax.named_scope("attn.core"):
            o = _attn.gqa_attention(q, k, kv[..., dn:])
        with jax.named_scope("attn.out"):
            y = jnp.dot(o.reshape(b, t, h * dv), params["wo"])
        return Arg(value=y, seq_lens=arg.seq_lens)


def lambda_init(layer_index: int) -> float:
    """A differential-attention layer's constant lam0, by its published
    index."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer_index)


@LAYERS.register("diff_attention")
class DiffAttentionLayer(_Gauge, Layer):
    """Causal differential attention: a head's output is the difference of
    two softmax maps over one value, normalised.

    attrs: num_heads H, num_kv_heads KV, head_dim hd (40, 20, 64); window
    or None; layer_index (the PUBLISHED index: lam0 = 0.8 - 0.6 exp(-0.3
    l)); epsilon (the 2 hd-wide norm's). size = the model width D. Params
    wqkv [D, (H + 2 KV) hd] with bqkv, wo [H hd, D] with bo, lambda_q1,
    lambda_k1, lambda_q2, lambda_k2 [hd] (float32 masters, used in float32),
    subln [2 hd].

    First a plain grouped attention, which is what the kernel sees: query
    head h reads key head h // 2; key heads 2j and 2j + 1 (pair j) share ONE
    value [v[2j] | v[2j+1]], 2 hd wide. Then differential head i = 2j + a
    takes its positive map from query head 4j + a and its negative map from
    4j + 2 + a: o_i = (1 - lam0) RMSNorm(A_{4j+a} - lam A_{4j+2+a}), lam =
    exp(<lq1, lk1>) - exp(<lq2, lk2>) + lam0 (the gauge `attn.lambda`). No
    rotary positions. Sequences are taken as packed to their full length,
    as `gqa_attention` takes them."""

    float32_params = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")
    gauge = "attn.lambda"

    def build(self, in_specs):
        (s,) = in_specs
        assert s.is_seq, "diff_attention needs a sequence input"
        a = self.conf.attrs
        d, hd = s.size, a["head_dim"]
        h, kv = a["num_heads"], a["num_kv_heads"]
        assert h == 2 * kv and kv % 2 == 0, (
            f"differential attention pairs 2 query heads a key head and 2 "
            f"key heads a value; got {h} on {kv}")
        wide = (h + 2 * kv) * hd
        return Spec(dim=(d,), is_seq=True), _named(self, (
            ("wqkv", (d, wide)), ("bqkv", (wide,), 0.0),
            ("wo", (h * hd, d)), ("bo", (d,), 0.0),
            *((lam, (hd,)) for lam in self.float32_params),
            ("subln", (2 * hd,), 1.0)))

    def forward(self, params, inputs, ctx: Ctx):
        (arg,) = inputs
        a = self.conf.attrs
        hd, h, kv = a["head_dim"], a["num_heads"], a["num_kv_heads"]
        x = arg.value
        b, t, _ = x.shape
        with jax.named_scope("attn.qkv"):
            qkv = jnp.dot(x, params["wqkv"]) + params["bqkv"]
            q = qkv[..., : h * hd].reshape(b, t, h, hd)
            k = qkv[..., h * hd: (h + kv) * hd].reshape(b, t, kv, hd)
            v = jnp.repeat(qkv[..., (h + kv) * hd:].reshape(
                b, t, kv // 2, 2 * hd), 2, axis=2)
        with jax.named_scope("attn.core"):
            maps = _attn.gqa_attention(q, k, v, window=a.get("window"))
        with jax.named_scope("attn.diff"):
            lam0 = lambda_init(a["layer_index"])
            # float32 masters, and a sum of products: no matmul rounds it
            lam = (jnp.exp(jnp.sum(params["lambda_q1"] * params["lambda_k1"]))
                   - jnp.exp(jnp.sum(params["lambda_q2"]
                                     * params["lambda_k2"])) + lam0)
            self._set_gauge(lam)
            maps = maps.reshape(b, t, kv // 2, 2, 2, 2 * hd).astype(
                jnp.float32)
            diff = maps[:, :, :, 0] - lam * maps[:, :, :, 1]
            o = (1.0 - lam0) * _rms(diff, params["subln"],
                                    a.get("epsilon", 1e-5))
            o = o.astype(x.dtype).reshape(b, t, h * hd)
        with jax.named_scope("attn.out"):
            y = jnp.dot(o, params["wo"]) + params["bo"]
        return Arg(value=y, seq_lens=arg.seq_lens)


@LAYERS.register("mamba")
class MambaLayer(_Gauge, Layer):
    """A Mamba (selective state-space) mixer.

    attrs: d_state N (16), d_conv K (4), expand (2), dt_rank R. size = the
    model width D; d_inner C = expand x D. Params, no bias but the two
    named: w_in [D, 2 C]; conv_w [C, K] with conv_b [C]: a causal depthwise
    convolution over time, zeros before a row's start; w_x [C, R + 2 N];
    w_dt [R, C] with b_dt [C]; a_log [C, N] (A = -exp(a_log)); d [C]; w_out
    [C, D]. a_log, d, b_dt and conv_b keep their float32 masters and are
    used in float32; dt = softplus(r w_dt + b_dt), the recurrence and its
    state are float32 (ops/selective_scan.py).

        [xr | z] = u w_in;  x = silu(conv(xr));  [r | B | C] = x w_x
        h_t = exp(dt_t A) h_{t-1} + (dt_t x_t) B_t;  s_t = <h_t, C_t> + d x_t
        y = (s * silu(z)) w_out

    The state runs through a packed row with no reset at a document's
    start, as the attention layers see across them. The gauge
    `ssm.state_absmax` is the largest |h| at a chunk's end."""

    float32_params = ("a_log", "d", "b_dt", "conv_b")
    gauge = "ssm.state_absmax"

    def _sizes(self, d):
        a = self.conf.attrs
        return (a.get("expand", 2) * d, a.get("d_state", 16),
                a.get("d_conv", 4), a["dt_rank"])

    def build(self, in_specs):
        (s,) = in_specs
        assert s.is_seq, "mamba needs a sequence input"
        d = s.size
        c, n, k, r = self._sizes(d)
        return Spec(dim=(d,), is_seq=True), _named(self, (
            ("w_in", (d, 2 * c)), ("conv_w", (c, k)), ("conv_b", (c,), 0.0),
            ("w_x", (c, r + 2 * n)), ("w_dt", (r, c)), ("b_dt", (c,), 0.0),
            ("a_log", (c, n)), ("d", (c,), 1.0), ("w_out", (c, d))))

    def forward(self, params, inputs, ctx: Ctx):
        (arg,) = inputs
        u = arg.value
        c, n, _, r = self._sizes(u.shape[-1])
        with jax.named_scope("ssm.in"):
            xz = jnp.dot(u, params["w_in"])
            xr, z = xz[..., :c], xz[..., c:]
        with jax.named_scope("ssm.conv"):
            acc = _conv.causal_depthwise(xr, params["conv_w"],
                                         params["conv_b"])
            x = jax.nn.silu(acc).astype(u.dtype)
        with jax.named_scope("ssm.proj"):
            rbc = jnp.dot(x, params["w_x"])
            dt = jax.nn.softplus(
                jnp.dot(rbc[..., :r], params["w_dt"],
                        preferred_element_type=jnp.float32)
                + params["b_dt"])
        with jax.named_scope("ssm.scan"):
            s, hmax = _scan.selective_scan(
                x, dt, -jnp.exp(params["a_log"]), rbc[..., r: r + n],
                rbc[..., r + n:], params["d"], with_state_absmax=True)
            self._set_gauge(hmax)
        with jax.named_scope("ssm.gate"):
            g = (s * jax.nn.silu(z.astype(jnp.float32))).astype(u.dtype)
        with jax.named_scope("ssm.out"):
            y = jnp.dot(g, params["w_out"])
        return Arg(value=y, seq_lens=arg.seq_lens)


@LAYERS.register("short_conv")
class ShortConvLayer(_Gauge, Layer):
    """A gated short convolution mixer (the `conv` layers of LFM2).

    attrs: L (3), the convolution's length. size = the model width D.
    Params, no bias: w_in [D, 3 D], conv_w [D, L], w_out [D, D].

        [B | C | x] = u w_in;  z = B * x
        s_t = sum_j conv_w[:, j] z_{t - (L - 1) + j}, zeros before a row's
              start (a causal depthwise convolution over time)
        y = (C * s) w_out

    The gates, the convolution and C * s are float32; C * s goes to w_out in
    u's dtype (`ops/short_conv.mix`: on a TPU one Pallas pass each way that
    reads [B | C | x] as the product gives it). The state runs through a
    packed row with no reset at a document's start, as the attention layers
    see across them. The gauge `conv.mix_absmax` is the largest |C * s|."""

    gauge = "conv.mix_absmax"

    def build(self, in_specs):
        (s,) = in_specs
        assert s.is_seq, "short_conv needs a sequence input"
        d = s.size
        return Spec(dim=(d,), is_seq=True), _named(self, (
            ("w_in", (d, 3 * d)), ("conv_w", (d, self.conf.attrs.get("L", 3))),
            ("w_out", (d, d))))

    def forward(self, params, inputs, ctx: Ctx):
        (arg,) = inputs
        u = arg.value
        with jax.named_scope("conv.in"):
            bcx = jnp.dot(u, params["w_in"])
        with jax.named_scope("conv.mix"):
            y, absmax = _conv.mix(bcx, params["conv_w"])
            self._set_gauge(absmax)
        with jax.named_scope("conv.out"):
            out = jnp.dot(y, params["w_out"])
        return Arg(value=out, seq_lens=arg.seq_lens)


@LAYERS.register("gated_mlp")
class GatedMLPLayer(Layer):
    """(act(x w_gate) * (x w_up)) w_down: a decoder's dense feed-forward
    block, and the shared experts of an expert layer (n of them side by
    side are one block n times as wide). attrs: hidden (the inner width),
    hidden_act ("silu"). size = the model width. No bias."""

    def build(self, in_specs):
        (s,) = in_specs
        d, f = s.size, self.conf.attrs["hidden"]
        return s, _named(self, (("w_gate", (d, f)), ("w_up", (d, f)),
                                ("w_down", (f, d))))

    def forward(self, params, inputs, ctx: Ctx):
        (arg,) = inputs
        act = activations.get(self.conf.attrs.get("hidden_act", "silu"))
        x = arg.value
        hid = act(jnp.dot(x, params["w_gate"])) * jnp.dot(x, params["w_up"])
        return arg.with_value(jnp.dot(hid, params["w_down"]))


@LAYERS.register("lm_head_cost")
class LMHeadCostLayer(CostLayerBase):
    """The vocabulary head fused with its softmax cross-entropy, in row
    chunks (ops/lm_head.py), so that [tokens, vocabulary] logits never
    stand whole. inputs: [hidden sequence, label ids]. attrs: vocab_size,
    chunk_rows (2048), coeff. Param w0 [D, V], no bias; with `tied_to`
    (an embedding layer's name) it has no matrix of its own and reads that
    layer's table `_<tied_to>.w0` [V, D] transposed: ONE leaf, whose
    gradient is the sum of its two uses (`tie_word_embeddings`).

    A cost layer, so it is handed float32; the head's matmul operands are
    rounded to the policy's compute dtype (`Ctx.compute_dtype`: bfloat16
    under AMP) as any compute layer's are, and the logits and the cost
    stay float32. Its output is
    a row's share of the MEAN cost over the batch's real tokens, times the
    rows: the trainer's mean over rows is then the mean over tokens."""

    def build(self, in_specs):
        s = in_specs[0]
        assert s.is_seq, "lm_head_cost needs a sequence input"
        self._in_specs = in_specs
        a = self.conf.attrs
        if a.get("tied_to"):
            pc = self.weight_conf(0, (a["vocab_size"], s.size))
            pc.name, pc.is_shared = f"_{a['tied_to']}.w0", True
        else:
            pc = self.weight_conf(0, (s.size, a["vocab_size"]))
        return Spec(dim=(1,), is_seq=False), {"w0": pc}

    def forward(self, params, inputs, ctx: Ctx):
        hid, label = inputs
        a = self.conf.attrs
        b, t, d = hid.value.shape
        w = params["w0"].T if a.get("tied_to") else params["w0"]
        per = _head.chunked_softmax_cost(
            hid.value.reshape(b * t, d), w,
            label.ids.reshape(b * t), chunk=a.get("chunk_rows", 2048),
            compute_dtype=ctx.compute_dtype,
        ).reshape(b, t)
        real = hid.mask(per.dtype)
        rows = jnp.sum(per * real, axis=1)
        scale = b / jnp.maximum(jnp.sum(real), 1.0)
        return Arg(value=a.get("coeff", 1.0) * scale * rows)
