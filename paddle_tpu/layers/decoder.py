"""The layers of a present-day pre-norm decoder block, beside the `moe`
layer (layers/moe.py): `rms_norm`, `gqa_attention`, `mla_attention`,
`gated_mlp`, `lm_head_cost`.

`models/mellum.py` and `models/kimi.py` build decoders from them through
the DSL; their parameter names (`_<layer>.w0`, `.wq` ...) are what a plain
reference's `param_spec` names too, so one set of seeded weights serves
both.
"""

from __future__ import annotations

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


@LAYERS.register("gqa_attention")
class GQAAttentionLayer(Layer):
    """Causal self-attention with grouped query heads and rotary positions.

    attrs: num_heads, num_kv_heads, head_dim; window (a query sees the
    last `window` positions, itself included) or None; rope (the model
    config's group: rope_theta, and for `rope_type: "yarn"` factor,
    original_max_position_embeddings, beta_fast, beta_slow,
    attention_factor). size = the model width. Params wq [D, H*hd],
    wk, wv [D, KV*hd], wo [H*hd, D]; no bias. Sequences are taken as
    packed to their full length: positions past `seq_lens` are computed
    like any other (causality keeps them out of the real ones) and masked
    by the cost."""

    def build(self, in_specs):
        (s,) = in_specs
        assert s.is_seq, "gqa_attention needs a sequence input"
        a = self.conf.attrs
        d, hd = s.size, a["head_dim"]
        h, kv = a["num_heads"], a["num_kv_heads"]
        assert h % kv == 0, f"{h} heads do not divide over {kv} KV heads"
        pcs = {}
        for slot, dims in (("wq", (d, h * hd)), ("wk", (d, kv * hd)),
                           ("wv", (d, kv * hd)), ("wo", (h * hd, d))):
            pc = self.weight_conf(0, dims)
            pc.name = f"_{self.name}.{slot}"
            pcs[slot] = pc
        return Spec(dim=(d,), is_seq=True), pcs

    def forward(self, params, inputs, ctx: Ctx):
        (arg,) = inputs
        a = self.conf.attrs
        hd, h, kv = a["head_dim"], a["num_heads"], a["num_kv_heads"]
        x = arg.value
        b, t, _ = x.shape
        q = jnp.dot(x, params["wq"]).reshape(b, t, h, hd)
        k = jnp.dot(x, params["wk"]).reshape(b, t, kv, hd)
        v = jnp.dot(x, params["wv"]).reshape(b, t, kv, hd)
        with jax.named_scope("attn.rope"):
            cos, sin = _rope.tables(t, hd, a["rope"])
            q, k = _rope.apply(q, cos, sin), _rope.apply(k, cos, sin)
        with jax.named_scope("attn.core"):
            o = _attn.gqa_attention(q, k, v, window=a.get("window"))
        y = jnp.dot(o.reshape(b, t, h * hd), params["wo"])
        return Arg(value=y, seq_lens=arg.seq_lens)


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
        pcs = {}
        for slot, dims in (("wq", (d, h * (dn + dr))), ("wkva", (d, r + dr)),
                           ("kv_norm", (r,)), ("wkvb", (r, h * (dn + dv))),
                           ("wo", (h * dv, d))):
            pc = self.weight_conf(0, dims)
            pc.name = f"_{self.name}.{slot}"
            if slot == "kv_norm" and pc.initial_std is None:
                pc.initial_strategy, pc.initial_value = "constant", 1.0
            pcs[slot] = pc
        return Spec(dim=(d,), is_seq=True), pcs

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


@LAYERS.register("gated_mlp")
class GatedMLPLayer(Layer):
    """(act(x w_gate) * (x w_up)) w_down: a decoder's dense feed-forward
    block, and the shared experts of an expert layer (n of them side by
    side are one block n times as wide). attrs: hidden (the inner width),
    hidden_act ("silu"). size = the model width. No bias."""

    def build(self, in_specs):
        (s,) = in_specs
        d, f = s.size, self.conf.attrs["hidden"]
        pcs = {}
        for slot, dims in (("w_gate", (d, f)), ("w_up", (d, f)),
                           ("w_down", (f, d))):
            pc = self.weight_conf(0, dims)
            pc.name = f"_{self.name}.{slot}"
            pcs[slot] = pc
        return s, pcs

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
    chunk_rows (2048), coeff. Param w0 [D, V], no bias.

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
        pc = self.weight_conf(0, (s.size, self.conf.attrs["vocab_size"]))
        return Spec(dim=(1,), is_seq=False), {"w0": pc}

    def forward(self, params, inputs, ctx: Ctx):
        hid, label = inputs
        a = self.conf.attrs
        b, t, d = hid.value.shape
        per = _head.chunked_softmax_cost(
            hid.value.reshape(b * t, d), params["w0"],
            label.ids.reshape(b * t), chunk=a.get("chunk_rows", 2048),
            compute_dtype=ctx.compute_dtype,
        ).reshape(b, t)
        real = hid.mask(per.dtype)
        rows = jnp.sum(per * real, axis=1)
        scale = b / jnp.maximum(jnp.sum(real), 1.0)
        return Arg(value=a.get("coeff", 1.0) * scale * rows)
