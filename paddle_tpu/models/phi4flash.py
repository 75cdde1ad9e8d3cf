"""A pre-norm decoder whose mixers alternate between Mamba layers and
differential attention, built through the DSL from a model config's own keys
(Phi-4-mini-flash-reasoning's `config.json`; microsoft; `model_type`
phi4flash: `mb_per_layer`, `sliding_window`, `layer_norm_eps`,
`tie_word_embeddings` ...).

    embedding -> N x [ layer_norm -> Mixer_l -> addto(residual)
                       -> layer_norm -> gated_mlp -> addto ]
              -> layer_norm -> lm_head_cost over the embedding itself

The graph holds `num_hidden_layers` layers of the `published_layers` the
model has, from the published index `first_layer` on: one stage of a
pipeline, or with the defaults the model's first decoder. Which mixer a
published layer `l` of `n` has (`layer_kind`): a `mamba` layer if `l %
mb_per_layer == 0`, else `diff_attention`, over a window of `sliding_window`
if `l < n / 2`, full causal at `l == n / 2 + 1`. From `l >= n / 2 + 2` the
model's second decoder follows: gated memory units (a gate over layer `n /
2`'s scan output, no scan of their own) and cross-attention over layer `n / 2
+ 1`'s keys and values. The program has neither, and the builder says so. No
rotary positions, no positional signal of any kind; ids are embedded with no
scale. The head is tied (`tie_word_embeddings`): it reads the embedding's one
leaf. `vocab_size` is the slice of the vocabulary held. Each block is a
recompute group of the graph when `recompute` is "block".

Parameter names, `l` the PUBLISHED index: `_emb.w0`, `_l{l}_norm1.w0|b0`,
`_l{l}_mamba.w_in|conv_w|conv_b|w_x|w_dt|b_dt|a_log|d|w_out` or
`_l{l}_attn.wqkv|bqkv|wo|bo|lambda_q1|lambda_k1|lambda_q2|lambda_k2|subln`,
`_l{l}_norm2.w0|b0`, `_l{l}_mlp.w_gate|w_up|w_down`, `_final_norm.w0|b0`.
"""

from __future__ import annotations

from paddle_tpu.core.config import ModelConf


def layer_kind(cfg: dict, l: int) -> str:
    """"mamba", "window" or "full" for the published layer `l`; raises for
    a layer of the second decoder, naming the mechanism it needs."""
    n = int(cfg.get("published_layers", cfg["num_hidden_layers"]))
    if not 0 <= l < n:
        raise ValueError(f"the model has layers 0 to {n - 1}; got {l}")
    if l >= n // 2 + 2:
        if l % cfg["mb_per_layer"] == 0:
            what = (f"a gated memory unit: a gate over layer {n // 2}'s "
                    "scan output, which no layer exports")
        else:
            what = (f"cross-attention over layer {n // 2 + 1}'s keys and "
                    "values, which no layer exports")
        raise NotImplementedError(
            f"published layer {l} of {n} belongs to the second decoder and "
            f"is {what}")
    if l % cfg["mb_per_layer"] == 0:
        return "mamba"
    return "window" if l < n // 2 else "full"


def _dt_rank(cfg) -> int:
    rank = cfg.get("mamba_dt_rank", "auto")
    return -(-cfg["hidden_size"] // 16) if rank == "auto" else int(rank)


def phi4flash(cfg: dict) -> ModelConf:
    """The training graph: slots `ids` and `label` (the next token at
    every position), the mean cross-entropy over real positions."""
    from paddle_tpu import dsl

    d = cfg["hidden_size"]
    eps = cfg.get("layer_norm_eps", 1e-5)
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    first = int(cfg.get("first_layer", 0))
    with dsl.model() as g:
        ids = dsl.data("ids", dim=(), is_ids=True, is_seq=True)
        label = dsl.data("label", dim=(), is_ids=True, is_seq=True)
        x = dsl.embedding(ids, size=d, vocab_size=cfg["vocab_size"],
                          name="emb")
        for l in range(first, first + cfg["num_hidden_layers"]):
            kind = layer_kind(cfg, l)
            a = dsl._add("layer_norm", [x], name=f"l{l}_norm1", bias=False,
                         epsilon=eps)
            if kind == "mamba":
                mixer = dsl._add(
                    "mamba", [a], name=f"l{l}_mamba", size=d, bias=False,
                    d_state=cfg.get("mamba_d_state", 16),
                    d_conv=cfg.get("mamba_d_conv", 4),
                    expand=cfg.get("mamba_expand", 2),
                    dt_rank=_dt_rank(cfg))
            else:
                mixer = dsl._add(
                    "diff_attention", [a], name=f"l{l}_attn", size=d,
                    bias=False, num_heads=heads, num_kv_heads=kv,
                    head_dim=d // heads, layer_index=l, epsilon=eps,
                    window=(cfg["sliding_window"] if kind == "window"
                            else None))
            h1 = dsl.addto(x, mixer, name=f"l{l}_res1")
            b = dsl._add("layer_norm", [h1], name=f"l{l}_norm2", bias=False,
                         epsilon=eps)
            ffn = dsl._add("gated_mlp", [b], name=f"l{l}_mlp", bias=False,
                           hidden=cfg["intermediate_size"],
                           hidden_act=cfg.get("hidden_act", "silu"))
            x = dsl.addto(h1, ffn, name=f"l{l}_res2")
            if cfg.get("recompute") == "block":
                g.conf.recompute.append(
                    [f"l{l}_norm1", mixer.name, f"l{l}_res1", f"l{l}_norm2",
                     f"l{l}_mlp", f"l{l}_res2"])
        x = dsl._add("layer_norm", [x], name="final_norm", bias=False,
                     epsilon=eps)
        head = {"tied_to": "emb"} if cfg.get("tie_word_embeddings") else {}
        dsl._add("lm_head_cost", [x, label], name="head", bias=False,
                 vocab_size=cfg["vocab_size"],
                 chunk_rows=cfg.get("head_chunk_rows", 2048), **head)
    return g.conf
