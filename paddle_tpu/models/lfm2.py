"""A pre-norm hybrid decoder of gated short convolutions and QK-normed
grouped attention over dense and routed-expert feed-forward blocks, built
through the DSL from a model config's own keys (LFM2-8B-A1B's `config.json`;
LiquidAI, `model_type: "lfm2_moe"`).

    embedding -> N x [ rms_norm -> Mixer_l -> addto(residual)
                       -> rms_norm -> FFN_l -> addto ]
              -> rms_norm -> lm_head_cost over the embedding itself

The graph holds the published layers `layers_held` (default the first
`num_hidden_layers`): one stage of a pipeline, or with the default the whole
model. `layer_types[l]` of the published list, which may stand whole, picks
`Mixer_l`: "conv" a `short_conv` of `conv_L_cache`, "full_attention" a
`gqa_attention` of `num_attention_heads` on `num_key_value_heads` heads of
hidden / heads, QK-normed, rotary at `rope_theta` over the whole head, full
causal. `FFN_l` is a dense `gated_mlp` of `intermediate_size` for a
published `l` below `num_dense_layers`; after them a `moe` layer (sigmoid
scores, with `use_expert_bias` a selection bias, the top
`num_experts_per_tok` renormalised and scaled by `routed_scaling_factor`)
that routes over `router_experts` (default `num_experts`) and holds
`num_experts` of them from `experts_held_first`: one chip's share of an
expert-parallel layer, or with the defaults the whole layer; every chip of
such a layer computes the mixers and the dense layer alike. No shared
expert. The head is tied to the embedding (`tie_word_embeddings`).
`vocab_size` is the slice of the vocabulary held. Each block is a recompute
group of the graph when `recompute` is "block".

Parameter names, `l` the PUBLISHED index: `_emb.w0`, `_l{l}_norm1.w0`,
`_l{l}_conv.w_in|conv_w|w_out` or `_l{l}_attn.wq|wk|wv|wo|q_norm|k_norm`,
`_l{l}_norm2.w0`, `_l{l}_mlp.w_gate|w_up|w_down` (a dense layer) or
`_l{l}_moe.router|e_score_correction_bias|w_gate|w_up|w_down`,
`_final_norm.w0`.
"""

from __future__ import annotations

from paddle_tpu.core.config import ModelConf


def lfm2(cfg: dict) -> ModelConf:
    """The training graph: slots `ids` and `label` (the next token at
    every position), the mean cross-entropy over real positions."""
    from paddle_tpu import dsl

    d = cfg["hidden_size"]
    eps = cfg.get("norm_eps", 1e-5)
    act = cfg.get("hidden_act", "silu")
    heads = cfg["num_attention_heads"]
    held = cfg.get("layers_held", range(cfg["num_hidden_layers"]))
    assert len(held) == cfg["num_hidden_layers"], held
    assert not cfg.get("conv_bias"), "short_conv has no bias"
    with dsl.model() as g:
        ids = dsl.data("ids", dim=(), is_ids=True, is_seq=True)
        label = dsl.data("label", dim=(), is_ids=True, is_seq=True)
        x = dsl.embedding(ids, size=d, vocab_size=cfg["vocab_size"],
                          name="emb")
        for l in held:
            a = dsl._add("rms_norm", [x], name=f"l{l}_norm1", bias=False,
                         epsilon=eps)
            if cfg["layer_types"][l] == "conv":
                mixer = dsl._add("short_conv", [a], name=f"l{l}_conv",
                                 size=d, bias=False,
                                 L=cfg.get("conv_L_cache", 3))
            else:
                mixer = dsl._add(
                    "gqa_attention", [a], name=f"l{l}_attn", size=d,
                    bias=False, num_heads=heads,
                    num_kv_heads=cfg["num_key_value_heads"],
                    head_dim=d // heads, window=None,
                    rope={"rope_theta": cfg["rope_theta"]}, qk_norm=True,
                    epsilon=eps)
            h1 = dsl.addto(x, mixer, name=f"l{l}_res1")
            b = dsl._add("rms_norm", [h1], name=f"l{l}_norm2", bias=False,
                         epsilon=eps)
            if l < cfg.get("num_dense_layers", 0):
                ffn = dsl._add("gated_mlp", [b], name=f"l{l}_mlp",
                               bias=False, hidden=cfg["intermediate_size"],
                               hidden_act=act)
            else:
                ffn = dsl._add(
                    "moe", [b], name=f"l{l}_moe", bias=False,
                    num_experts=cfg.get("router_experts", cfg["num_experts"]),
                    top_k=cfg["num_experts_per_tok"],
                    held=(cfg.get("experts_held_first", 0),
                          cfg["num_experts"]),
                    hidden=cfg["moe_intermediate_size"], expert_act=act,
                    norm_topk=cfg.get("norm_topk_prob", True),
                    scoring_func="sigmoid",
                    topk_method=("noaux_tc" if cfg.get("use_expert_bias")
                                 else "greedy"),
                    routed_scaling_factor=cfg.get("routed_scaling_factor",
                                                  1.0))
            x = dsl.addto(h1, ffn, name=f"l{l}_res2")
            if cfg.get("recompute") == "block":
                g.conf.recompute.append(
                    [f"l{l}_norm1", mixer.name, f"l{l}_res1", f"l{l}_norm2",
                     ffn.name, f"l{l}_res2"])
        x = dsl._add("rms_norm", [x], name="final_norm", bias=False,
                     epsilon=eps)
        head = {"tied_to": "emb"} if cfg.get("tie_word_embeddings") else {}
        dsl._add("lm_head_cost", [x, label], name="head", bias=False,
                 vocab_size=cfg["vocab_size"],
                 chunk_rows=cfg.get("head_chunk_rows", 2048), **head)
    return g.conf
