"""A pre-norm decoder whose layers differ in their attention's head count,
mask and rotary width, with a per-head gate on attention's output and
routed experts beside a shared one, built through the DSL from a model
config's own keys (Laguna-XS.2's `config.json` layout; poolside,
`model_type: "laguna"`).

    embedding -> N x [ rms_norm -> gqa_attention (gated) -> addto(residual)
                       -> rms_norm -> FFN_i -> addto ]
              -> rms_norm -> lm_head_cost

Layer `i` has `num_attention_heads_per_layer[i]` query heads on
`num_key_value_heads` KV heads; `layer_types[i]` picks its mask and its
group of `rope_parameters`: `sliding_attention` (a causal window of
`sliding_window`) or `full_attention`; a group's `partial_rotary_factor`
says how much of a head turns. With `gating` every head's output is scaled,
a token, by a sigmoid of a linear map of the block's normed input
(`gqa_attention`'s `gate="per_head"`). `FFN_i` is a dense `gated_mlp` of
`intermediate_size` where `mlp_layer_types[i]` is "dense"; else a `moe` layer
(softmax over `router_experts`, the top `num_experts_per_tok` renormalised
and scaled by `moe_routed_scaling_factor`) BESIDE a `gated_mlp` of
`shared_expert_intermediate_size` that every token passes: the residual takes
the two side by side (`addto` of three). The expert layer routes over
`router_experts` (default `num_experts`) and holds `num_experts` of them from
`experts_held_first`: one chip's share of an expert-parallel layer, or with
the defaults the whole layer; every chip of such a layer computes attention,
the dense layer and the shared expert alike. `vocab_size` is the slice of
the vocabulary held. The per-layer lists may be the published ones, whole:
the first `num_hidden_layers` entries are built. Each block is a recompute
group of the graph when `recompute` is "block".

Parameter names: `_emb.w0`, `_l{i}_norm1.w0`, `_l{i}_attn.wq|wk|wv|wo|wg`,
`_l{i}_norm2.w0`, `_l{i}_mlp.w_gate|w_up|w_down` (a dense layer), or
`_l{i}_moe.router|w_gate|w_up|w_down` and `_l{i}_shared.w_gate|w_up|w_down`
(an expert layer), `_final_norm.w0`, `_head.w0`.
"""

from __future__ import annotations

from paddle_tpu.core.config import ModelConf


def laguna(cfg: dict) -> ModelConf:
    """The training graph: slots `ids` and `label` (the next token at
    every position), the mean cross-entropy over real positions."""
    from paddle_tpu import dsl

    d = cfg["hidden_size"]
    eps = cfg.get("rms_norm_eps", 1e-6)
    act = cfg.get("hidden_act", "silu")
    routed = cfg.get("router_experts", cfg["num_experts"])
    with dsl.model() as g:
        ids = dsl.data("ids", dim=(), is_ids=True, is_seq=True)
        label = dsl.data("label", dim=(), is_ids=True, is_seq=True)
        x = dsl.embedding(ids, size=d, vocab_size=cfg["vocab_size"],
                          name="emb")
        for i in range(cfg["num_hidden_layers"]):
            kind = cfg["layer_types"][i]
            a = dsl._add("rms_norm", [x], name=f"l{i}_norm1", bias=False,
                         epsilon=eps)
            att = dsl._add(
                "gqa_attention", [a], name=f"l{i}_attn", size=d, bias=False,
                num_heads=cfg["num_attention_heads_per_layer"][i],
                num_kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"],
                window=(cfg["sliding_window"]
                        if kind == "sliding_attention" else None),
                rope=dict(cfg["rope_parameters"][kind]),
                gate="per_head" if cfg.get("gating") else None,
            )
            h1 = dsl.addto(x, att, name=f"l{i}_res1")
            b = dsl._add("rms_norm", [h1], name=f"l{i}_norm2", bias=False,
                         epsilon=eps)
            if cfg["mlp_layer_types"][i] == "dense":
                ffn = [dsl._add("gated_mlp", [b], name=f"l{i}_mlp",
                                bias=False, hidden=cfg["intermediate_size"],
                                hidden_act=act)]
            else:
                ffn = [dsl._add(
                    "moe", [b], name=f"l{i}_moe", bias=False,
                    num_experts=routed, top_k=cfg["num_experts_per_tok"],
                    held=(cfg.get("experts_held_first", 0),
                          cfg["num_experts"]),
                    hidden=cfg["moe_intermediate_size"], expert_act=act,
                    norm_topk=cfg.get("norm_topk_prob", True),
                    scoring_func="softmax",
                    routed_scaling_factor=cfg.get(
                        "moe_routed_scaling_factor", 1.0),
                )]
                if cfg.get("shared_expert_intermediate_size"):
                    ffn.append(dsl._add(
                        "gated_mlp", [b], name=f"l{i}_shared", bias=False,
                        hidden=cfg["shared_expert_intermediate_size"],
                        hidden_act=act))
            x = dsl.addto(h1, *ffn, name=f"l{i}_res2")
            if cfg.get("recompute") == "block":
                g.conf.recompute.append(
                    [f"l{i}_norm1", f"l{i}_attn", f"l{i}_res1",
                     f"l{i}_norm2", *(f.name for f in ffn), f"l{i}_res2"])
        x = dsl._add("rms_norm", [x], name="final_norm", bias=False,
                     epsilon=eps)
        dsl._add("lm_head_cost", [x, label], name="head", bias=False,
                 vocab_size=cfg["vocab_size"],
                 chunk_rows=cfg.get("head_chunk_rows", 2048))
    return g.conf
