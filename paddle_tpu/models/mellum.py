"""A present-day pre-norm decoder, built through the DSL from a model
config's own keys (Mellum 2's `config.json` layout; JetBrains,
`model_type: "mellum"`).

    embedding -> N x [ rms_norm -> gqa_attention -> addto(residual)
                       -> rms_norm -> moe (top-k, held share) -> addto ]
              -> rms_norm -> lm_head_cost

`layer_types[i]` picks the layer's attention: `sliding_attention` (a causal
window of `sliding_window`, plain rotary positions) or `full_attention`
(YaRN-scaled rotary positions), each with its group of `rope_parameters`.
The expert layer routes over `router_experts` (default `num_experts`) and
holds `num_experts` of them from `experts_held_first`: one chip's share of
an expert-parallel layer, or with the defaults the whole layer. `vocab_size`
is the slice of the vocabulary held. Each block is a recompute group of the
graph when `recompute` is "block".

Parameter names: `_emb.w0`, `_l{i}_norm1.w0`, `_l{i}_attn.wq|wk|wv|wo`,
`_l{i}_norm2.w0`, `_l{i}_moe.router|w_gate|w_up|w_down`, `_final_norm.w0`,
`_head.w0`.
"""

from __future__ import annotations

from paddle_tpu.core.config import ModelConf


def mellum(cfg: dict) -> ModelConf:
    """The training graph: slots `ids` and `label` (the next token at
    every position), the mean cross-entropy over real positions."""
    from paddle_tpu import dsl

    d = cfg["hidden_size"]
    eps = cfg.get("rms_norm_eps", 1e-6)
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
                num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"],
                window=(cfg["sliding_window"]
                        if kind == "sliding_attention" else None),
                rope=dict(cfg["rope_parameters"][kind]),
            )
            h1 = dsl.addto(x, att, name=f"l{i}_res1")
            b = dsl._add("rms_norm", [h1], name=f"l{i}_norm2", bias=False,
                         epsilon=eps)
            moe = dsl._add(
                "moe", [b], name=f"l{i}_moe", bias=False,
                num_experts=routed, top_k=cfg["num_experts_per_tok"],
                held=(cfg.get("experts_held_first", 0), cfg["num_experts"]),
                hidden=cfg["moe_intermediate_size"],
                expert_act=cfg.get("hidden_act", "silu"),
                norm_topk=cfg.get("norm_topk_prob", True),
            )
            x = dsl.addto(h1, moe, name=f"l{i}_res2")
            if cfg.get("recompute") == "block":
                g.conf.recompute.append(
                    [f"l{i}_norm1", f"l{i}_attn", f"l{i}_res1",
                     f"l{i}_norm2", f"l{i}_moe", f"l{i}_res2"])
        x = dsl._add("rms_norm", [x], name="final_norm", bias=False,
                     epsilon=eps)
        dsl._add("lm_head_cost", [x, label], name="head", bias=False,
                 vocab_size=cfg["vocab_size"],
                 chunk_rows=cfg.get("head_chunk_rows", 2048))
    return g.conf
