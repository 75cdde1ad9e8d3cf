"""A pre-norm decoder with latent attention and shared-beside-routed
experts, built through the DSL from a model config's own keys (the language
model of Kimi-VL-A3B's `config.json`; moonshotai; the layout its family
shares: `first_k_dense_replace`, `n_routed_experts`, `n_shared_experts`,
`kv_lora_rank` ...).

    embedding -> N x [ rms_norm -> mla_attention -> addto(residual)
                       -> rms_norm -> FFN_i -> addto ]
              -> rms_norm -> lm_head_cost

`FFN_i` is a dense `gated_mlp` of `intermediate_size` for the first
`first_k_dense_replace` layers; after them a `moe` layer (sigmoid scores, a
selection bias, the top `num_experts_per_tok` of `router_experts`,
renormalised and scaled by `routed_scaling_factor`) BESIDE a `gated_mlp` of
`n_shared_experts` x `moe_intermediate_size` that every token passes: the
residual takes the two side by side (`addto` of three). The expert layer
routes over `router_experts` (default `n_routed_experts`) and holds
`n_routed_experts` of them from `experts_held_first`: one chip's share of an
expert-parallel layer, or with the defaults the whole layer; every chip of
such a layer computes attention, the dense layer and the shared experts
alike. `vocab_size` is the slice of the vocabulary held. Each block is a
recompute group of the graph when `recompute` is "block". Ids go in: the
image tower and its projector are no part of this graph.

Parameter names: `_emb.w0`, `_l{i}_norm1.w0`,
`_l{i}_attn.wq|wkva|kv_norm|wkvb|wo`, `_l{i}_norm2.w0`,
`_l{i}_mlp.w_gate|w_up|w_down` (a dense layer), or
`_l{i}_moe.router|e_score_correction_bias|w_gate|w_up|w_down` and
`_l{i}_shared.w_gate|w_up|w_down` (an expert layer), `_final_norm.w0`,
`_head.w0`.
"""

from __future__ import annotations

from paddle_tpu.core.config import ModelConf


def kimi(cfg: dict) -> ModelConf:
    """The training graph: slots `ids` and `label` (the next token at
    every position), the mean cross-entropy over real positions."""
    from paddle_tpu import dsl

    d = cfg["hidden_size"]
    eps = cfg.get("rms_norm_eps", 1e-6)
    act = cfg.get("hidden_act", "silu")
    routed = cfg.get("router_experts", cfg["n_routed_experts"])
    with dsl.model() as g:
        ids = dsl.data("ids", dim=(), is_ids=True, is_seq=True)
        label = dsl.data("label", dim=(), is_ids=True, is_seq=True)
        x = dsl.embedding(ids, size=d, vocab_size=cfg["vocab_size"],
                          name="emb")
        for i in range(cfg["num_hidden_layers"]):
            a = dsl._add("rms_norm", [x], name=f"l{i}_norm1", bias=False,
                         epsilon=eps)
            att = dsl._add(
                "mla_attention", [a], name=f"l{i}_attn", size=d, bias=False,
                num_heads=cfg["num_attention_heads"],
                kv_lora_rank=cfg["kv_lora_rank"],
                qk_nope_head_dim=cfg["qk_nope_head_dim"],
                qk_rope_head_dim=cfg["qk_rope_head_dim"],
                v_head_dim=cfg["v_head_dim"], rope_theta=cfg["rope_theta"],
                epsilon=eps,
            )
            h1 = dsl.addto(x, att, name=f"l{i}_res1")
            b = dsl._add("rms_norm", [h1], name=f"l{i}_norm2", bias=False,
                         epsilon=eps)
            if i < cfg.get("first_k_dense_replace", 0):
                ffn = [dsl._add("gated_mlp", [b], name=f"l{i}_mlp",
                                bias=False, hidden=cfg["intermediate_size"],
                                hidden_act=act)]
            else:
                ffn = [dsl._add(
                    "moe", [b], name=f"l{i}_moe", bias=False,
                    num_experts=routed, top_k=cfg["num_experts_per_tok"],
                    held=(cfg.get("experts_held_first", 0),
                          cfg["n_routed_experts"]),
                    hidden=cfg["moe_intermediate_size"], expert_act=act,
                    norm_topk=cfg.get("norm_topk_prob", True),
                    scoring_func=cfg.get("scoring_func", "softmax"),
                    topk_method=cfg.get("topk_method", "greedy"),
                    routed_scaling_factor=cfg.get("routed_scaling_factor",
                                                  1.0),
                )]
                if cfg.get("n_shared_experts"):
                    ffn.append(dsl._add(
                        "gated_mlp", [b], name=f"l{i}_shared", bias=False,
                        hidden=(cfg["n_shared_experts"]
                                * cfg["moe_intermediate_size"]),
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
