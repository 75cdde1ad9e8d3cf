"""Configuration `lfm2_8b_a1b_ep4`: the program's graph, the plain reference
beside it, and the analytic operations and bytes of the step and of its two
kernels, each from the configuration and the traffic alone: nothing of the
program is read for a count, so a change to a kernel's tiles or to what the
program recomputes moves the time a share is measured over and never what
it is a share of. The one module that knows both the program
(`paddle_tpu.models.lfm2`, for `program_conf` alone) and the reference."""

from __future__ import annotations

from benchmarks.reference import lfm2 as reference

BF16 = 2


def program_conf(cfg):
    from paddle_tpu.models import lfm2

    return lfm2(cfg)


def reference_batch(cols: dict) -> dict:
    return {"ids": cols["ids"], "label": cols["label"],
            "lens": cols["ids_lens"]}


def _seq(traffic) -> int:
    """The cell's sequence length: the top of its counted length group."""
    return int(traffic["lengths"][traffic["count"]["length_group"]][1])


def train_flops_per_row(cfg, traffic) -> float:
    """A row is a token."""
    return reference.train_flops_per_row(cfg, _seq(traffic))


def qk_norm_attention_cost(cfg, traffic) -> dict:
    """Operations and HBM bytes of the attention kernels of one step,
    forward and backward, over every attention layer: {"flops", "bytes"}.
    The model's work, whatever tiles a kernel cuts it into and whatever the
    program chooses to run twice (as the step's `mfu` counts it); the norms
    of q and k and the rotary positions before the kernel are not kernel
    work and are not counted.

    Operations: per (query, key) pair the causal mask keeps, counted exactly
    (t (t + 1) / 2 a head and row): 2 matmuls of 2 x head_dim forward
    (scores, values) and 5 backward (the scores again, which no flash kernel
    stores, then dv, dp, dq, dk). Bytes: q, k, v read and o written once
    forward; backward reads q, k, v, o, do and writes dq, dk, dv, once each
    (the least any kernel can move)."""
    t, rows = _seq(traffic), int(traffic["batch"])
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 reference.head_dim(cfg))
    q_o, k_v = rows * t * h * hd * BF16, rows * t * kv * hd * BF16
    pairs = rows * h * reference.attended_keys(t)
    layers = sum(not reference.is_conv(cfg, l)
                 for l in reference.layers_held(cfg))
    return {"flops": float(layers * pairs * 2 * hd * (2 + 5)),
            "bytes": float(layers * ((2 * q_o + 2 * k_v)
                                     + (4 * q_o + 4 * k_v)))}


def moe_gmm_cost(cfg, traffic) -> dict:
    """Operations and HBM bytes of the grouped matrix products of one step,
    forward and backward, over every expert layer: {"flops", "bytes"}. The
    rows counted are the expected slots on the experts held (tokens x top-k
    x held / routed: one slot a token here), not the buffer's; each of the
    three projections is 3 products of 2 x rows x in x out (forward, the
    rows' gradient, the weights'); a recomputed forward is not counted.
    Bytes: each product's rows in and out and the held experts' weights,
    once a product."""
    t, rows = _seq(traffic), int(traffic["batch"])
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held, routed = cfg["num_experts"], reference.router_width(cfg)
    slots = rows * t * cfg["num_experts_per_tok"] * held / routed
    flops = bytes_ = 0.0
    for l in reference.layers_held(cfg):
        if reference.is_dense(cfg, l):
            continue
        for k, n in ((d, f), (d, f), (f, d)):
            flops += 3 * 2 * slots * k * n
            bytes_ += 3 * (slots * (k + n) + held * k * n) * BF16
    return {"flops": flops, "bytes": bytes_}
