"""Configuration `kimi_vl_a3b_ep8`: the program's graph, the plain reference
beside it, and the analytic operations and bytes of the step and of its two
kernels, each from the configuration and the traffic alone: nothing of the
program is read for a count, so a change to a kernel's tiles (or a kernel
that pads a 192-wide head to 256) or to what the program recomputes moves
the time a share is measured over and never what it is a share of. The one
module that knows both the program (`paddle_tpu.models.kimi`, for
`program_conf` alone) and the reference."""

from __future__ import annotations

from benchmarks.reference import kimi as reference

BF16 = 2


def program_conf(cfg):
    from paddle_tpu.models import kimi

    return kimi(cfg)


def reference_batch(cols: dict) -> dict:
    return {"ids": cols["ids"], "label": cols["label"],
            "lens": cols["ids_lens"]}


def _seq(traffic) -> int:
    """The cell's sequence length: the top of its counted length group."""
    return int(traffic["lengths"][traffic["count"]["length_group"]][1])


def train_flops_per_row(cfg, traffic) -> float:
    """A row is a token."""
    return reference.train_flops_per_row(cfg, _seq(traffic))


def mla_attention_cost(cfg, traffic) -> dict:
    """Operations and HBM bytes of the attention kernels of one step,
    forward and backward, over every layer: {"flops", "bytes"}. The
    model's work at the model's widths, whatever tiles a kernel cuts it
    into, whatever it pads a head to, and whatever the program chooses to
    run twice (as the step's `mfu` counts it).

    Operations: per (query, key) pair the causal mask keeps, counted
    exactly (t (t + 1) / 2 a head and row): forward the scores at 2 x
    (nope + rope) and the values at 2 x v_head_dim; backward the scores
    again (no flash kernel stores them) and dq, dk at 2 x (nope + rope)
    each, dv and dp at 2 x v_head_dim each. Bytes: q and k at nope + rope
    a head, v and o at v_head_dim, read or written once forward; backward
    reads q, k, v, o, do and writes dq, dk, dv, once each (the least any
    kernel can move)."""
    t, rows = _seq(traffic), int(traffic["batch"])
    h = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    pairs = rows * h * reference.attended_keys(t)
    wide, narrow = rows * t * h * qk * BF16, rows * t * h * dv * BF16
    layers = reference.n_layers(cfg)
    forward = 2 * qk + 2 * dv
    backward = 3 * 2 * qk + 2 * 2 * dv
    return {"flops": float(layers * pairs * (forward + backward)),
            # forward q, k | v, o; backward q, k, dq, dk | v, o, do, dv
            "bytes": float(layers * ((2 * wide + 2 * narrow)
                                     + (4 * wide + 4 * narrow)))}


def moe_gmm_cost(cfg, traffic) -> dict:
    """Operations and HBM bytes of the grouped matrix products of one step,
    forward and backward, over every expert layer: {"flops", "bytes"}. The
    rows counted are the expected slots on the experts held (tokens x top-k
    x held / routed: 0.75 a token here), not the buffer's; each of the
    three projections is 3 products of 2 x rows x in x out (forward, the
    rows' gradient, the weights'); a recomputed forward is not counted.
    Bytes: each product's rows in and out and the held experts' weights,
    once a product."""
    t, rows = _seq(traffic), int(traffic["batch"])
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held, routed = cfg["n_routed_experts"], reference.router_width(cfg)
    slots = rows * t * cfg["num_experts_per_tok"] * held / routed
    flops = bytes_ = 0.0
    for i in range(reference.n_layers(cfg)):
        if reference.is_dense(cfg, i):
            continue
        for k, n in ((d, f), (d, f), (f, d)):
            flops += 3 * 2 * slots * k * n
            bytes_ += 3 * (slots * (k + n) + held * k * n) * BF16
    return {"flops": flops, "bytes": bytes_}
