"""Configuration `phi4_mini_flash_pp8`: the program's graph, the plain
reference beside it, and the analytic operations and bytes of the step and of
its two kernels, each from the configuration and the traffic alone: nothing of
the program is read for a count, so a change to a kernel's tiles (or a scan
that keeps its operands in float32, or recomputes a chunk's states) or to what
the program recomputes moves the time a share is measured over and never what
it is a share of. The one module that knows both the program
(`paddle_tpu.models.phi4flash`, for `program_conf` alone) and the reference."""

from __future__ import annotations

from benchmarks.reference import phi4flash as reference

BF16, F32 = 2, 4


def program_conf(cfg):
    from paddle_tpu.models import phi4flash

    return phi4flash(cfg)


def reference_batch(cols: dict) -> dict:
    return {"ids": cols["ids"], "label": cols["label"],
            "lens": cols["ids_lens"]}


def _seq(traffic) -> int:
    """The cell's sequence length: the top of its counted length group."""
    return int(traffic["lengths"][traffic["count"]["length_group"]][1])


def train_flops_per_row(cfg, traffic) -> float:
    """A row is a token."""
    return reference.train_flops_per_row(cfg, _seq(traffic))


def _kinds(cfg) -> list:
    return [reference.kind_of(cfg, l) for l in reference.layers_held(cfg)]


def selective_scan_cost(cfg, traffic) -> dict:
    """Operations and HBM bytes of the scan kernels of one step, forward and
    backward, over every Mamba layer: {"flops", "bytes"}. The model's work,
    whatever a kernel recomputes (a chunk's states in its backward pass,
    the whole forward under the block's recomputation: neither is counted).

    Operations: per position, channel and state 9 forward (the decay's
    product and exponential, the input's two products, the state's product
    and sum, the output's product and sum, D x shared over the states) and
    18 backward. This is vector work: the chip's matrix peak does not bound
    it, and `peaks.json` has no vector peak, so the bytes set the time
    allowed. Bytes: x and s at the compute type (bfloat16) and dt at float32
    a channel, B and C at bfloat16 a state, read or written once forward;
    backward reads x, dt, B, C, ds and writes dx, d dt, dB, dC, once each
    (the least any kernel can move; A and D are 0.4 MB)."""
    t, rows = _seq(traffic), int(traffic["batch"])
    c, n, _, _ = reference.mamba_sizes(cfg)
    layers = _kinds(cfg).count("mamba")
    forward = c * (BF16 + F32 + BF16) + 2 * n * BF16
    backward = (c * (BF16 + F32 + BF16) + 2 * n * BF16       # x, dt, ds, B, C
                + c * (BF16 + F32) + 2 * n * BF16)           # dx, d dt, dB, dC
    return {"flops": float(layers * rows * t * c * n * (9 + 18)),
            "bytes": float(layers * rows * t * (forward + backward))}


def diff_attention_cost(cfg, traffic) -> dict:
    """Operations and HBM bytes of the attention kernels of one step,
    forward and backward, over every attention layer: {"flops", "bytes"}.
    The model's work at the model's widths, whatever tiles a kernel cuts it
    into, whatever it pads a 64-wide head to, and whatever the program runs
    twice.

    Operations: per (query, key) pair the mask keeps, counted exactly (the
    causal triangle, on the window layer cut to the window), for each of the
    query heads (both maps of a differential head are computed): forward
    the score at 2 x head_dim and the value at 2 x 2 head_dim; backward the
    score again (no flash kernel stores it) and dq, dk at 2 x head_dim
    each, dv and dp at 2 x 2 head_dim each. Bytes: q a query head and k a key
    head at head_dim, v a PAIR of key heads at 2 head_dim (the pair's one
    value, whatever a kernel's grouping repeats), o a query head at 2
    head_dim, read or written once forward; backward reads q, k, v, o, do and
    writes dq, dk, dv, once each."""
    t, rows = _seq(traffic), int(traffic["batch"])
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // h
    tokens = rows * t
    q, k = tokens * h * hd * BF16, tokens * kv * hd * BF16
    v, o = tokens * (kv // 2) * 2 * hd * BF16, tokens * h * 2 * hd * BF16
    forward = 2 * hd + 2 * 2 * hd
    backward = 3 * 2 * hd + 2 * 2 * 2 * hd
    flops = bytes_ = 0.0
    for kind in _kinds(cfg):
        if kind == "mamba":
            continue
        window = cfg["sliding_window"] if kind == "window" else None
        pairs = rows * h * reference.attended_keys(t, window)
        flops += pairs * (forward + backward)
        # forward q, k, v, o; backward q, k, v, o, do, dq, dk, dv
        bytes_ += (q + k + v + o) + (2 * q + 2 * k + 2 * v + 2 * o)
    return {"flops": flops, "bytes": bytes_}
