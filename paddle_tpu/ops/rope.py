"""Rotary positions: plain and YaRN-scaled frequencies, half-split pairing.

`rope` is the model config's own group for a layer type, e.g.
`{"rope_type": "default", "rope_theta": 500000}` or `{"rope_type": "yarn",
"rope_theta": 500000, "factor": 16, "original_max_position_embeddings":
8192, "beta_fast": 32, "beta_slow": 1, "attention_factor": 1.277...}`.
With `partial_rotary_factor` below 1 in the group only the leading part of a
head turns (`rotary_width`): frequencies, the YaRN blend and the tables are
taken over that width, and `apply` passes the rest of the head through.
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np


def yarn_range(head_dim, theta, original, beta_fast, beta_slow):
    """(lo, hi) pair indices YaRN blends between: a pair below `lo` turns
    more than `beta_fast` times within `original` positions and keeps its
    frequency; one above `hi` turns fewer than `beta_slow` times and is
    divided by the factor."""
    def pair_at(rotations):
        return (head_dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(pair_at(beta_fast)), 0)
    hi = min(math.ceil(pair_at(beta_slow)), head_dim - 1)
    return lo, hi


def rotary_width(head_dim: int, rope: dict) -> int:
    """The leading dims of a head that turn: `partial_rotary_factor` of it
    (the whole head without the key)."""
    return int(head_dim * rope.get("partial_rotary_factor", 1))


def inv_freq(head_dim: int, rope: dict):
    """-> ([head_dim / 2] float64 numpy frequencies, factor on cos/sin);
    `head_dim` is the width that turns."""
    i = np.arange(head_dim // 2, dtype=np.float64)
    freq = float(rope["rope_theta"]) ** (-2.0 * i / head_dim)
    if rope.get("rope_type", "default") != "yarn":
        return freq, 1.0
    lo, hi = yarn_range(head_dim, rope["rope_theta"],
                        rope["original_max_position_embeddings"],
                        rope["beta_fast"], rope["beta_slow"])
    keep = 1.0 - np.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    freq = freq / rope["factor"] * (1.0 - keep) + freq * keep
    factor = rope.get("attention_factor")
    if factor is None:
        factor = 0.1 * math.log(rope["factor"]) + 1.0
    return freq, float(factor)


def tables(t: int, head_dim: int, rope: dict):
    """cos, sin [T, head_dim / 2] float32 for positions 0..T-1, computed
    on the device (no T-sized constant in the program); `head_dim` is the
    width that turns."""
    freq, factor = inv_freq(head_dim, rope)
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None]
           * jnp.asarray(freq, jnp.float32)[None, :])
    return jnp.cos(ang) * factor, jnp.sin(ang) * factor


def apply(x, cos, sin):
    """x [B, T, n, head_dim], cos and sin [T, r / 2]: of the leading r dims
    pair (i, i + r / 2) is turned by the position's angle, float32 inside,
    x's dtype out; the dims past r (none when the tables are the head's
    width) pass through untouched."""
    r = 2 * cos.shape[-1]
    if r < x.shape[-1]:
        return jnp.concatenate([apply(x[..., :r], cos, sin), x[..., r:]],
                               axis=-1)
    hd = x.shape[-1]
    xf = x.astype(jnp.float32)
    a, b = xf[..., : hd // 2], xf[..., hd // 2:]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([a * c - b * s, b * c + a * s],
                           axis=-1).astype(x.dtype)
