"""The vocabulary head and its softmax cross-entropy in row chunks.

Float32 logits of [tokens, vocabulary] are the largest tensor of a language
model's step (16,384 x 24,576 x 4 bytes = 1.6 GB, and as much again for
their gradient). Here a chunk of rows at a time goes through the head, its
log-sum-exp and its picked logit, and the chunk's logits are rematerialised
in the backward pass: what is stored is [tokens] costs and one chunk.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def chunked_softmax_cost(x, w, labels, chunk: int = 2048,
                         compute_dtype=None):
    """-log softmax(x @ w)[label] a row. x [N, D], w [D, V], labels [N]
    int32 -> [N] float32. Operands in `compute_dtype` (default x's),
    accumulation, logits and everything after them in float32. `chunk`
    rows at a time (cut to a divisor of N); chunk >= N is the unchunked
    cost."""
    n = x.shape[0]
    dt = compute_dtype or x.dtype
    x, w = x.astype(dt), w.astype(dt)

    def cost(xc, lc):
        logits = jnp.dot(xc, w, preferred_element_type=jnp.float32)
        picked = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked

    if chunk >= n:
        return cost(x, labels)
    chunk = math.gcd(n, chunk)
    per = jax.lax.map(
        jax.checkpoint(lambda xl: cost(*xl)),
        (x.reshape(n // chunk, chunk, -1), labels.reshape(n // chunk, chunk)),
    )
    return per.reshape(n)
