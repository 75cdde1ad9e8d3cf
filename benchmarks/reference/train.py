"""Seeded weights and the reference's first training steps.

`init_params` makes a configuration's weights on the device in one jitted
call from the seed; the program and the reference are both handed these.
`first_steps` drives a plain reference (`reference/<model>.py: loss`)
through the same batches with a plain optimizer and returns what the
comparison reads: each step's loss, the norm of every leaf's first
gradient, and the norm of every leaf's change after the last step.

Faults that the comparison has to catch can be planted here, in the
reference put in the program's place (`fault=`): `half_batch` trains on the
first half of every batch and takes the mean over it; `quarter_batch` on
the first quarter, which is what one of four chips computes when the
exchange of gradients between them is left out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

FAULTS = {"half_batch": 2, "quarter_batch": 4}


@functools.partial(jax.jit, static_argnums=(0,))
def _init(spec_items, key):
    out = {}
    for i, (name, shape, (kind, x)) in enumerate(spec_items):
        if kind == "const":
            out[name] = jnp.full(shape, x, jnp.float32)
        else:
            k = jax.random.fold_in(key, i)
            out[name] = x * jax.random.normal(k, shape, jnp.float32)
    return out


def init_params(spec: dict, seed: int) -> dict:
    """name -> float32 array on the default device. Threefry, keyed by the
    seed and the leaf's place in the sorted spec: the same on any backend."""
    items = tuple((n, tuple(s), tuple(init)) for n, (s, init)
                  in sorted(spec.items()))
    key = jax.random.key(int(seed) % (2 ** 31), impl="threefry2x32")
    return _init(items, key)


def _update(opt, p, g, s, t):
    """One leaf, one step. -> (new p, new state). `t` counts from 0."""
    lr = opt["learning_rate"]
    if opt["method"] == "momentum":
        v = opt["momentum"] * s["mom"] - lr * g
        return p + v, {"mom": v}
    if opt["method"] == "adam":
        b1, b2, eps = opt["beta1"], opt["beta2"], opt["epsilon"]
        m = b1 * s["m"] + (1 - b1) * g
        v = b2 * s["v"] + (1 - b2) * jnp.square(g)
        mhat = m / (1 - b1 ** (t + 1.0))
        vhat = v / (1 - b2 ** (t + 1.0))
        return p - lr * mhat / (jnp.sqrt(vhat) + eps), {"m": m, "v": v}
    raise ValueError(f"unknown optimizer method {opt['method']!r}")


def opt_init(opt, params):
    keys = {"momentum": ("mom",), "adam": ("m", "v")}[opt["method"]]
    return {n: {k: jnp.zeros_like(p) for k in keys}
            for n, p in params.items()}


def first_gradient_from_state(opt, opt_state_leaf):
    """The first gradient as the optimizer got it, worked out from its
    state after ONE step from a zero state."""
    if opt["method"] == "momentum":
        return -opt_state_leaf["mom"] / opt["learning_rate"]
    if opt["method"] == "adam":
        return opt_state_leaf["m"] / (1 - opt["beta1"])
    raise ValueError(f"unknown optimizer method {opt['method']!r}")


def leaf_norms(tree) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


@jax.jit
def delta(p, p0):
    return leaf_norms({n: p[n] - p0[n] for n in p})


@functools.lru_cache(maxsize=None)
def _step_program(loss_fn, opt_items, fault):
    """One jitted reference step per (loss, optimizer, fault): a tool that
    reads many seeds in one process compiles it once."""
    opt = dict(opt_items)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(p, s, batch, t):
        if fault is not None:
            batch = {k: v[: v.shape[0] // FAULTS[fault]]
                     for k, v in batch.items()}
        loss, g = jax.value_and_grad(loss_fn)(p, batch)
        new = {n: _update(opt, p[n], g[n], s[n], t) for n in p}
        return ({n: v[0] for n, v in new.items()},
                {n: v[1] for n, v in new.items()}, loss, leaf_norms(g))

    return step


def first_steps(loss_fn, opt, params, batches, fault=None):
    """-> {"loss": [per step], "grad1": {leaf: norm}, "delta": {leaf:
    norm of the change after the last step}} as Python floats.
    `loss_fn(params, batch) -> scalar`. `params` is consumed."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; one of {sorted(FAULTS)}")
    step = _step_program(loss_fn, tuple(sorted(opt.items())), fault)

    p0 = jax.tree_util.tree_map(jnp.copy, params)
    state = opt_init(opt, params)
    losses, grad1 = [], None
    for t, batch in enumerate(batches):
        params, state, loss, gn = step(params, state, batch,
                                       jnp.float32(t))
        losses.append(float(loss))
        if t == 0:
            grad1 = {k: float(v) for k, v in gn.items()}
    d = {k: float(v) for k, v in delta(params, p0).items()}
    return {"loss": losses, "grad1": grad1, "delta": d}
