"""Activation functions.

Parity with the reference's 14 activation classes
(paddle/gserver/activations/ActivationFunction.cpp:94-438): sigmoid,
softmax, sequence_softmax, relu, brelu, tanh, stanh, softrelu, abs,
square, exponential, reciprocal, sqrt, log (+ linear = identity).

Forward-only definitions: backward comes from jax.grad, unlike the
reference's paired forward/backward methods.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import nn as jnn

from paddle_tpu.core.registry import ACTIVATIONS

_FUNCS = {}


def register_activation(name):
    def deco(fn):
        _FUNCS[name] = fn
        ACTIVATIONS.register(name)(type("Act_" + name, (), {"fn": staticmethod(fn)}))
        return fn

    return deco


def get(name: str):
    if name in ("", "linear", None):
        return lambda x: x
    try:
        return _FUNCS[name]
    except KeyError:
        raise KeyError(
            f"unknown activation {name!r}; known: {sorted(_FUNCS)}"
        ) from None


register_activation("sigmoid")(jnn.sigmoid)
register_activation("relu")(jnn.relu)
register_activation("tanh")(jnp.tanh)
register_activation("silu")(jnn.silu)  # the gated expert's; not of the 14
register_activation("abs")(jnp.abs)
register_activation("square")(jnp.square)
register_activation("exponential")(jnp.exp)
register_activation("sqrt")(jnp.sqrt)
register_activation("log")(jnp.log)


@register_activation("softmax")
def softmax(x):
    return jnn.softmax(x, axis=-1)


@register_activation("brelu")
def brelu(x):
    # bounded relu: min(max(x, 0), 24) (ActivationFunction.cpp BRelu)
    return jnp.clip(x, 0.0, 24.0)


@register_activation("stanh")
def stanh(x):
    # scaled tanh: 1.7159 * tanh(2/3 x)
    return 1.7159 * jnp.tanh(x * (2.0 / 3.0))


@register_activation("softrelu")
def softrelu(x):
    # log(1 + exp(x)), input clipped to +-40 as in the reference
    return jnn.softplus(jnp.clip(x, -40.0, 40.0))


@register_activation("reciprocal")
def reciprocal(x):
    return 1.0 / x


@register_activation("sequence_softmax")
def sequence_softmax_unmasked(x):
    """Placeholder registration — real sequence softmax needs the mask and
    lives in ops.sequence_ops.masked_softmax; layers route there when the
    input is a sequence."""
    return jnn.softmax(x, axis=-1)
