"""The precisions a plain reference is computed in.

`f32`   the reference itself: float32 operands, `highest` matmul precision
        (on a TPU a float32 matmul is otherwise one bf16 pass).
`bf16`  the precision the configurations state: every layer but the cost
        computes in bfloat16, so matmul operands AND every layer's output
        are rounded to bfloat16 (float32 accumulation). For diagnosis only.
`fp8`   the control: the same policy one precision lower. Operands and
        layer outputs are rounded to float8_e4m3 with one scale per tensor,
        the cotangents that come back through them to float8_e5m2, as fp8
        training does; accumulation stays float32.

Nothing here imports the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

MODES = ("f32", "bf16", "fp8")


def _round_to(x, dtype):
    """Round to `dtype` with one scale per tensor, value kept in float32."""
    top = float(jnp.finfo(dtype).max)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / top, 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8(x):
    return _round_to(x, jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (_round_to(g, jnp.float8_e5m2),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def operand(x, mode: str):
    """An operand of a matmul or convolution, as `mode` holds it."""
    x = x.astype(jnp.float32)
    if mode == "f32":
        return x
    if mode == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if mode == "fp8":
        return _fp8(x)
    raise ValueError(f"unknown precision mode {mode!r}; one of {MODES}")


def act(x, mode: str):
    """A layer's output, as `mode` stores it: every layer but the cost
    hands its successor a value of the configuration's compute type."""
    return operand(x, mode)


def dot(x, w, mode: str):
    return jnp.dot(operand(x, mode), operand(w, mode),
                   precision=lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def conv(x, w, stride: int, pad: int, mode: str):
    """NHWC x HWIO convolution."""
    return lax.conv_general_dilated(
        operand(x, mode), operand(w, mode), (stride, stride),
        ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
