"""ResNet-50 v1 (He et al. 2015, arXiv:1512.03385, table 1), plain jax.numpy.

Stem 7x7/2 + BN + ReLU, 3x3/2 max pool, bottleneck stages (3, 4, 6, 3) of
1x1 -> 3x3 -> 1x1(x4) with a projection shortcut on each stage's first
block and the stride on the first 1x1, global average pool, 1000-way fc,
softmax cross entropy averaged over the batch. Batch norm normalises with
the batch's own mean and biased variance (eps 1e-5) in training.

Float32 throughout; `mode` is the precision of the convolution and matmul
operands and of every layer's output (reference/precision.py). Each bottleneck is rematerialised in the
backward pass so that float32 activations of 256 images fit one chip; that
changes what is stored, not what is computed. Imports nothing of the
program; the parameter names are the ones the program's graph gives its
layers, since the benchmark hands one set of seeded weights to both.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference import precision as P

STAGES = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}
EPS = 1e-5


def _blocks(cfg):
    for si, (n, ch) in enumerate(zip(STAGES[cfg["depth"]],
                                     cfg["stage_channels"])):
        for bi in range(n):
            yield (f"res{si + 2}{chr(ord('a') + bi)}", ch,
                   2 if (si > 0 and bi == 0) else 1, bi == 0)


def param_spec(cfg) -> dict:
    """name -> (shape, ("normal", std) | ("const", value))."""
    spec = {}

    def conv(name, k, cin, cout):
        spec[f"_{name}.w0"] = ((k, k, cin, cout),
                               ("normal", (2.0 / (k * k * cin)) ** 0.5))

    def bn(name, c):
        spec[f"_{name}.w0"] = ((c,), ("const", 1.0))
        spec[f"_{name}.wbias"] = ((c,), ("const", 0.0))

    cin = cfg["image_shape"][2]
    conv("conv1", 7, cin, cfg["stem_channels"])
    bn("conv1_bn", cfg["stem_channels"])
    cin = cfg["stem_channels"]
    for name, ch, _stride, project in _blocks(cfg):
        conv(f"{name}_a", 1, cin, ch)
        bn(f"{name}_a_bn", ch)
        conv(f"{name}_b", 3, ch, ch)
        bn(f"{name}_b_bn", ch)
        conv(f"{name}_c", 1, ch, ch * 4)
        bn(f"{name}_c_bn", ch * 4)
        if project:
            conv(f"{name}_sc", 1, cin, ch * 4)
            bn(f"{name}_sc_bn", ch * 4)
        cin = ch * 4
    spec["_output.w0"] = ((cin, cfg["num_classes"]),
                          ("normal", 1.0 / cin ** 0.5))
    spec["_output.wbias"] = ((cfg["num_classes"],), ("const", 0.0))
    return spec


def _bn(p, name, x, mode, relu):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    inv = lax.rsqrt(var + EPS)
    y = (x - mean) * (inv * p[f"_{name}.w0"]) + p[f"_{name}.wbias"]
    return P.act(jax.nn.relu(y) if relu else y, mode)


def _conv(p, name, x, stride, pad, mode):
    return P.act(P.conv(x, p[f"_{name}.w0"], stride, pad, mode), mode)


def _bottleneck(p, x, name, stride, project, mode):
    h = _conv(p, f"{name}_a", x, stride, 0, mode)
    h = _bn(p, f"{name}_a_bn", h, mode, True)
    h = _conv(p, f"{name}_b", h, 1, 1, mode)
    h = _bn(p, f"{name}_b_bn", h, mode, True)
    h = _conv(p, f"{name}_c", h, 1, 0, mode)
    h = _bn(p, f"{name}_c_bn", h, mode, False)
    if project:
        sc = _conv(p, f"{name}_sc", x, stride, 0, mode)
        sc = _bn(p, f"{name}_sc_bn", sc, mode, False)
    else:
        sc = x
    return P.act(jax.nn.relu(h + sc), mode)


def loss(cfg, params, batch, mode="f32"):
    """Mean softmax cross entropy of one batch: image [B, H*W*C], label [B]."""
    h, w, c = cfg["image_shape"]
    x = batch["image"].astype(jnp.float32).reshape(-1, h, w, c)
    x = _conv(params, "conv1", x, 2, 3, mode)
    x = _bn(params, "conv1_bn", x, mode, True)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          ((0, 0), (1, 1), (1, 1), (0, 0)))
    for name, _ch, stride, project in _blocks(cfg):
        names = [k for k in params if k.startswith(f"_{name}_")]
        block = jax.checkpoint(
            lambda p, x, name=name, stride=stride, project=project:
            _bottleneck(p, x, name, stride, project, mode))
        x = block({k: params[k] for k in names}, x)
    x = P.act(jnp.mean(x, axis=(1, 2)), mode)
    logits = P.act(P.dot(x, params["_output.w0"], mode)
                   + params["_output.wbias"], mode)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, batch["label"][:, None], axis=-1)
    return jnp.mean(lse - picked[:, 0])


def train_flops_per_row(cfg) -> float:
    """Analytic training FLOPs of one image: 2 per multiply-add of every
    convolution and the fc, times 3 (forward, and the two matmuls of the
    backward pass). Recomputation is not counted."""
    h = cfg["image_shape"][0]
    macs = 0

    def conv(k, cin, cout, out_hw):
        return k * k * cin * cout * out_hw * out_hw

    hw = h // 2
    macs += conv(7, cfg["image_shape"][2], cfg["stem_channels"], hw)
    hw //= 2
    cin = cfg["stem_channels"]
    for _name, ch, stride, project in _blocks(cfg):
        out = hw // stride
        macs += conv(1, cin, ch, out) + conv(3, ch, ch, out)
        macs += conv(1, ch, ch * 4, out)
        if project:
            macs += conv(1, cin, ch * 4, out)
        cin, hw = ch * 4, out
    macs += cin * cfg["num_classes"]
    return 3 * 2 * float(macs)
