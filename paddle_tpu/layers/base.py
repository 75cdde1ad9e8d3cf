"""Layer base class and spec plumbing.

Reference: paddle/gserver/layers/Layer.h:56 (class Layer) — there, a layer
owns mutable output state and hand-written forward/backward methods
dispatched per device. Here a layer is a *pure-function module*: `build`
declares output spec + parameter specs from input specs; `forward` maps
(params, inputs) -> Arg. Backward is jax.grad over the whole network —
an intentional, idiomatic divergence with identical observable behavior.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp

from paddle_tpu.core.config import LayerConf, ModelConf, ParameterConf
from paddle_tpu.core.registry import LAYERS
from paddle_tpu.ops import activations


@dataclass(frozen=True)
class Spec:
    """Static description of a layer output (per-example feature shape,
    sequence-ness, dtype). The analogue of LayerConfig.size plus the image
    shape attrs the reference threads through config_parser."""

    dim: tuple = ()  # per-timestep feature shape, e.g. (784,) or (28,28,32)
    is_seq: bool = False
    has_subseq: bool = False
    is_ids: bool = False
    dtype: object = jnp.float32

    @property
    def size(self) -> int:
        n = 1
        for d in self.dim:
            n *= d
        return n


@dataclass
class Ctx:
    """Per-call context: train/test phase + RNG (for dropout/sampling)."""

    train: bool = False
    rng: Optional[jax.Array] = None
    # non-parameter persistent state (e.g. batch-norm running stats):
    # layers read ctx.state[layer_name] and write ctx.updated_state[layer_name]
    state: dict = field(default_factory=dict)
    updated_state: dict = field(default_factory=dict)
    extra: dict = field(default_factory=dict)
    # the dtype Network's precision policy has compute layers work in
    # (bfloat16 under AMP), None without a policy. A cost layer is handed
    # float32 whatever the policy; one that holds a matmul rounds that
    # matmul's operands to this
    compute_dtype: Optional[object] = None

    def split(self, name: str) -> jax.Array:
        assert self.rng is not None, "layer needs rng but Ctx.rng is None"
        import zlib

        return jax.random.fold_in(self.rng, zlib.crc32(name.encode()))


class Layer:
    """Base layer. Subclasses set `type_names` via @LAYERS.register and
    implement build() and forward()."""

    def __init__(self, conf: LayerConf, model: ModelConf):
        self.conf = conf
        self.name = conf.name

    # ---- static graph construction ----
    def build(self, in_specs: list) -> tuple:
        """Return (out_spec, param_confs) where param_confs maps *local*
        param slot -> ParameterConf (with dims filled in)."""
        raise NotImplementedError

    def forward(self, params: dict, inputs: list, ctx: Ctx):
        raise NotImplementedError

    # ---- helpers ----
    def activation(self):
        return activations.get(self.conf.active_type)

    def apply_activation_and_dropout(self, y, ctx: Ctx, seq_lens=None):
        if self.conf.active_type == "sequence_softmax":
            from paddle_tpu.ops import sequence_ops

            assert seq_lens is not None, "sequence_softmax needs sequence input"
            sq = y.shape[-1] == 1
            y2 = y[..., 0] if sq else y
            y2 = sequence_ops.masked_softmax(y2, seq_lens)
            y = y2[..., None] if sq else y2
        else:
            y = self.activation()(y)
        rate = self.conf.drop_rate
        if rate > 0.0 and ctx.train:
            keep = 1.0 - rate
            m = jax.random.bernoulli(ctx.split(self.name + "/drop"), keep, y.shape)
            y = jnp.where(m, y / keep, 0.0)
        return y

    def weight_conf(self, idx: int, dims: tuple) -> ParameterConf:
        """Materialize a ParameterConf for input edge `idx` with dims.
        Returns a copy — never mutates the user's InputConf.parameter, so a
        layer may call this twice for one edge and parameter sharing stays
        by-name, not by-aliased-object."""
        import dataclasses

        ic = self.conf.inputs[idx]
        pc = (
            dataclasses.replace(ic.parameter)
            if ic.parameter is not None
            else ParameterConf()
        )
        if not pc.name:
            pc.name = f"_{self.name}.w{idx}"
        pc.dims = tuple(dims)
        return pc

    def bias_conf(self, dims: tuple) -> Optional[ParameterConf]:
        import dataclasses

        if not self.conf.bias:
            return None
        pc = (
            dataclasses.replace(self.conf.bias_parameter)
            if self.conf.bias_parameter is not None
            else ParameterConf()
        )
        if not pc.name:
            pc.name = f"_{self.name}.wbias"
        pc.dims = tuple(dims)
        return pc


def init_parameter(key: jax.Array, pc: ParameterConf, dtype=jnp.float32):
    """Initialize one parameter per its config.

    Matches the reference's defaults (paddle/parameter/Parameter.cpp
    randomize(): normal with std 1/sqrt(fan_in) for weights, zeros for
    biases/1-D unless initial_std is set)."""
    dims = tuple(pc.dims)
    if pc.initializer is not None:
        # user callback name -> ndarray (v2 ParameterAttribute
        # initializer; reference parameters.py __initialize_with__)
        return jnp.asarray(pc.initializer(pc.name), dtype).reshape(dims)
    if pc.initial_strategy == "zero":
        return jnp.zeros(dims, dtype)
    if pc.initial_strategy == "constant":
        return jnp.full(dims, pc.initial_value, dtype)
    std = pc.initial_std
    if std is None:
        if len(dims) == 1:
            return jnp.full(dims, pc.initial_mean, dtype)
        fan_in = dims[0] if len(dims) == 2 else int(jnp.prod(jnp.asarray(dims[:-1])))
        std = 1.0 / (fan_in ** 0.5)
    if pc.initial_strategy == "uniform":
        u = jax.random.uniform(key, dims, dtype, -1.0, 1.0)
        return pc.initial_mean + std * u
    return pc.initial_mean + std * jax.random.normal(key, dims, dtype)


def create_layer(conf: LayerConf, model: ModelConf) -> Layer:
    return LAYERS.get(conf.type)(conf, model)
