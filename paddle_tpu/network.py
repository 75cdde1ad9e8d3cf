"""Network: config -> executable pure functions.

The analogue of the reference's NeuralNetwork gradient machine
(gserver/gradientmachines/NeuralNetwork.cpp:68,235,285): build layers from
ModelConf, walk them in topological order for forward. There is no
hand-written backward walk — `loss_fn` is differentiated with jax.grad and
the whole step jit-compiles to one XLA program (the TPU-idiomatic
equivalent of forward+backward+update fusion).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from paddle_tpu.core import flags as _flags
from paddle_tpu.core.arg import Arg
from paddle_tpu.core.config import ModelConf, ParameterConf
from paddle_tpu.layers.base import Ctx, create_layer, init_parameter
from paddle_tpu.obs import get_registry
from paddle_tpu.ops import gqa_attention as _attention
from paddle_tpu.ops import selective_scan as _scan


def _cast_arg(a: Arg, dtype) -> Arg:
    """Cast an Arg's float value to `dtype` (ids/lens untouched)."""
    if a.value is None or a.value.dtype not in (
        jnp.float32,
        jnp.bfloat16,
    ):
        return a
    if a.value.dtype == dtype:
        return a
    return a.with_value(a.value.astype(dtype))

# ensure all layer types are registered
import paddle_tpu.layers  # noqa: F401


# What a recompute group keeps from its forward to its backward beside its
# inputs: the values tagged with these names where they are produced
# (jax.ad_checkpoint.checkpoint_name), so that what produced them alone, a
# kernel call, is dead code in the second forward. Worth a name is a kernel
# call's output that its backward reads (the attention kernel's output and
# log-sum-exp, the scan's output and chunk-end states), not an elementwise
# value XLA fuses again for nothing. ONE object for every group and every
# trace: jax caches its partial evaluation of a sub-jaxpr (an expert
# layer's passes, shared by every block) by the policy's identity
_KEEP = jax.checkpoint_policies.save_only_these_names(
    _attention.KEPT, _scan.KEPT)


class Network:
    def __init__(self, conf: ModelConf):
        self.conf = conf
        self.layers = {}
        self.specs = {}
        self._extra_producer: dict[str, str] = {}
        self.param_confs: dict[str, ParameterConf] = {}  # global name -> conf
        self.layer_params: dict[str, dict] = {}  # layer -> {slot: global name}
        self._stateful: dict[str, object] = {}
        order = []
        for lc in conf.layers:
            layer = create_layer(lc, conf)
            self.layers[lc.name] = layer
            for n in lc.input_names():
                if n not in self.specs:
                    raise KeyError(
                        f"layer {lc.name!r} input {n!r} is not defined above it "
                        f"(layers must be in topological order)"
                    )
            in_specs = [self.specs[n] for n in lc.input_names()]
            spec, pcs = layer.build(in_specs)
            self.specs[lc.name] = spec
            slot_map = {}
            for slot, pc in pcs.items():
                if pc is None:
                    continue
                if pc.name in self.param_confs:
                    # shared parameter: dims must agree
                    prev = self.param_confs[pc.name]
                    assert tuple(prev.dims) == tuple(pc.dims), (
                        f"shared param {pc.name} dim mismatch"
                    )
                else:
                    self.param_confs[pc.name] = pc
                slot_map[slot] = pc.name
            self.layer_params[lc.name] = slot_map
            if hasattr(layer, "init_state"):
                self._stateful[lc.name] = layer
            if hasattr(layer, "extra_output_specs"):
                for xname, xspec in layer.extra_output_specs().items():
                    if xname in self.specs:
                        raise KeyError(
                            f"extra output {xname!r} of layer {lc.name!r} "
                            f"collides with an existing layer name"
                        )
                    self.specs[xname] = xspec
                    self._extra_producer[xname] = lc.name
            order.append(lc.name)
        self.order = order
        # extra outputs that carry a layer's counters (`<name>@stats`),
        # each with the layer that publishes it: the step keeps them, and
        # the trainer hands them to their layers at its fence
        self.stat_outputs = {
            layer.stats_output: layer for layer in self.layers.values()
            if getattr(layer, "stats_output", None)
        }
        self._groups = self._recompute_groups(conf.recompute)
        self.output_names = list(conf.output_layer_names) or (
            [order[-1]] if order else []
        )
        self.cost_names = [
            n for n in order if getattr(self.layers[n], "is_cost", False)
        ]
        # Declared outputs built FROM cost layers by layer arithmetic
        # (e.g. the VAE's `outputs(reconstruct_error(...) + KL_loss(...))`
        # where the KL term is scaled 0.5× via slope_intercept) are the
        # training objective themselves: the reference's cost is the sum
        # of the OUTPUT arguments (TrainerInternal.cpp:135 Argument::sum),
        # so such an output replaces its cost-layer ancestors in the
        # loss — counting the unscaled ancestors would mis-weight it.
        derived = []
        absorbed = set()
        for out_name in self.output_names:
            out_name = self._extra_producer.get(out_name, out_name)
            if getattr(self.layers.get(out_name), "is_cost", False):
                continue
            anc = set()
            frontier = [out_name]
            while frontier:
                n = frontier.pop()
                n = self._extra_producer.get(n, n)
                if n in anc:
                    continue
                anc.add(n)
                frontier.extend(self.conf.layer(n).input_names())
            cost_anc = [c for c in self.cost_names if c in anc]
            if cost_anc:
                derived.append(out_name)
                absorbed.update(cost_anc)
        if derived:
            self.cost_names = [
                n for n in self.cost_names if n not in absorbed
            ] + derived
        self.input_names = list(conf.input_layer_names) or [
            lc.name for lc in conf.layers if lc.type == "data"
        ]

    def _recompute_groups(self, groups) -> dict:
        """first layer's name -> the group's names, each group checked: its
        layers exist, follow one another in the graph's order, and keep no
        state (a recomputed forward must be a pure function)."""
        out = {}
        for names in groups:
            names = list(names)
            at = self.order.index(names[0]) if names[0] in self.order else -1
            if at < 0 or self.order[at: at + len(names)] != names:
                raise ValueError(
                    f"recompute group {names} is not a run of consecutive "
                    f"layers of the graph"
                )
            stateful = [n for n in names if n in self._stateful]
            if stateful or any(self.conf.layer(n).type == "data"
                               for n in names):
                raise ValueError(
                    f"recompute group {names} holds data or stateful "
                    f"layers {stateful}"
                )
            out[names[0]] = names
        return out

    # ---- parameters & state ----
    def init_params(self, key: jax.Array, dtype=jnp.float32) -> dict:
        params = {}
        names = sorted(self.param_confs)
        keys = jax.random.split(key, max(len(names), 1))
        for k, name in zip(keys, names):
            params[name] = init_parameter(k, self.param_confs[name], dtype)
        return params

    def init_state(self) -> dict:
        return {name: layer.init_state() for name, layer in self._stateful.items()}

    def _layer_param_view(self, name: str, params: dict) -> dict:
        return {slot: params[g] for slot, g in self.layer_params[name].items()}


    # ---- execution ----
    def forward(
        self,
        params: dict,
        feed: dict,
        *,
        state: Optional[dict] = None,
        train: bool = False,
        rng: Optional[jax.Array] = None,
        outputs: Optional[list] = None,
    ):
        """Run all layers. Returns (outputs: {layer_name: Arg}, new_state).

        `feed` maps data-layer names to Arg. Mirrors NeuralNetwork::forward
        (NeuralNetwork.cpp:235) with passType train/test folded into
        Ctx.train."""
        if state is None:
            state = self.init_state()
        # Mixed precision (flags "matmul_precision" = "bfloat16"): master
        # params stay float32; per consuming edge below, compute-layer
        # operands are cast to bfloat16 (halved HBM traffic, single-pass
        # MXU) while cost-layer operands stay float32 so targets and
        # loss math keep full precision. The casts are inside the
        # autodiff region, so grads flow back to the float32 masters
        # (classic master-weight AMP).
        amp = _flags.get_flag("matmul_precision") in ("bfloat16", "bf16")
        ctx = Ctx(train=train, rng=rng, state=state,
                  compute_dtype=jnp.bfloat16 if amp else None)
        outs: dict[str, Arg] = {}
        if outputs is not None:
            # run only the ancestor closure of the requested outputs
            # (inference prunes cost layers and their label inputs)
            run = set()
            frontier = list(outputs)
            while frontier:
                n = frontier.pop()
                n = self._extra_producer.get(n, n)  # extra out -> its group
                if n in run:
                    continue
                run.add(n)
                frontier.extend(self.conf.layer(n).input_names())
            order = [n for n in self.order if n in run]
        else:
            order = self.order
        needed = {
            n
            for ln in order
            for n in self.conf.layer(ln).input_names()
        }
        for name in order:
            lc = self.conf.layer(name)
            if lc.type == "data":
                if name in feed:
                    outs[name] = feed[name]
                elif name in needed:
                    raise KeyError(
                        f"data layer {name!r} is consumed by the network but "
                        f"missing from feed (fed: {sorted(feed)})"
                    )
                continue
            if train and outputs is None and name in self._groups:
                self._run_group(self._groups[name], outs, params, ctx, amp)
            elif name not in outs:
                self._run_layer(name, outs, params, ctx, amp)
        new_state = {**ctx.state, **ctx.updated_state}
        return outs, new_state

    def _run_layer(self, name, outs, params, ctx, amp) -> None:
        """One layer: its inputs from `outs`, its output (and any extra
        outputs) into `outs`."""
        lc = self.conf.layer(name)
        layer = self.layers[name]
        inputs = [outs[n] for n in lc.input_names()]
        layer_params = self._layer_param_view(name, params)
        if amp:
            # per consuming EDGE: cost layers see float32 (targets
            # straight from the feed keep full precision even if the
            # same data layer also feeds compute layers), everything
            # else computes in bfloat16, but for the parameters a layer
            # names in `float32_params` (a router's: its logits decide a
            # discrete choice), which it gets as the float32 masters
            to = (
                jnp.float32
                if getattr(layer, "is_cost", False)
                else jnp.bfloat16
            )
            inputs = [_cast_arg(a, to) for a in inputs]
            keep = getattr(layer, "float32_params", ())
            layer_params = {
                k: (
                    v.astype(to)
                    if v.dtype in (jnp.float32, jnp.bfloat16)
                    and k not in keep
                    else v
                )
                for k, v in layer_params.items()
            }
        try:
            with jax.named_scope(f"{lc.type}:{name}"):
                outs[name] = layer.forward(layer_params, inputs, ctx)
        except Exception as e:
            # the layer-stack-on-crash context of the reference's
            # CustomStackTrace (utils/CustomStackTrace.h:51, pushed
            # per layer in NeuralNetwork.cpp:249-251)
            e.add_note(
                f"  while running layer {name!r} "
                f"(type={lc.type!r}, inputs={lc.input_names()})"
            )
            raise
        spec = lc.attrs.get("out_sharding")
        if spec is not None:
            # Per-layer placement hint — the GSPMD replacement for the
            # reference's ParallelNeuralNetwork per-layer `device` attr
            # (gserver/gradientmachines/ParallelNeuralNetwork.h:34).
            from jax.sharding import PartitionSpec
            from paddle_tpu.core.mesh import get_mesh
            from paddle_tpu.parallel.sharding import constrain

            outs[name] = constrain(
                outs[name], get_mesh(), PartitionSpec(*spec)
            )
        extra = getattr(layer, "_extra_outs", None)
        if extra:
            outs.update(extra)

    def _run_group(self, names, outs, params, ctx, amp) -> None:
        """A recompute group: its layers as one function of the group's
        parameters and of what it reads from outside, under
        jax.checkpoint, so that the backward pass keeps its inputs and
        runs its forward again, but for the values its layers tagged with
        a name `_KEEP` knows: those it keeps. The bfloat16 copies of its
        weights are made inside and so are recomputed too. The gauge
        `recompute.kept_bytes` by `group` (the group's first layer) is what
        the group holds beyond its inputs: the bytes its ops tagged
        (`ops.note_kept`) while its forward was traced."""
        inside = set(names)
        reads = sorted({
            n for ln in names for n in self.conf.layer(ln).input_names()
            if n not in inside
        })
        pnames = sorted({
            g for ln in names for g in self.layer_params[ln].values()
        })

        def group(gparams, gin, rng):
            local = dict(gin)
            c = Ctx(train=ctx.train, rng=rng, state={},
                    compute_dtype=ctx.compute_dtype)
            for ln in names:
                self._run_layer(ln, local, gparams, c, amp)
            return {k: v for k, v in local.items() if k not in gin}

        # in a trace the recomputed forward reads as
        # `.../rematted_computation/<type>:<name>/...`, the first as
        # `.../checkpoint/<type>:<name>/...`: no scope of its own, so that
        # an operation's outermost scope stays its layer's
        tagged = get_registry().counter("recompute.tagged_bytes")
        before = tagged.get()
        outs.update(jax.checkpoint(group, policy=_KEEP)(
            {g: params[g] for g in pnames},
            {n: outs[n] for n in reads}, ctx.rng,
        ))
        get_registry().gauge("recompute.kept_bytes").set(
            int(tagged.get() - before), group=names[0])

    def loss_fn(self, params, feed, state=None, train=True, rng=None):
        """Scalar batch-mean cost over all cost layers — what
        TrainerInternal reduces via Argument::sum (TrainerInternal.cpp:135).
        Returns (loss, (outputs, new_state))."""
        outs, new_state = self.forward(
            params, feed, state=state, train=train, rng=rng
        )
        assert self.cost_names, "network has no cost layer"
        total = 0.0
        for n in self.cost_names:
            total = total + jnp.mean(outs[n].value)
        return total, (outs, new_state)
