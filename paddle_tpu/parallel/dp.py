"""Data-parallel (and sharded-state) training over a device mesh.

Replaces three reference subsystems with one compiled program:
- MultiGradientMachine's per-device TrainerThreads + ring gradient merge
  (gserver/gradientmachines/MultiGradientMachine.cpp:389,502-598),
- the C++ sync parameter server (pserver/ParameterServer2.h:254,482,660:
  barriers, gradient add, server-side op_SGD),
- the Go pserver's dense shards (go/pserver/service.go:221,240).

TPU-first: the batch is sharded over the mesh "data" axis; params are
either replicated or sharded (ZeRO-style, the optimizer-state analogue of
pserver block shards). XLA inserts the psum/all-gather over ICI. The
optimizer runs sharded on-device — there is no parameter-server process.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.analysis.recompile_guard import RecompileGuard
from paddle_tpu.core import compile_cache
from paddle_tpu.core.mesh import DATA_AXIS
from paddle_tpu.obs import tracing as _tracing


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Arg leaves are [B, ...]: shard batch dim over the data axis."""
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def param_sharding(mesh: Mesh, pc=None) -> NamedSharding:
    """Parameter placement: delegated to the tensor-parallel auto rules
    (parallel/sharding.py) — replicated on a pure-data mesh, model-sharded
    weights / row-sharded embedding tables when a `model` axis exists."""
    from paddle_tpu.parallel.sharding import auto_param_spec

    if pc is None:
        return NamedSharding(mesh, P())
    return NamedSharding(mesh, auto_param_spec(pc, mesh))


def shard_batch(feed: dict, mesh: Optional[Mesh], sharding=None,
                timeline=None) -> dict:
    """The one function that places a fed batch on the device: on a
    mesh with batch-dim sharding (or `sharding`), with a mesh of None
    as uncommitted arrays on the default device (`jax.device_put(x)`
    with no device and no sharding: the step lowers, compiles and
    caches as it does for a numpy feed). The transfer is a call of its
    own under the span `train.h2d`, which says what it took and is
    handed to `timeline` (an obs.StepTimeline) where one is given. A
    feed that is placed already (every leaf a device array, on a mesh
    with this sharding) is returned as it is, under no span: the
    training loop places a batch a step ahead, an external loop may
    hand `TrainStep` numpy."""
    sh = None
    if mesh is not None:
        sh = sharding or batch_sharding(mesh)
    leaves = jax.tree_util.tree_leaves(feed)
    if all(isinstance(x, jax.Array) and (
            sh is None or x.sharding.is_equivalent_to(sh, x.ndim))
            for x in leaves):
        return feed
    nbytes = sum(getattr(x, "nbytes", 0) for x in leaves)
    with _tracing.span("train.h2d", bytes=nbytes) as moved:
        placed = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, sh), feed)
    if timeline is not None:
        timeline.add(moved)
    return placed


class TrainStep:
    """One jit-compiled train step: forward + grad + optimizer update.

    With a mesh, the feed is sharded over DATA_AXIS and params/opt-state
    are placed per `param_sharding`; XLA emits the gradient allreduce over
    ICI (the compiled replacement for ADD_GRADIENT + barriers,
    ParameterService.proto:24-41).

    With `watchdog=True` the step additionally computes an on-device
    all-finite reduction over the loss and every gradient leaf, SKIPS
    the whole update when any value is non-finite (params, opt-state
    and layer state keep their previous values — a bad batch can never
    poison the model), takes an `lr_scale` operand (the watchdog's
    backoff/re-warm multiplier; a traced scalar, so changing it never
    recompiles), and returns a 2-float `health` vector
    `[loss, all_finite]` IN PLACE of the scalar loss — the finiteness
    verdict rides the loss fetch the trainer already pays for, so the
    happy path adds zero device->host transfers."""

    def __init__(
        self,
        net,
        opt,
        mesh: Optional[Mesh] = None,
        donate=True,
        keep_outputs=None,
        sharding_rules=None,
        watchdog=False,
    ):
        compile_cache.watch()  # what the step's first call spends
        self.net = net
        self.opt = opt
        self.mesh = mesh
        self.sharding_rules = sharding_rules
        self.watchdog = watchdog
        # Only declared outputs survive the step: returning every layer's
        # activations would pin all intermediates in HBM and block XLA
        # fusion/rematerialization.
        keep = set(keep_outputs or []) | set(net.output_names) | set(
            net.cost_names
        ) | set(getattr(net, "stat_outputs", ()))
        # jit-cache-miss tracker (ISSUE 13): note() runs at TRACE
        # time only (it is a plain Python call in the traced body),
        # so the cached dispatch path pays nothing. The trainer arms
        # it after warmup; an armed retrace is a steady-state
        # recompile — the silent seconds-long stall the dispatch
        # -floor work exists to kill.
        guard = self.recompile_guard = RecompileGuard("train_step")

        def step(params, opt_state, state, feed, step_i, rng,
                 lr_scale=None):
            guard.note(params, feed)
            # every device operation of the step has a scope a trace
            # can be read by: `<type>:<name>` from Network.forward
            # (backward: the same under `transpose(jvp(...))`),
            # `optimizer`, `watchdog`. Scopes are metadata: the
            # compiled program and its cache key do not change.
            (loss, (outs, new_state)), grads = jax.value_and_grad(
                net.loss_fn, has_aux=True
            )(params, feed, state=state, train=True, rng=rng)
            with jax.named_scope("optimizer"):
                new_params, new_opt_state = opt.update(
                    grads, params, opt_state, step_i, lr_scale=lr_scale
                )
            outs = {k: v for k, v in outs.items() if k in keep}
            if not watchdog:
                return new_params, new_opt_state, new_state, loss, outs
            # all-finite reduction, fused into the update program: a
            # handful of per-leaf reductions + ANDs, no extra pass over
            # activations and no host sync
            with jax.named_scope("watchdog"):
                finite = jnp.isfinite(loss)
                for g in jax.tree_util.tree_leaves(grads):
                    finite = finite & jnp.all(jnp.isfinite(g))

                def _keep(new, old):
                    return jnp.where(finite, new, old)

                new_params = jax.tree_util.tree_map(
                    _keep, new_params, params
                )
                new_opt_state = jax.tree_util.tree_map(
                    _keep, new_opt_state, opt_state
                )
                new_state = jax.tree_util.tree_map(
                    _keep, new_state, state)
                health = jnp.stack([
                    loss.astype(jnp.float32),
                    finite.astype(jnp.float32),
                ])
            return new_params, new_opt_state, new_state, health, outs

        if mesh is not None:
            from paddle_tpu.parallel.sharding import Sharder

            rep = replicated(mesh)
            data = batch_sharding(mesh)
            sharder = Sharder(mesh, rules=sharding_rules)
            param_sh = sharder.param_shardings(net.param_confs)

            def param_tree_sharding(params):
                return {k: param_sh.get(k, rep) for k in params}

            self._param_sh = param_sh
            self._rep = rep
            self._data = data
            # in_shardings: params, opt_state (match params), state (rep),
            # feed (data), step (rep), rng (rep)
            self._step = jax.jit(
                step,
                donate_argnums=(0, 1, 2) if donate else (),
            )
        else:
            self._step = jax.jit(
                step, donate_argnums=(0, 1, 2) if donate else ()
            )

        # multi-step pipelining (ROADMAP 5d): N consecutive steps as a
        # lax.scan over the SAME step body inside ONE jitted dispatch,
        # so short-step models amortize the per-program submission
        # floor (tests/test_steps_per_dispatch.py) N-fold. Per-step RNG is
        # fold_in(step_key, global_step) — bit-identical to what the
        # sequential loop derives, so N-step and 1-step training walk
        # the same trajectory. Returns stacked per-step losses (or
        # [n, 2] health vectors in watchdog mode) and stacked outs.
        def multi_step(params, opt_state, state, feeds, step_i,
                       step_key, lr_scale=None):
            guard.note(params, feeds)

            def body(carry, feed):
                params, opt_state, state, i = carry
                rng = jax.random.fold_in(step_key, i)
                params, opt_state, state, loss, outs = step(
                    params, opt_state, state, feed, i, rng,
                    lr_scale=lr_scale,
                )
                return (params, opt_state, state, i + 1), (loss, outs)

            carry = (params, opt_state, state, jnp.int32(step_i))
            (params, opt_state, state, _), (losses, outs) = jax.lax.scan(
                body, carry, feeds
            )
            return params, opt_state, state, losses, outs

        self._multi = jax.jit(
            multi_step, donate_argnums=(0, 1, 2) if donate else ()
        )

    def place(self, params, opt_state, state):
        """Place params/opt-state/state on the mesh per their shardings."""
        if self.mesh is None:
            return params, opt_state, state
        p = {
            k: jax.device_put(v, self._param_sh.get(k, self._rep))
            for k, v in params.items()
        }
        o = {
            k: jax.tree_util.tree_map(
                lambda x: jax.device_put(x, self._param_sh.get(k, self._rep)),
                v,
            )
            for k, v in opt_state.items()
        }
        s = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, self._rep), state
        )
        return p, o, s

    def multi(self, params, opt_state, state, feeds, step_i, step_key,
              lr_scale=None):
        """Run n = leading-dim(feeds) consecutive steps in ONE
        dispatch. `feeds` is the per-step feed pytree stacked on a new
        leading axis (jnp.stack over the batch feeds); `step_key` is
        the TRAINER's step key (per-step rngs are derived inside, so
        the trajectory matches n sequential __call__s exactly).
        Returns (params, opt_state, state, losses, outs) with losses
        [n] (or [n, 2] health vectors in watchdog mode) and outs
        leaves stacked [n, ...]. jax.jit retraces per distinct n —
        use one or two stable chunk sizes."""
        stacked = None  # the steps' axis first, then the batch's
        if self.mesh is not None:
            stacked = NamedSharding(self.mesh, P(None, DATA_AXIS))
        feeds = shard_batch(feeds, self.mesh, stacked)
        if self.watchdog:
            return self._multi(
                params, opt_state, state, feeds, step_i, step_key,
                1.0 if lr_scale is None else float(lr_scale),
            )
        return self._multi(params, opt_state, state, feeds, step_i,
                           step_key)

    def __call__(self, params, opt_state, state, feed, step_i, rng,
                 lr_scale=None):
        """`feed`: placed already by `shard_batch` (the training loop
        does that a step ahead) or not (an external loop's numpy):
        placed here then, inside the caller's `train.dispatch`."""
        feed = shard_batch(feed, self.mesh)
        if self.watchdog:
            # always pass the scale so the traced signature is stable;
            # a changed float re-dispatches, never recompiles
            return self._step(
                params, opt_state, state, feed, step_i, rng,
                1.0 if lr_scale is None else float(lr_scale),
            )
        return self._step(params, opt_state, state, feed, step_i, rng)

    def aot(self, params, opt_state, state, feed, step_i, rng):
        """AOT-compile the step for exactly these args; returns
        (run, hlo_text) where run() executes the compiled step. The
        multi-chip gate asserts the expected collectives (all-reduce
        for dp grads, all-to-all for sp/MoE dispatch,
        collective-permute for ring/pp) are really in hlo_text, so a
        sharding-dropping regression fails loudly instead of silently
        running replicated. AOT compilation does NOT populate the jit
        dispatch cache — run() reuses the compiled executable so the
        step is compiled once."""
        feed = shard_batch(feed, self.mesh)
        args = (params, opt_state, state, feed, step_i, rng)
        if self.watchdog:
            args += (1.0,)
        compiled = self._step.lower(*args).compile()

        def run():
            return compiled(*args)

        return run, compiled.as_text()


def assert_collectives(hlo: str, where: str, *, require=(),
                       forbid=()) -> dict:
    """Parser-backed collective gate (ISSUE 15): parse the compiled
    module's collective INSTRUCTIONS (analysis/hlo_text) and assert
    each `require`d kind appears at least once and each `forbid`den
    kind not at all. Returns {kind: count} so callers can reason
    about the mix.

    This replaces the old substring gate (`"all-reduce" in hlo`): a
    substring matches comments, metadata op_names, and region names —
    e.g. a fused computation NAMED after an inlined-away all-reduce —
    so it can vacuously pass after the real collective is gone. The
    parser only counts instruction lines (async -start/-done pairs
    collapse to one), which is the same object the spmd-audit byte
    budgets are built from."""
    from paddle_tpu.analysis import hlo_text as _hlo

    counts: dict = {}
    for c in _hlo.parse_collectives(hlo.splitlines()):
        counts[c["kind"]] = counts.get(c["kind"], 0) + 1
    for kind in require:
        if not counts.get(kind):
            raise AssertionError(
                f"{where}: expected a {kind!r} instruction in the "
                f"compiled HLO but none parsed (found: {counts}) — "
                f"a sharding was dropped"
            )
    for kind in forbid:
        if counts.get(kind):
            raise AssertionError(
                f"{where}: {counts[kind]} forbidden {kind!r} "
                f"instruction(s) in the compiled HLO — the program "
                f"is repartitioning instead of staying sharded"
            )
    return counts
