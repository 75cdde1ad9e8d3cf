"""Pipeline parallelism: GPipe-style microbatch pipelining over a mesh
axis.

Beyond-reference capability (SURVEY.md §2 parallelism table: absent in
2017). TPU-first design: the classic SPMD pipeline — every device holds
ONE stage's parameters (stacked stage-major and sharded over the
"pipe" axis), microbatches stream through a `lax.scan` of pipeline
ticks, and activations hop stage-to-stage with `lax.ppermute` over ICI.
Because ppermute/scan are differentiable, `jax.grad` through
`pipeline_apply` IS pipelined backprop (activations rematerialized per
tick by XLA; add jax.checkpoint on stage_fn for long pipelines) — no
hand-built 1F1B schedule.

All stages must share one activation signature (same shape in/out), the
standard homogeneous-stage formulation (e.g. a stack of identical
transformer/FC blocks split across devices).
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P



def _pipeline_local(stage_fn, axis_name, params, xs):
    """Runs under shard_map: `params` is THIS device's stage slice (no
    stage axis), `xs` [M, ...] the full microbatch stream (replicated).
    Returns [M, ...] outputs, valid on the LAST stage (zeros elsewhere,
    all-gathered by the caller)."""
    idx = lax.axis_index(axis_name)
    S = lax.axis_size(axis_name)  # static: sets the tick count
    M = xs.shape[0]
    T = M + S - 1  # total ticks to drain the pipe

    perm = [(i, (i + 1) % S) for i in range(S)]

    def tick(carry, t):
        acts, outputs = carry
        # stage 0 ingests microbatch t; other stages process what the
        # previous tick handed them
        inp = jnp.where(idx == 0, xs[jnp.clip(t, 0, M - 1)], acts)
        y = stage_fn(params, inp)
        # hand to the next stage over ICI
        passed = lax.ppermute(y, axis_name, perm)
        # last stage emits microbatch t-(S-1) at this tick
        out_t = t - (S - 1)
        emit = (idx == S - 1) & (out_t >= 0)
        outputs = jnp.where(
            emit,
            outputs.at[jnp.clip(out_t, 0, M - 1)].set(y),
            outputs,
        )
        return (passed, outputs), None

    acts0 = jnp.zeros_like(stage_fn(params, xs[0]))
    outs0 = jnp.zeros((M,) + acts0.shape, acts0.dtype)
    (acts, outputs), _ = lax.scan(
        tick, (acts0, outs0), jnp.arange(T)
    )
    # only the last stage ever writes outputs (zeros elsewhere), so a
    # psum over the pipe axis replicates its values to every member
    return lax.psum(outputs, axis_name)


def pipeline_apply(
    mesh: Mesh,
    axis_name: str,
    stage_fn: Callable,
    stacked_params,
    xs: jax.Array,
    batch_axis: str | None = None,
):
    """Run the pipeline.

    stacked_params: pytree whose leaves have a leading stage axis of
    size mesh.shape[axis_name], sharded over `axis_name` (see
    `shard_stacked_params`). xs: [M, micro_batch, ...] microbatches.
    Returns [M, micro_batch, ...] outputs. Differentiable end-to-end.

    batch_axis: name of a mesh data axis to shard the micro_batch dim
    over — pp×dp in one program (each data shard streams its slice of
    every microbatch through the same pipe; stage params are replicated
    across `batch_axis`, so their gradient allreduce over data is
    inserted by shard_map's transpose automatically).
    """
    xspec = P(None, batch_axis) if batch_axis else P()
    in_specs = (
        jax.tree_util.tree_map(lambda _: P(axis_name), stacked_params),
        xspec,
    )

    def local(params, xs):
        # shard_map hands us the [1, ...]-sliced stage params
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        return _pipeline_local(stage_fn, axis_name, params, xs)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=xspec,
        check_vma=False,
    )(stacked_params, xs)


def shard_stacked_params(mesh: Mesh, axis_name: str, stacked_params):
    """Place each stage's slice on its pipe device."""
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(
            a, NamedSharding(mesh, P(axis_name))
        ),
        stacked_params,
    )


def microbatch(x: jax.Array, n_micro: int) -> jax.Array:
    """[B, ...] -> [M, B/M, ...]."""
    assert x.shape[0] % n_micro == 0, (
        f"batch {x.shape[0]} not divisible into {n_micro} microbatches"
    )
    return x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])


def unmicrobatch(x: jax.Array) -> jax.Array:
    return x.reshape((-1,) + x.shape[2:])
