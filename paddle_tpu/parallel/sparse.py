"""Sharded embedding tables + row-sparse updates (large-model training).

Capability parity with the reference's sparse distributed training: huge
embedding tables sharded across pservers, trainers prefetching only the
rows a batch touches and pushing row-sparse gradients
(math/SparseRowMatrix.h:29,204,235; trainer/RemoteParameterUpdater.h:265;
ParameterService.proto:40 GET_PARAMETER_SPARSE;
doc/design/cluster_train/large_model_dist_train.md).

TPU-first: the table lives row-sharded over the mesh (`model` axis) in
HBM. Lookup is a shard_map: each shard gathers the rows it owns and a
psum combines partial rows — one ICI allreduce instead of a pserver RPC.
The backward of this program is automatically the row-sparse
scatter-add, and `touched_rows`/`apply_rows` reproduce the
"optimize only touched rows" update rule (ThreadParameterUpdater.h:71
catchUpWith semantics) for the host-side updater parity tests.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu import ops as _ops
from paddle_tpu.core import compile_cache
from paddle_tpu.core.mesh import MODEL_AXIS


def _signature(*arrays) -> tuple:
    """What a compiled program is specific to, where `jax.jit` would
    retrace: every argument's shape and dtype."""
    return tuple((tuple(a.shape), str(a.dtype)) for a in arrays)


def _compile_pinned(jitted, *args):
    """Compile a layout-pinned program for exactly these arguments,
    outside the persistent compilation cache. An executable that a
    LATER process reloads from that cache comes back without the
    pinned parameter layout and rejects the placed tables — seen again
    on JAX 0.9.0 / libtpu 0.0.34 (PR 21, on the chip: "expected
    parameter 0 ... {0,2,1:T(8,128)} but got ... {2,1,0:T(1,128)}") —
    so these few programs are compiled in every process. Returns the
    compiled program: same call, and `.as_text()` is the program that
    runs."""
    with compile_cache.bypassed():
        return jitted.lower(*args).compile()


def embedding_lookup(table, ids, mesh: Mesh, *, axis: str = MODEL_AXIS):
    """Gather rows from a row-sharded table.

    table: [V, D] sharded P(axis, None); ids: int32 [...] replicated.
    Returns [..., D] replicated (shard it over data/batch downstream via
    sharding constraints; XLA folds the transpose)."""
    n = mesh.shape[axis]
    V = table.shape[0]
    assert V % n == 0, f"vocab {V} not divisible by {n} shards"
    rows_local = V // n

    def local(tbl, ids):
        shard = lax.axis_index(axis)
        local_ids = ids - shard * rows_local
        ok = (local_ids >= 0) & (local_ids < rows_local)
        safe = jnp.clip(local_ids, 0, rows_local - 1)
        rows = jnp.take(tbl, safe, axis=0)
        rows = jnp.where(ok[..., None], rows, 0)
        return lax.psum(rows, axis)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis, None), P()),
        out_specs=P(),
        check_vma=False,
    )(table, ids)


def touched_rows(ids, vocab_size: int):
    """Boolean [V] marker of rows referenced by this batch — the analogue
    of the prefetch row-id set (SparsePrefetchRowCpuMatrix)."""
    return (
        jnp.zeros((vocab_size,), jnp.bool_)
        .at[ids.reshape(-1)]
        .set(True)
    )


def apply_rows(update_fn, param, grad, touched):
    """DENSE reference implementation: apply `update_fn(param_rows,
    grad_rows) -> new_rows` to touched rows, leaving the rest
    bit-identical — the sparse_update optimizer contract
    (ParameterOptimizer needSpecialTraversal / catchUpWith). O(V) — the
    parity oracle for `sparse_apply` and `SparseUpdater`."""
    new = update_fn(param, grad)
    return jnp.where(touched[:, None], new, param)


def _unique_segment_grads(flat_ids, grads, k):
    """Unique the touched ids into k sorted slots and segment-sum the
    per-occurrence grads into them. Returns (uids [k] with -1 fills at
    the END, gsum [k, ...]).

    Capacity guard: with k below the batch's true unique count,
    jnp.unique truncates and the inverse aliases dropped ids onto
    surviving slots — their gradients would land on WRONG rows. An
    occurrence only contributes where its slot really holds its id;
    overflowed ids are skipped this step (matching the prefetch-capacity
    semantics of SparsePrefetchRowCpuMatrix rather than corrupting
    neighbors). Shared by sparse_apply and SparseUpdater so the oracle
    and the kernel cannot diverge."""
    n = flat_ids.shape[0]
    uids, inv = jnp.unique(
        flat_ids, size=k, fill_value=-1, return_inverse=True
    )
    inv = inv.reshape(-1)
    hit = (uids[inv] == flat_ids).astype(grads.dtype)
    g = grads.reshape((n,) + grads.shape[1:])
    g = g * hit.reshape((n,) + (1,) * (g.ndim - 1))
    gsum = (
        jnp.zeros((k,) + grads.shape[1:], grads.dtype).at[inv].add(g)
    )
    return uids, gsum


def sparse_apply(update_fn, param, ids, grads, state=(), num_slots=None):
    """Gather-touched -> update -> scatter, as ONE functional XLA
    program — use this form INSIDE a larger jit (a training step whose
    other ops dominate), and as the numpy-checkable oracle for
    `SparseUpdater`. For a STANDALONE large-table update step (the
    pserver-analogue big-embedding path), use `SparseUpdater`: on its
    own, this formulation pays O(V) full-table relayout copies that XLA
    inserts between the gather and the scatter (measured and documented
    in PERF.md), which the SparseUpdater kernel eliminates.

    The reference's large-model update rule (math/SparseRowMatrix.h:204
    SparsePrefetchRowCpuMatrix + trainer/RemoteParameterUpdater.h:265
    SparseRemoteParameterUpdater; design
    doc/design/cluster_train/large_model_dist_train.md): only the rows a
    batch touches are pulled, optimized, and written back.

    param: [V, D]. ids: int [N] (token occurrences, duplicates fine).
    grads: [N, D] per-occurrence gradients (the row-sparse cotangent of
    the lookups). state: tuple of [V, ...] optimizer-state tensors
    sliced/written alongside param (momentum, adagrad accumulators...).
    update_fn(param_rows, grad_rows, *state_rows) ->
    (new_rows, *new_state_rows) — or just new_rows when state is empty.
    num_slots: static unique-row capacity (default N).

    Returns (new_param, new_state) (new_state a tuple like `state`).
    All compute is O(num_slots * D): ids are unique'd (sorted, static
    size), per-occurrence grads segment-summed into their slot, rows
    gathered once, updated, and scattered back as deltas."""
    ids = ids.reshape(-1).astype(jnp.int32)
    k = num_slots or ids.shape[0]
    uids, gsum = _unique_segment_grads(ids, grads, k)
    valid = uids >= 0
    safe = jnp.where(valid, uids, 0)
    prows = param[safe]
    srows = tuple(s[safe] for s in state)
    out = update_fn(prows, gsum, *srows)
    if state:
        new_rows, *new_srows = out
    else:
        new_rows, new_srows = out, []
    # scatter as masked DELTAS: invalid slots all alias row 0, and
    # adding zero there is order-independent (a .set with duplicate
    # indices would not be)
    vmask = valid[:, None].astype(param.dtype)
    new_param = param.at[safe].add((new_rows - prows) * vmask)
    new_state = tuple(
        s.at[safe].add(
            (ns - sr) * valid.reshape((k,) + (1,) * (ns.ndim - 1)).astype(
                s.dtype
            )
        )
        for s, sr, ns in zip(state, srows, new_srows)
    )
    return new_param, new_state


class SparseUpdater:
    """Truly V-independent sparse step: ONE Pallas kernel updates the
    touched rows of the table (and optimizer state) IN PLACE.

    Why a kernel: in plain XLA the table is both gathered (wants
    row-major) and scattered (the compiler picks dim0-minor tiling for
    [V, small-D] tables), so every formulation materializes full-table
    relayout copies — measured in round 2 as `ctr_sparse_step_v_independence`
    = 2.17 (a 4x larger table doubled step time) with the copies
    visible in the HLO. The Mosaic kernel owns the layout end to end:
    tables are born in the kernel's row-major layout (`place`), the
    grid walks the k unique touched rows via scalar-prefetched indices,
    and input_output_aliases make the update genuinely in place.
    Measured: 2.8 ms at 1M rows vs 3.5 ms at 4M rows x 64 (the
    dispatch floor) vs 6.4/13.8 ms for the XLA scatter formulation.

    This is the TPU realization of the reference's in-place sparse-row
    update (math/SparseRowMatrix.h:204 SparsePrefetchRowCpuMatrix;
    trainer/RemoteParameterUpdater.h:265;
    doc/design/cluster_train/large_model_dist_train.md): like the
    pserver-hosted table, the placed table lives outside the regular
    training program and only its touched rows move.

    Layout contract: tables are [V, 1, D] arrays placed by
    `place()` (the singleton axis satisfies Mosaic's (8,128) block
    tiling rule for single-row blocks). `unplace()` returns a plain
    [V, D] numpy view for checkpointing. Any jit that is handed a
    placed table compiles for its layout; compile such a program under
    `core.compile_cache.bypassed()` (see `_compile_pinned`).

    Overflow: when the batch touches FEWER than num_slots unique rows,
    the unused fill slots map to a dedicated SCRATCH row appended by
    `place()` (index V), so they write only scratch — never a real
    row. (Masking the write instead would race: the pipeline
    prefetches each slot's block before earlier slots' write-backs, so
    an "unchanged" write of a real row could clobber a real update.)
    When the batch touches MORE than num_slots unique rows,
    jnp.unique truncation keeps the num_slots SMALLEST ids; the
    dropped ids' gradients are zeroed by the hit-mask in
    _unique_segment_grads before the kernel ever runs — skipped this
    step, never corrupting neighbors (sparse_apply's contract).

    Usage:
        upd = SparseUpdater(momentum_update)
        param = upd.place(table_2d)          # once per table
        mom = upd.place(np.zeros_like(table_2d))
        param, (mom,) = upd(param, ids, grads, (mom,))  # per step;
        # the PREVIOUS buffers are donated (invalidated)
    """

    def __init__(self, update_fn, num_slots=None, interpret=None):
        self.update_fn = update_fn
        self.num_slots = num_slots
        self._interpret = _ops.pallas_interpret(interpret)
        self._steps: dict = {}
        # what the runtime ACTUALLY produced per (shape, dtype) — TPU
        # tilings are dtype-dependent, so one recorded format must not
        # be forced onto tables of another dtype
        self._achieved_fmt: dict = {}
        self._relayouts: dict = {}

    # ---- table placement ----
    def _format(self, shape=None, dtype=None):
        """The table format every layout-pinned program agrees on.
        Until a table of this (shape, dtype) is placed this is the
        REQUESTED row-major layout; afterwards it is whatever the
        runtime ACTUALLY produced for that request (`place` records
        it) — runtimes differ in which layouts/tilings they honor
        (one honored Layout((0,1,2)) exactly, a later one substituted
        a (1,128)-tiled variant and ignored custom device_put layouts
        entirely), and hard-coding the ideal form makes every pinned
        jit reject the real arrays. Called with no
        key (external users sharing ONE table kind) it returns the
        single recorded format when unambiguous."""
        if shape is not None:
            key = (tuple(shape), str(dtype))
            if key in self._achieved_fmt:
                return self._achieved_fmt[key]
        elif len(self._achieved_fmt) == 1:
            return next(iter(self._achieved_fmt.values()))
        from jax.experimental.layout import Format, Layout
        from jax.sharding import SingleDeviceSharding

        return Format(
            Layout((0, 1, 2)), SingleDeviceSharding(jax.devices()[0])
        )

    def place(self, table):
        """[V, D] -> [V, 1, D] device array in the kernel's row-major
        layout (no per-step relayout copies).

        The relayout runs through a jitted identity with the layout as
        its out_shardings rather than a layouted device_put (which one
        runtime ignored; see _format)."""
        t = np.asarray(table)
        v, d = t.shape
        # +1 scratch row: the landing zone for fill/overflow slots
        t = np.concatenate([t, np.zeros((1, d), t.dtype)], axis=0)
        if self._interpret:
            return jnp.asarray(t.reshape(v + 1, 1, d))
        arr = jax.device_put(t.reshape(v + 1, 1, d))
        key = (arr.shape, str(arr.dtype))
        if key not in self._relayouts:
            self._relayouts[key] = _compile_pinned(jax.jit(
                lambda x: x,
                out_shardings=self._format(arr.shape, arr.dtype),
            ), arr)
        arr = self._relayouts[key](arr)
        if key not in self._achieved_fmt:
            # record what the runtime really produced; all pinned jits
            # (_jit_pinned and external in_shardings users) key off it
            self._achieved_fmt[key] = arr.format
        else:
            assert arr.format == self._achieved_fmt[key], (
                f"runtime produced {arr.format} for {key}, previously "
                f"{self._achieved_fmt[key]} — layout contract drifted"
            )
        return arr

    @staticmethod
    def unplace(table):
        t = np.asarray(table)
        return t.reshape(t.shape[0], t.shape[2])[:-1]  # drop scratch

    # ---- the kernel ----
    def _make_call(self, V, D, k, n_state, dtype):
        """The pallas_call updating k touched rows in place (shared by
        the single-step and amortized multi-step builders)."""
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        update_fn = self.update_fn

        def kernel(ids_ref, gsum_ref, *refs):
            table_refs = refs[: 1 + n_state]
            out_refs = refs[1 + n_state :]
            p = table_refs[0][...]
            srows = tuple(r[...] for r in table_refs[1:])
            out = update_fn(p, gsum_ref[...], *srows)
            if n_state:
                new_p, *new_s = out
            else:
                new_p, new_s = out, []
            # every slot's row is distinct (unique ids; fills share only
            # the scratch row, whose content is don't-care), so writes
            # are unconditional — no masking, no pipeline write races
            out_refs[0][...] = new_p
            for o, ns in zip(out_refs[1:], new_s):
                o[...] = ns

        def row_map(i, ids):
            # V here is the scratch row index (tables are [V+1, 1, D])
            return (jnp.minimum(ids[i], V), 0, 0)

        blk = pl.BlockSpec((1, 1, D), row_map)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(k,),
            in_specs=[pl.BlockSpec((1, 1, D), lambda i, ids: (i, 0, 0))]
            + [blk] * (1 + n_state),
            out_specs=[blk] * (1 + n_state),
        )
        shape = jax.ShapeDtypeStruct((V + 1, 1, D), dtype)
        # operand index space includes the scalar-prefetch arg: ids=0,
        # gsum=1, tables start at 2; alias table_j -> output_j
        aliases = {2 + j: j for j in range(1 + n_state)}
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[shape] * (1 + n_state),
            input_output_aliases=aliases,
            interpret=self._interpret,
        )

    def _one_step(self, call, V, k):
        def step_once(param, state, ids, grads):
            flat = ids.reshape(-1).astype(jnp.int32)
            uids, gsum = _unique_segment_grads(
                flat, grads.reshape((flat.shape[0], -1)), k
            )
            oob = jnp.where(uids >= 0, uids, V).astype(jnp.int32)
            outs = call(oob, gsum.reshape(k, 1, -1), param, *state)
            return outs[0], tuple(outs[1:])

        return step_once

    def _jit_pinned(self, fn, n_state, V=None, D=None, dtype=None):
        """Donating jit with the table layouts pinned on BOTH sides:
        without out_shardings the compiler would emit outputs in the
        default (dim0-minor) layout and every subsequent step would pay
        two full-table relayout copies on entry."""
        if self._interpret:
            return jax.jit(fn, donate_argnums=(0, 1))
        fmt = (
            self._format((V + 1, 1, D), dtype)
            if V is not None
            else self._format()
        )
        return jax.jit(
            fn,
            donate_argnums=(0, 1),
            in_shardings=(fmt, (fmt,) * n_state, None, None),
            out_shardings=(fmt, (fmt,) * n_state),
        )

    def _build(self, V, D, k, n_state, dtype):
        call = self._make_call(V, D, k, n_state, dtype)
        return self._jit_pinned(self._one_step(call, V, k), n_state,
                                V=V, D=D, dtype=dtype)

    def _build_multi(self, V, D, k, n_state, dtype, n_steps):
        """n_steps updates inside ONE jitted program (lax.fori_loop over
        the kernel). Amortizes the per-dispatch floor so benchmarks
        measure the row-update work itself, and serves k-step update
        bursts (the catchUpWith batching) with one dispatch."""
        call = self._make_call(V, D, k, n_state, dtype)
        step = self._one_step(call, V, k)

        def steps(param, state, ids_seq, grads_seq):
            def body(i, carry):
                p, s = carry
                ids = jax.lax.dynamic_index_in_dim(
                    ids_seq, i, keepdims=False
                )
                g = jax.lax.dynamic_index_in_dim(
                    grads_seq, i, keepdims=False
                )
                return step(p, s, ids, g)

            return jax.lax.fori_loop(
                0, n_steps, body, (param, tuple(state))
            )

        return self._jit_pinned(steps, n_state, V=V, D=D, dtype=dtype)

    def __call__(self, param, ids, grads, state=()):
        V = param.shape[0] - 1  # last row is scratch
        D = param.shape[2]
        k = self.num_slots or int(np.prod(ids.shape))
        args = (param, tuple(state), ids, grads)
        key = (k, _signature(param, *state, ids, grads))
        if key not in self._steps:
            self._steps[key] = _compile_pinned(
                self._build(V, D, k, len(state), param.dtype), *args
            )
        return self._steps[key](*args)

    def run_steps(self, param, ids_seq, grads_seq, state=()):
        """Apply n_steps sequential updates in one dispatch.
        ids_seq: [n_steps, N]; grads_seq: [n_steps, N, D]."""
        V = param.shape[0] - 1
        D = param.shape[2]
        n_steps = ids_seq.shape[0]
        k = self.num_slots or int(np.prod(ids_seq.shape[1:]))
        args = (param, tuple(state), ids_seq, grads_seq)
        key = ("multi", k, _signature(param, *state, ids_seq, grads_seq))
        if key not in self._steps:
            self._steps[key] = _compile_pinned(
                self._build_multi(V, D, k, len(state), param.dtype,
                                  n_steps), *args
            )
        return self._steps[key](*args)

