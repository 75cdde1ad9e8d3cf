"""Elastic sharded embedding tier for 100M–1B-row CTR tables
(ISSUE 20 tentpole).

`parallel/sparse.py` proves O(touched) sparse updates and
V-independence for tables that FIT: every row is materialized in
device memory. The reference's CTR workloads
(math/SparseRowMatrix.h:29 SparseRowCpuMatrix,
doc/design/cluster_train/large_model_dist_train.md) are an order of
magnitude past that — a 1B x 64 f32 table is 256 GB, and the pserver
tier existed precisely so no single host ever held it. This module is
that tier rebuilt TPU-first, with elasticity as the design
constraint:

- **Explicit placement.** Every logical row id has exactly one owner
  shard — `range` (id // rows_per_shard: the pserver block layout,
  ParameterService.proto GET_PARAMETER_SPARSE) or `hash` (splitmix64
  mix, the skew-resistant layout for power-law CTR vocabularies).
  Ownership is arithmetic, not a directory: any process can compute
  where any row lives, which is what makes per-shard recovery
  manifests possible (a respawned rank knows exactly which shard
  files are its rows).

- **Hot-cache residency, not materialization.** Each shard owns a
  fixed-capacity device buffer of `capacity` rows (plus parallel
  per-shard optimizer-slot buffers). A host-side LRU map binds
  resident row ids to slots; rows the traffic stops touching are
  EVICTED — written back to the shard's host spill store — and rows
  touched again are rebuilt from spill (or from the deterministic
  init for never-touched rows), never silently zero. The device
  programs see only slot indices in [0, capacity): their shapes,
  layouts, and compiled code depend on (capacity, dim, batch) and
  NEVER on `rows_total` — V-independence by construction, at any V.

- **All-gather-free by construction, policed by audit.** Lookup is
  the `sparse.embedding_lookup` shard_map (local gather + one psum);
  update and residency fill are local masked scatters with NO
  collective at all. The committed `mc_sparse_shard_step` capture is
  audited by `analysis/spmd_audit.py` under a policy that FORBIDS
  all-gather — a future "optimization" that gathers the hot cache
  onto every chip fails CI, it does not ship.

Checkpointing: `export_shards()` returns one payload dict per shard
(resident rows in LRU order + spill store + optimizer slots), the
unit `trainer/async_checkpoint.py`'s sharded-table generations
(`sharded-table-v1`) commit with per-shard sha256 manifests. See
`trainer/online.py` for the commit-acknowledged training ledger that
turns those generations into the zero-batches-lost elastic contract.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass
from math import ceil

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.core.mesh import MODEL_AXIS, get_mesh
from paddle_tpu.parallel.sparse import embedding_lookup

_MASK64 = np.uint64(0xFFFFFFFFFFFFFFFF)


def _mix64(x):
    """splitmix64 finalizer, vectorized over uint64 numpy arrays —
    the hash behind `hash` placement and the deterministic row init.
    Stdlib-deterministic: the same id hashes the same on every
    process, every run, every platform."""
    x = np.asarray(x, np.uint64)
    with np.errstate(over="ignore"):
        x = (x + np.uint64(0x9E3779B97F4A7C15)) & _MASK64
        x = ((x ^ (x >> np.uint64(30)))
             * np.uint64(0xBF58476D1CE4E5B9)) & _MASK64
        x = ((x ^ (x >> np.uint64(27)))
             * np.uint64(0x94D049BB133111EB)) & _MASK64
        return x ^ (x >> np.uint64(31))


# Memoized by hyperparameters: two calls with the same lr return the
# SAME function object, so two tables configured alike share every
# compiled program (the V-independence cache test leans on this).
_UPDATE_FNS: dict = {}


def sgd_row_update(lr: float = 0.1):
    """Plain row SGD `update_fn` (no optimizer slots)."""
    key = ("sgd", float(lr))
    if key not in _UPDATE_FNS:
        def update(rows, grads):
            return rows - lr * grads

        _UPDATE_FNS[key] = update
    return _UPDATE_FNS[key]


def adagrad_row_update(lr: float = 0.1, eps: float = 1e-6):
    """Adagrad with one per-row accumulator slot buffer — the
    catchUpWith-style sparse optimizer state the shard checkpoints
    must carry (evict-then-touch would silently reset a row's
    effective learning rate if the accumulator were dropped)."""
    key = ("adagrad", float(lr), float(eps))
    if key not in _UPDATE_FNS:
        def update(rows, grads, acc):
            acc = acc + grads * grads
            return rows - lr * grads / jnp.sqrt(acc + eps), acc

        _UPDATE_FNS[key] = update
    return _UPDATE_FNS[key]


@dataclass(frozen=True)
class ShardedTableConfig:
    """Static shape/placement contract for one sharded table.

    rows_total: LOGICAL vocabulary (100M–1B). Costs nothing: only
        host-side owner arithmetic ever sees it.
    dim: row width D.
    capacity: HOT rows per shard (device-resident). Total device
        footprint = num_shards * capacity * dim * 4 bytes.
    num_slots: static unique-touched-rows capacity per update step
        (the `sparse_apply` k). Must be <= capacity so one batch can
        always be made fully resident.
    placement: "range" | "hash".
    init_scale: deterministic per-(row, col) init amplitude; 0.0 =
        zero init. Never-touched rows ARE this init — there is no
        materialized cold table to read them from.
    seed: folded into the init hash stream.
    """

    rows_total: int
    dim: int
    capacity: int
    num_slots: int
    placement: str = "range"
    init_scale: float = 0.0
    seed: int = 0

    def __post_init__(self):
        if self.placement not in ("range", "hash"):
            raise ValueError(f"placement {self.placement!r}")
        if self.num_slots > self.capacity:
            raise ValueError(
                f"num_slots {self.num_slots} > capacity "
                f"{self.capacity}: a single batch could not be made "
                f"resident"
            )


# ---- compiled-program cache -----------------------------------------
#
# Keyed on (mesh, axis, hot-cache shape, batch shape, update_fn) —
# NEVER on rows_total. Two tables differing only in logical vocab hit
# the SAME entries: the V-independence invariant is testable as cache
# identity, not just as a wall-clock smoke.
_PROGRAMS: dict = {}


def program_cache_size() -> int:
    return len(_PROGRAMS)


def _lookup_program(mesh, axis, S, D, N, dtype):
    key = ("lookup", mesh, axis, S, D, N, str(dtype))
    if key not in _PROGRAMS:
        def fn(cache, slots):
            return embedding_lookup(cache, slots, mesh, axis=axis)

        _PROGRAMS[key] = jax.jit(fn)
    return _PROGRAMS[key]


def _pull_program(mesh, axis, S, D, M, n_state, dtype):
    """Gather M rows (by global slot) from cache AND every optimizer
    slot buffer — the eviction write-back read. -1 slots return 0 and
    are ignored by the host."""
    key = ("pull", mesh, axis, S, D, M, n_state, str(dtype))
    if key not in _PROGRAMS:
        def fn(cache, state, slots):
            rows = embedding_lookup(cache, slots, mesh, axis=axis)
            srows = tuple(
                embedding_lookup(st, slots, mesh, axis=axis)
                for st in state
            )
            return rows, srows

        _PROGRAMS[key] = jax.jit(fn)
    return _PROGRAMS[key]


def _push_program(mesh, axis, S, D, M, n_state, dtype):
    """Write M rows (by global slot) into cache + slot buffers — the
    residency fill. Pure local masked scatter: each shard writes only
    its own slot range, NO collective touches the table."""
    key = ("push", mesh, axis, S, D, M, n_state, str(dtype))
    if key not in _PROGRAMS:
        n = mesh.shape[axis]
        C = S // n

        def local(cache, state, slots, rows, srows):
            shard = lax.axis_index(axis)
            loc = slots - shard * C
            ok = (loc >= 0) & (loc < C)
            safe = jnp.clip(loc, 0, C - 1)
            m = ok[:, None].astype(cache.dtype)
            new_cache = cache.at[safe].add((rows - cache[safe]) * m)
            new_state = tuple(
                st.at[safe].add((sr - st[safe]) * m)
                for st, sr in zip(state, srows)
            )
            return new_cache, new_state

        sharded = P(axis, None)
        fn = jax.shard_map(
            local, mesh=mesh,
            in_specs=(sharded, (sharded,) * n_state, P(), P(),
                      (P(),) * n_state),
            out_specs=(sharded, (sharded,) * n_state),
            check_vma=False,
        )
        _PROGRAMS[key] = jax.jit(fn, donate_argnums=(0, 1))
    return _PROGRAMS[key]


def _update_program(mesh, axis, S, D, N, k, n_state, dtype,
                    update_fn):
    """The sparse train step: segment-sum per-occurrence grads into k
    unique slots, gather those rows + optimizer slots, apply
    update_fn, scatter back as masked deltas. Each shard touches only
    its own slot range — like push, NO collective."""
    key = ("update", mesh, axis, S, D, N, k, n_state, str(dtype),
           update_fn)
    if key not in _PROGRAMS:
        n = mesh.shape[axis]
        C = S // n

        def local(cache, state, uslots, inv, grads):
            gsum = jnp.zeros((k, D), grads.dtype).at[inv].add(grads)
            shard = lax.axis_index(axis)
            loc = uslots - shard * C
            ok = (loc >= 0) & (loc < C)
            safe = jnp.clip(loc, 0, C - 1)
            prows = cache[safe]
            srows = tuple(st[safe] for st in state)
            out = update_fn(prows, gsum, *srows)
            if n_state:
                new_rows, *new_srows = out
            else:
                new_rows, new_srows = out, []
            m = ok[:, None].astype(cache.dtype)
            new_cache = cache.at[safe].add((new_rows - prows) * m)
            new_state = tuple(
                st.at[safe].add((ns - sr) * m)
                for st, sr, ns in zip(state, srows, new_srows)
            )
            return new_cache, new_state

        sharded = P(axis, None)
        fn = jax.shard_map(
            local, mesh=mesh,
            in_specs=(sharded, (sharded,) * n_state, P(), P(), P()),
            out_specs=(sharded, (sharded,) * n_state),
            check_vma=False,
        )
        _PROGRAMS[key] = jax.jit(fn, donate_argnums=(0, 1))
    return _PROGRAMS[key]


def step_program(mesh, axis, S, D, N, k, n_state, dtype, update_fn):
    """Lookup + sparse update as ONE traced program — the
    `mc_sparse_shard_step` capture target (tools/profile_multichip).
    Shapes are (hot-cache, batch) only: lowering this at rows_total =
    2**30 produces byte-identical HLO to rows_total = 2**20, which is
    the audit-visible form of the V-independence claim."""
    n = mesh.shape[axis]
    C = S // n

    def local(cache, state, slots, uslots, inv, grads):
        shard = lax.axis_index(axis)
        # lookup: local gather + psum (the only collective)
        loc_l = slots - shard * C
        ok_l = (loc_l >= 0) & (loc_l < C)
        rows = jnp.take(cache, jnp.clip(loc_l, 0, C - 1), axis=0)
        out = lax.psum(jnp.where(ok_l[:, None], rows, 0), axis)
        # update: local masked delta scatter, no collective
        gsum = jnp.zeros((k, D), grads.dtype).at[inv].add(grads)
        loc = uslots - shard * C
        ok = (loc >= 0) & (loc < C)
        safe = jnp.clip(loc, 0, C - 1)
        prows = cache[safe]
        srows = tuple(st[safe] for st in state)
        upd = update_fn(prows, gsum, *srows)
        if n_state:
            new_rows, *new_srows = upd
        else:
            new_rows, new_srows = upd, []
        m = ok[:, None].astype(cache.dtype)
        new_cache = cache.at[safe].add((new_rows - prows) * m)
        new_state = tuple(
            st.at[safe].add((ns - sr) * m)
            for st, sr, ns in zip(state, srows, new_srows)
        )
        return out, new_cache, new_state

    sharded = P(axis, None)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(sharded, (sharded,) * n_state, P(), P(), P(), P()),
        out_specs=(P(), sharded, (sharded,) * n_state),
        check_vma=False,
    )
    return jax.jit(fn, donate_argnums=(0, 1))


class ShardedEmbeddingTable:
    """A logically huge embedding table as explicit per-shard hot
    caches over the mesh `axis`. See the module docstring for the
    design; the API is host-driven:

        cfg = ShardedTableConfig(rows_total=1 << 30, dim=16,
                                 capacity=4096, num_slots=256)
        table = ShardedEmbeddingTable(cfg, mesh, update_fn=sgd_row_update(0.1))
        emb = table.lookup(ids)          # [..., D] — ids anywhere in [0, 1<<30)
        table.update(ids, grads)         # per-occurrence grads [N, D]
        payloads = table.export_shards() # one dict per shard, for
                                         # async_checkpoint table generations
        table.restore_shards(payloads)   # elastic resume

    Thread contract: single-writer (the training loop). Checkpoint
    snapshots copy on export, so the async writer never races device
    donation.
    """

    def __init__(self, config: ShardedTableConfig, mesh=None,
                 axis: str = MODEL_AXIS, update_fn=None,
                 num_state: int = 0):
        self.config = config
        self.mesh = mesh if mesh is not None else get_mesh()
        if axis not in self.mesh.shape:
            raise ValueError(f"mesh has no axis {axis!r}")
        self.axis = axis
        self.num_shards = int(self.mesh.shape[axis])
        self.update_fn = (update_fn if update_fn is not None
                          else sgd_row_update(0.1))
        self.num_state = int(num_state)
        self.rows_per_shard = ceil(config.rows_total / self.num_shards)
        C, D = config.capacity, config.dim
        self._S = self.num_shards * C  # total hot slots
        self._sharding = NamedSharding(self.mesh, P(axis, None))
        zeros = jnp.zeros((self._S, D), jnp.float32)
        self._cache = jax.device_put(zeros, self._sharding)
        self._state = tuple(
            jax.device_put(jnp.zeros((self._S, D), jnp.float32),
                           self._sharding)
            for _ in range(self.num_state)
        )
        # host residency maps, per shard: id -> local slot, LRU order
        # (oldest first); free slots; spill store id -> (row, *slots)
        self._slot_of = [OrderedDict() for _ in range(self.num_shards)]
        self._free = [list(range(C - 1, -1, -1))
                      for _ in range(self.num_shards)]
        self._spill = [dict() for _ in range(self.num_shards)]
        self.stats = {"hits": 0, "misses": 0, "evictions": 0,
                      "steps": 0}

    # ---- placement ----
    def owners(self, ids) -> np.ndarray:
        ids = np.asarray(ids, np.int64)
        if self.config.placement == "range":
            return ids // self.rows_per_shard
        return (_mix64(ids.astype(np.uint64))
                % np.uint64(self.num_shards)).astype(np.int64)

    # ---- deterministic init ----
    def _init_rows(self, ids) -> np.ndarray:
        D = self.config.dim
        ids = np.asarray(ids, np.int64)
        if not self.config.init_scale:
            return np.zeros((len(ids), D), np.float32)
        base = (ids.astype(np.uint64)[:, None] * np.uint64(D)
                + np.arange(D, dtype=np.uint64)[None, :]
                + np.uint64(self.config.seed) * np.uint64(0x9E37))
        u = (_mix64(base) >> np.uint64(11)).astype(np.float64) * 2.0**-53
        return ((u * 2.0 - 1.0)
                * self.config.init_scale).astype(np.float32)

    # ---- residency ----
    def _global_slot(self, shard: int, local: int) -> int:
        return shard * self.config.capacity + local

    def ensure_resident(self, uids: np.ndarray) -> None:
        """Make every id in `uids` (unique, any order) resident,
        faulting misses in from spill/init and LRU-evicting to make
        room. Evicted rows are written back (row + optimizer slots)
        to the owner shard's spill store — an evicted row touched
        again is REBUILT, never reset."""
        uids = np.asarray(uids, np.int64)
        if len(uids) and (int(uids.min()) < 0
                          or int(uids.max()) >= self.config.rows_total):
            raise ValueError(
                f"ids must lie in [0, {self.config.rows_total}); got "
                f"range [{int(uids.min())}, {int(uids.max())}]"
            )
        shards = self.owners(uids)
        misses = []  # (shard, id)
        for i, s in zip(uids.tolist(), shards.tolist()):
            d = self._slot_of[s]
            if i in d:
                d.move_to_end(i)
                self.stats["hits"] += 1
            else:
                misses.append((s, i))
                self.stats["misses"] += 1
        if not misses:
            return
        if len(misses) > self.config.num_slots:
            raise ValueError(
                f"{len(misses)} misses in one batch > num_slots "
                f"{self.config.num_slots}"
            )
        evict = []   # (shard, id, local slot)
        assign = []  # (shard, id, local slot)
        for s, i in misses:
            if self._free[s]:
                slot = self._free[s].pop()
            else:
                old_id, slot = self._slot_of[s].popitem(last=False)
                evict.append((s, old_id, slot))
                self.stats["evictions"] += 1
            assign.append((s, i, slot))
            self._slot_of[s][i] = slot  # newest; never a victim below
        if evict:
            self._write_back(evict)
        # values for the faulted-in rows: spill wins, else init
        vals = np.empty((len(assign), self.config.dim), np.float32)
        svals = [np.zeros_like(vals) for _ in range(self.num_state)]
        init_ix, init_ids = [], []
        for j, (s, i, _slot) in enumerate(assign):
            spilled = self._spill[s].pop(i, None)
            if spilled is not None:
                vals[j] = spilled[0]
                for t in range(self.num_state):
                    svals[t][j] = spilled[1 + t]
            else:
                init_ix.append(j)
                init_ids.append(i)
        if init_ix:
            vals[init_ix] = self._init_rows(init_ids)
        gslots = np.array(
            [self._global_slot(s, slot) for s, _i, slot in assign],
            np.int32,
        )
        self._push(gslots, vals, svals)

    def _write_back(self, evict) -> None:
        gslots = np.full((self.config.num_slots,), -1, np.int32)
        for j, (s, _i, slot) in enumerate(evict):
            gslots[j] = self._global_slot(s, slot)
        pull = _pull_program(
            self.mesh, self.axis, self._S, self.config.dim,
            len(gslots), self.num_state, "float32",
        )
        rows, srows = pull(self._cache, self._state, gslots)
        rows = np.asarray(rows)
        srows = [np.asarray(sr) for sr in srows]
        for j, (s, i, _slot) in enumerate(evict):
            self._spill[s][i] = (
                rows[j].copy(),
                *(sr[j].copy() for sr in srows),
            )

    def _push(self, gslots, vals, svals) -> None:
        M = self.config.num_slots
        pad = M - len(gslots)
        if pad:
            gslots = np.concatenate(
                [gslots, np.full((pad,), -1, np.int32)]
            )
            vals = np.concatenate(
                [vals, np.zeros((pad, self.config.dim), np.float32)]
            )
            svals = [
                np.concatenate(
                    [sv, np.zeros((pad, self.config.dim), np.float32)]
                )
                for sv in svals
            ]
        push = _push_program(
            self.mesh, self.axis, self._S, self.config.dim, M,
            self.num_state, "float32",
        )
        self._cache, self._state = push(
            self._cache, self._state, gslots, vals, tuple(svals)
        )

    # ---- the data path ----
    def _slots_for(self, flat_ids: np.ndarray) -> np.ndarray:
        shards = self.owners(flat_ids)
        out = np.empty(len(flat_ids), np.int32)
        for j, (i, s) in enumerate(
            zip(flat_ids.tolist(), shards.tolist())
        ):
            out[j] = self._global_slot(s, self._slot_of[s][i])
        return out

    def lookup(self, ids):
        """ids: int array, any shape, values in [0, rows_total).
        Returns [*ids.shape, D] (replicated)."""
        ids = np.asarray(ids, np.int64)
        flat = ids.reshape(-1)
        self.ensure_resident(np.unique(flat))
        slots = self._slots_for(flat)
        look = _lookup_program(
            self.mesh, self.axis, self._S, self.config.dim,
            len(slots), "float32",
        )
        out = look(self._cache, slots)
        return out.reshape(ids.shape + (self.config.dim,))

    def update(self, ids, grads):
        """One sparse optimizer step: per-occurrence grads [N, D] are
        segment-summed per touched row and applied via `update_fn`.
        More than `num_slots` unique rows in one batch raises (the
        capacity contract is explicit here, unlike sparse_apply's
        skip-silently prefetch semantics — a sharded trainer must
        never silently drop gradient)."""
        ids = np.asarray(ids, np.int64).reshape(-1)
        grads = np.asarray(grads, np.float32).reshape(
            len(ids), self.config.dim
        )
        uids, inv = np.unique(ids, return_inverse=True)
        k = self.config.num_slots
        if len(uids) > k:
            raise ValueError(
                f"{len(uids)} unique ids in one step > num_slots {k}"
            )
        self.ensure_resident(uids)
        uslots = np.full((k,), -1, np.int32)
        uslots[: len(uids)] = self._slots_for(uids)
        upd = _update_program(
            self.mesh, self.axis, self._S, self.config.dim,
            len(ids), k, self.num_state, "float32", self.update_fn,
        )
        self._cache, self._state = upd(
            self._cache, self._state, uslots,
            inv.astype(np.int32), grads,
        )
        self.stats["steps"] += 1

    # ---- introspection ----
    @property
    def rows_materialized(self) -> int:
        """Distinct rows this table has ever touched (resident +
        spilled): over `rows_total`, the share of the logical
        table that was ever touched."""
        return sum(len(d) for d in self._slot_of) + sum(
            len(sp) for sp in self._spill
        )

    def resident_ids(self, shard: int) -> list:
        return list(self._slot_of[shard])

    # ---- checkpointing ----
    def export_shards(self) -> list:
        """One payload dict per shard, each self-contained: resident
        ids in LRU order (oldest first) with their slots + rows +
        optimizer slots, and the spill store. Bytes are COPIED — the
        async writer serializes while training donates these very
        buffers (the snapshot_shards lesson)."""
        C, D = self.config.capacity, self.config.dim
        cache = np.array(self._cache, copy=True)
        state = [np.array(st, copy=True) for st in self._state]
        out = []
        for s in range(self.num_shards):
            d = self._slot_of[s]
            rids = np.fromiter(d.keys(), np.int64, len(d))
            slots = np.fromiter(d.values(), np.int32, len(d))
            g = s * C + slots
            payload = {
                "ids": rids,
                "slots": slots,
                "rows": cache[g] if len(d) else
                np.zeros((0, D), np.float32),
            }
            for t, st in enumerate(state):
                payload[f"state{t}"] = (
                    st[g] if len(d) else np.zeros((0, D), np.float32)
                )
            sp = self._spill[s]
            sids = np.fromiter(sp.keys(), np.int64, len(sp))
            payload["spill_ids"] = sids
            payload["spill_rows"] = (
                np.stack([sp[i][0] for i in sids.tolist()])
                if len(sp) else np.zeros((0, D), np.float32)
            )
            for t in range(self.num_state):
                payload[f"spill_state{t}"] = (
                    np.stack([sp[i][1 + t] for i in sids.tolist()])
                    if len(sp) else np.zeros((0, D), np.float32)
                )
            out.append(payload)
        return out

    def table_meta(self) -> dict:
        """Config echo for the generation manifest — restore verifies
        shape agreement instead of quietly mis-assembling."""
        return {
            "rows_total": self.config.rows_total,
            "dim": self.config.dim,
            "capacity": self.config.capacity,
            "num_shards": self.num_shards,
            "num_state": self.num_state,
            "placement": self.config.placement,
        }

    def restore_shards(self, payloads) -> None:
        """Rebuild residency + device buffers from `export_shards`
        payloads (the elastic resume path). LRU order, slot
        assignment, optimizer slots, and the spill store all come
        back exactly, so a resumed trainer evicts the same rows the
        dead one would have."""
        if len(payloads) != self.num_shards:
            raise ValueError(
                f"{len(payloads)} shard payloads for "
                f"{self.num_shards} shards"
            )
        C, D = self.config.capacity, self.config.dim
        cache = np.zeros((self._S, D), np.float32)
        state = [np.zeros((self._S, D), np.float32)
                 for _ in range(self.num_state)]
        self._slot_of = [OrderedDict() for _ in range(self.num_shards)]
        self._free = [list(range(C - 1, -1, -1))
                      for _ in range(self.num_shards)]
        self._spill = [dict() for _ in range(self.num_shards)]
        for s, p in enumerate(payloads):
            rids = np.asarray(p["ids"], np.int64)
            slots = np.asarray(p["slots"], np.int32)
            rows = np.asarray(p["rows"], np.float32)
            used = set()
            for j, (i, slot) in enumerate(
                zip(rids.tolist(), slots.tolist())
            ):
                self._slot_of[s][i] = slot
                used.add(slot)
                cache[s * C + slot] = rows[j]
                for t in range(self.num_state):
                    state[t][s * C + slot] = np.asarray(
                        p[f"state{t}"], np.float32
                    )[j]
            self._free[s] = [sl for sl in range(C - 1, -1, -1)
                             if sl not in used]
            sids = np.asarray(p["spill_ids"], np.int64)
            srows = np.asarray(p["spill_rows"], np.float32)
            sstate = [
                np.asarray(p[f"spill_state{t}"], np.float32)
                for t in range(self.num_state)
            ]
            for j, i in enumerate(sids.tolist()):
                self._spill[s][i] = (
                    srows[j].copy(),
                    *(ss[j].copy() for ss in sstate),
                )
        self._cache = jax.device_put(
            jnp.asarray(cache), self._sharding
        )
        self._state = tuple(
            jax.device_put(jnp.asarray(st), self._sharding)
            for st in state
        )
