"""DataFeeder: python samples -> Arg batches (ragged -> dense packing).

Reference: python/paddle/v2/data_feeder.py + the input-type declarations of
PyDataProvider2.py:47-214 (dense_vector, integer_value, sparse_*, each ×
{no_sequence, sequence, sub_sequence}). The reference emits padding-free
flat buffers + start positions; we emit dense [B, T_bucket] + lengths
(see core/arg.py for why). Bucketing rounds T up to a power-of-two-ish
bucket so XLA recompiles only per bucket, not per batch.
"""

from __future__ import annotations

import weakref
from dataclasses import dataclass
from typing import Optional

import numpy as np

from paddle_tpu.core.arg import Arg
from paddle_tpu.obs import metrics as _obs


@dataclass(frozen=True)
class InputType:
    kind: str  # dense | ids | sparse_binary | sparse_float
    shape: tuple  # feature shape
    seq: int  # 0 = none, 1 = sequence, 2 = sub-sequence
    vocab: int = 0  # ids slots: the value range (v1 slot "dim")

    @property
    def size(self) -> int:
        """Layer width this slot feeds (reference InputType.dim: vocab
        for integer slots, feature dim otherwise)."""
        if self.kind == "ids":
            return self.vocab
        n = 1
        for d in self.shape:
            n *= d
        return n

    # --- the reference InputType attribute surface
    #     (PyDataProvider2.py:47 InputType(dim, seq_type, type)) ---
    @property
    def dim(self) -> int:
        return self.size

    @property
    def seq_type(self) -> int:
        return self.seq

    @property
    def type(self) -> int:
        """DataType enum value (PyDataProvider2.py:32)."""
        return {
            "dense": 0,  # DataType.Dense
            "sparse_binary": 1,  # DataType.SparseNonValue
            "sparse_float": 2,  # DataType.SparseValue
            "ids": 3,  # DataType.Index
        }[self.kind]


def dense_vector(dim, seq_type=0):
    dim = tuple(dim) if isinstance(dim, (tuple, list)) else (dim,)
    return InputType("dense", dim, seq_type)


def integer_value(vocab, seq_type=0):
    return InputType("ids", (1,), seq_type, vocab=vocab)


def sparse_binary_vector(dim, seq_type=0):
    return InputType("sparse_binary", (dim,), seq_type)


def sparse_float_vector(dim, seq_type=0):
    return InputType("sparse_float", (dim,), seq_type)


# sequence variants, mirroring PyDataProvider2 naming
def dense_vector_sequence(dim):
    return dense_vector(dim, 1)


def integer_value_sequence(vocab):
    return integer_value(vocab, 1)


def integer_value_sub_sequence(vocab):
    return integer_value(vocab, 2)


def _bucket(n: int, buckets=None) -> int:
    """Round up to a bucket to bound recompilation."""
    if buckets:
        for b in buckets:
            if n <= b:
                return b
        raise ValueError(
            f"sequence of length {n} exceeds the largest bucket "
            f"{buckets[-1]}; add a larger bucket or truncate upstream"
        )
    b = 8
    while b < n:
        b *= 2 if b < 128 else 1
        if b >= 128:
            b = ((n + 127) // 128) * 128
            break
    return b


def _sparse_float_row(row):
    """Normalize a sparse-float row to (indices, values). Accepts the
    reference sample format — a sequence of (col, value) pairs
    (PyDataProvider2.py sparse_float slots; DataProviderConverter's
    SparseFloatScanner) — or the internal two-tuple of parallel LISTS
    (proto_provider). A tuple of exactly two pairs is ambiguous by
    shape; the parallel form is only recognized when both halves are
    lists/arrays, so reference pair data can never be misread as
    (indices, values)."""
    if (
        isinstance(row, tuple)
        and len(row) == 2
        and all(isinstance(e, (list, np.ndarray)) for e in row)
    ):
        return row  # internal parallel (indices, values)
    if len(row) == 0:
        return (), ()
    if all(hasattr(e, "__len__") and len(e) == 2 for e in row):
        return tuple(zip(*row))  # reference (col, value) pairs
    if isinstance(row, tuple) and len(row) == 2:
        return row  # parallel form with tuple storage
    return tuple(zip(*row))


class _Kept:
    """A slot's batch memory of one shape and dtype: `free` holds the
    blocks that nothing outside the feeder refers to any more."""

    def __init__(self, shape, dtype):
        self.shape, self.dtype = shape, dtype
        self.free = []


class DataFeeder:
    """feeding maps data-layer name -> position in each sample tuple.

    Whose memory a fed batch is: the caller's, for as long as anything
    refers to it. A dense slot whose rows are arrays of one shape is
    stacked into a block of memory that the feeder takes back once the
    batch array, every view of it (a reshape, a slice, a consumer's
    own view) and every reference the runtime holds while it reads the
    argument have gone, and a later batch of that slot and shape then
    lands there: mapping 154 MB of fresh pages for every batch of 256
    images cost ten times the copy. A batch that is kept stays what it
    was; a block that is never let go costs the feeder one more block,
    never a wrong batch. What may not be kept across the batch's death
    is its raw address. `feeder.buffers_reused` / `feeder
    .buffers_fresh` count the blocks taken again and newly made: where
    `fresh` keeps rising, something holds every batch."""

    def __init__(self, feeding: dict, types: dict, buckets=None):
        self.feeding = feeding
        self.types = types
        self.buckets = buckets
        self._kept = {}  # slot name -> _Kept

    def __call__(self, batch: list) -> dict:
        return self.convert(batch)

    def convert(self, batch: list) -> dict:
        out = {}
        for name, pos in self.feeding.items():
            t = self.types[name]
            column = [sample[pos] for sample in batch]
            out[name] = self._column_to_arg(column, t, name)
        return out

    def _batch_array(self, slot, shape, dtype) -> np.ndarray:
        """An uninitialised array for one batch of `slot`, in memory
        that nothing else refers to. The array handed out is a view of
        an owner that wraps the block through a memoryview, so every
        view of it, however derived, has that owner as its base; the
        owner's death is the last reference's, and only then does the
        block come back to `free`. A batch of another shape or dtype
        starts the slot anew: what it had kept goes with the last
        batch that is still alive in it."""
        kept = self._kept.get(slot)
        if kept is None or (kept.shape, kept.dtype) != (shape, dtype):
            kept = self._kept[slot] = _Kept(shape, dtype)
        reg = _obs.get_registry()
        try:
            block = kept.free.pop()
            reg.counter("feeder.buffers_reused").inc()
        except IndexError:
            block = np.empty(shape, dtype)
            reg.counter("feeder.buffers_fresh").inc()
        owner = np.frombuffer(block.data, dtype)
        weakref.finalize(owner, kept.free.append, block).atexit = False
        return owner.reshape(shape)

    def _dense_batch(self, column, slot) -> np.ndarray:
        """[b, ...] float32 of a dense column: rows that are arrays of
        one shape are stacked in place into memory the feeder keeps
        (`_batch_array`); lists, scalars and rows of mixed shapes go
        through `np.asarray`, which says what is wrong with them."""
        rows_stack = column and all(
            type(r) is np.ndarray and r.shape == column[0].shape
            for r in column
        )
        if not rows_stack:
            return np.asarray(column, np.float32)
        out = self._batch_array(
            slot, (len(column),) + column[0].shape, np.float32
        )
        return np.stack(column, out=out, casting="unsafe")

    def _column_to_arg(self, column, t: InputType, slot=None) -> Arg:
        b = len(column)
        if t.seq == 0:
            if t.kind == "dense":
                arr = self._dense_batch(column, slot)
                try:
                    v = arr.reshape((b,) + t.shape)
                except ValueError:
                    # dense_array: the declared dim is advisory — the
                    # actual sample shape wins (reference
                    # DenseScanner keeps multi-dim data as fed and
                    # only records frame height/width)
                    v = arr.reshape(b, -1)
                return Arg(value=v)
            if t.kind == "ids":
                ids = np.asarray(column, np.int64).reshape(b).astype(np.int32)
                return Arg(ids=ids)
            if t.kind in ("sparse_binary", "sparse_float"):
                v = np.zeros((b,) + t.shape, np.float32)
                for i, row in enumerate(column):
                    if t.kind == "sparse_binary":
                        v[i, np.asarray(row, np.int64)] = 1.0
                    else:
                        idx, vals = _sparse_float_row(row)
                        v[i, np.asarray(idx, np.int64)] = np.asarray(
                            vals, np.float32
                        )
                return Arg(value=v)
        if t.seq == 1:
            lens = np.asarray([len(s) for s in column], np.int32)
            tmax = _bucket(int(lens.max()) if b else 1, self.buckets)
            if t.kind == "ids":
                ids = np.zeros((b, tmax), np.int32)
                for i, s in enumerate(column):
                    ids[i, : len(s)] = np.asarray(s, np.int64)
                return Arg(ids=ids, seq_lens=lens)
            v = np.zeros((b, tmax) + t.shape, np.float32)
            if t.kind in ("sparse_binary", "sparse_float"):
                # sequence of sparse rows: each timestep is an index
                # list (or (indices, values)) — PyDataProvider2's
                # sparse_*_vector_sequence slots
                for i, s in enumerate(column):
                    for ti, row in enumerate(s):
                        if t.kind == "sparse_binary":
                            v[i, ti, np.asarray(row, np.int64)] = 1.0
                        else:
                            idx, vals = _sparse_float_row(row)
                            v[i, ti, np.asarray(idx, np.int64)] = (
                                np.asarray(vals, np.float32)
                            )
                return Arg(value=v, seq_lens=lens)
            for i, s in enumerate(column):
                v[i, : len(s)] = np.asarray(s, np.float32).reshape(
                    (len(s),) + t.shape
                )
            return Arg(value=v, seq_lens=lens)
        if t.seq == 2:
            # sub-sequences: sample = list of list of tokens/vectors
            sub_lens = [[len(ss) for ss in s] for s in column]
            smax = max(len(s) for s in sub_lens)
            flat_lens = np.asarray([sum(s) for s in sub_lens], np.int32)
            tmax = _bucket(int(flat_lens.max()), self.buckets)
            subl = np.zeros((b, smax), np.int32)
            for i, s in enumerate(sub_lens):
                subl[i, : len(s)] = s
            if t.kind == "ids":
                ids = np.zeros((b, tmax), np.int32)
                for i, s in enumerate(column):
                    flat = [tok for ss in s for tok in ss]
                    ids[i, : len(flat)] = flat
                return Arg(ids=ids, seq_lens=flat_lens, subseq_lens=subl)
            v = np.zeros((b, tmax) + t.shape, np.float32)
            for i, s in enumerate(column):
                flat = np.asarray(
                    [tok for ss in s for tok in ss], np.float32
                ).reshape(-1, *t.shape)
                v[i, : len(flat)] = flat
            return Arg(value=v, seq_lens=flat_lens, subseq_lens=subl)
        raise ValueError(f"unsupported input type {t}")
