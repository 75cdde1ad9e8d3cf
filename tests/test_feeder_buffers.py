"""`DataFeeder` stacks a dense batch of array rows into memory it takes
again once nothing refers to the batch (ISSUE 30). Held here: memory IS
taken again when a batch is dropped, and counted; a batch that anything
still refers to (the array, a view of it however derived, the runtime's
copy of the argument) is never written again; another batch shape
starts the slot anew; and rows that are not arrays of one shape give
what `np.asarray(column, np.float32)` gives, value for value."""

import gc

import jax
import numpy as np
import pytest

from paddle_tpu.data import feeder as F
from paddle_tpu.obs import metrics as om

DIM = 12


def _feeder(dim=DIM):
    return F.DataFeeder({"x": 0, "y": 1},
                        {"x": F.dense_vector(dim), "y": F.integer_value(9)})


def _rows(k, n=4, shape=(DIM,), dtype=np.float32):
    """Batch number k: row i is filled with 100 * k + i."""
    return [(np.full(shape, 100 * k + i, dtype), i) for i in range(n)]


def _want(k, shape=(4, DIM)):
    return np.asarray([np.full(shape[1:], 100 * k + i, np.float32)
                       for i in range(shape[0])])


def _address(a):
    return a.__array_interface__["data"][0]


class Counters:
    def __init__(self):
        reg = om.get_registry()
        self._c = {n: reg.counter("feeder.buffers_" + n)
                   for n in ("reused", "fresh")}
        self._0 = {n: c.get() for n, c in self._c.items()}

    def __getattr__(self, name):
        return self._c[name].get() - self._0[name]


def test_a_dropped_batch_s_memory_takes_the_next_batch():
    f, c = _feeder(), Counters()
    first = f(_rows(0))["x"].value
    np.testing.assert_array_equal(first, _want(0))
    where = _address(first)
    assert (c.fresh, c.reused) == (1, 0)
    del first
    second = f(_rows(1))["x"].value
    assert _address(second) == where
    np.testing.assert_array_equal(second, _want(1))
    assert (c.fresh, c.reused) == (1, 1)


HOLDERS = {
    "the_array": lambda v: v,
    "the_arg": None,            # the whole fed dict, see the test
    "a_reshape": lambda v: v.reshape(2, 2, DIM),
    "a_slice": lambda v: v[1:3, ::2],
    "a_view_of_a_view": lambda v: v.reshape(-1)[DIM:].reshape(3, DIM)[1:],
    "a_transpose": lambda v: v.T,
    "a_memoryview": memoryview,
    "np_asarray_of_it": lambda v: np.asarray(v),
    "a_device_put": jax.device_put,
}


@pytest.mark.parametrize("how", sorted(HOLDERS))
def test_a_batch_that_is_held_is_never_written_again(how):
    """Whatever still refers to the batch's memory keeps its values
    through 8 further batches, each of which is dropped at once (so the
    feeder has every reason to take memory again, and does)."""
    f, c = _feeder(), Counters()
    fed = f(_rows(1))
    if how == "the_arg":
        held, read = fed, lambda h: h["x"].value
    else:
        held, read = HOLDERS[how](fed["x"].value), np.asarray
    want = np.array(read(held))
    assert want.size and want.min() >= 100 and want.max() < 200
    del fed
    for k in range(2, 10):
        np.testing.assert_array_equal(f(_rows(k))["x"].value, _want(k))
    np.testing.assert_array_equal(read(held), want)
    assert c.fresh == 2 and c.reused == 7
    del held
    gc.collect()
    f(_rows(10))
    assert c.fresh == 2         # and the held one came back


def test_a_jitted_call_s_argument_is_not_written_while_the_call_has_it():
    """The runtime keeps the argument of the last call; the result was
    computed from the values the batch had."""
    f = _feeder()
    total = jax.jit(lambda x: x.sum())
    got = []
    for k in range(12):
        got.append(total(f(_rows(k))["x"].value))
    assert [float(g) for g in got] == [float(_want(k).sum())
                                       for k in range(12)]


def test_every_batch_alive_at_once_has_memory_of_its_own():
    """A caller that collects its batches (tests do; so does a chunk of
    `steps_per_dispatch` feeds) costs memory, never a wrong batch; once
    they go, no further memory is made."""
    f, c = _feeder(), Counters()
    alive = [f(_rows(k))["x"].value for k in range(6)]
    assert len({_address(a) for a in alive}) == 6 and c.fresh == 6
    for k, a in enumerate(alive):
        np.testing.assert_array_equal(a, _want(k))
    del alive, a
    for k in range(6, 30):
        chunk = [f(_rows(k))["x"].value for _ in range(4)]
        assert len({_address(a) for a in chunk}) == 4
        del chunk
    assert c.fresh == 6 and c.reused == 4 * 24


def test_another_batch_shape_drops_what_the_slot_had_kept():
    """A ragged last batch (or another row shape) starts the slot anew:
    memory of the old shape is not taken again, nor kept."""
    f, c = _feeder(), Counters()
    for k in range(3):
        f(_rows(k))
    assert (c.fresh, c.reused) == (1, 2)
    kept = f._kept["x"]
    assert kept.shape == (4, DIM) and len(kept.free) == 1
    ragged = f(_rows(3, n=3))["x"].value
    np.testing.assert_array_equal(ragged, _want(3, shape=(3, DIM)))
    assert f._kept["x"] is not kept and f._kept["x"].shape == (3, DIM)
    assert f._kept["x"].free == []
    full = f(_rows(4))["x"].value       # back to 4 rows: anew again
    np.testing.assert_array_equal(full, _want(4))
    assert (c.fresh, c.reused) == (3, 2)
    del ragged, full
    assert f._kept["x"].shape == (4, DIM) and len(f._kept["x"].free) == 1


def test_slots_keep_their_memory_apart():
    f = F.DataFeeder({"a": 0, "b": 1},
                     {"a": F.dense_vector(DIM), "b": F.dense_vector(DIM)})
    for k in range(5):
        fed = f([(np.full(DIM, k, np.float32), np.full(DIM, -k, np.float32))
                 for _ in range(4)])
        assert (fed["a"].value == k).all() and (fed["b"].value == -k).all()
        assert not np.shares_memory(fed["a"].value, fed["b"].value)


def _as_the_parent(column, shape):
    """What the feeder made of a dense column before it kept memory."""
    arr = np.asarray(column, np.float32)
    try:
        return arr.reshape((len(column),) + shape)
    except ValueError:
        return arr.reshape(len(column), -1)


COLUMNS = {
    "lists": (DIM, lambda k: [[float(k + i + j) for j in range(DIM)]
                              for i in range(4)]),
    "tuples_and_arrays_mixed": (DIM, lambda k: [
        tuple(range(k, k + DIM)), np.arange(DIM, dtype=np.float32) + k]),
    "float64_arrays": (DIM, lambda k: [
        np.linspace(k, k + 1 / 3, DIM) for _ in range(4)]),
    "int64_arrays": (DIM, lambda k: [
        np.arange(DIM, dtype=np.int64) * (2 ** 40 + k) for _ in range(4)]),
    "bool_arrays": (DIM, lambda k: [
        np.arange(DIM) % (2 + k) == 0 for _ in range(4)]),
    "strided_rows": (DIM, lambda k: [
        np.arange(2 * DIM, dtype=np.float32)[::2] + k for _ in range(4)]),
    "image_rows_declared_flat": (3 * 4 * 4, lambda k: [
        np.full((3, 4, 4), k + i, np.float32) for i in range(4)]),
    "the_declared_dim_is_advisory": (7, lambda k: [
        np.full((2, 5), k + i, np.float32) for i in range(4)]),
    "scalars_of_dim_one": (1, lambda k: [np.float32(k + i)
                                         for i in range(4)]),
    "zero_d_arrays": (1, lambda k: [np.asarray(k + i, np.float32)
                                    for i in range(4)]),
    "an_array_subclass": (DIM, lambda k: [
        np.ma.masked_array(np.arange(DIM, dtype=np.float32) + k)
        for _ in range(4)]),
}


@pytest.mark.parametrize("rows", sorted(COLUMNS))
def test_every_kind_of_row_gives_what_the_parent_gave(rows):
    """Value for value, shape and dtype too, batch after batch (the
    second and third land in memory taken again where rows stack)."""
    dim, column = COLUMNS[rows]
    f = F.DataFeeder({"x": 0}, {"x": F.dense_vector(dim)})
    for k in range(3):
        got = f([(r,) for r in column(k)])["x"].value
        want = _as_the_parent(column(k), (dim,))
        assert got.dtype == np.float32 and got.shape == want.shape
        np.testing.assert_array_equal(got, want)


def test_rows_of_mixed_shapes_fail_as_they_did():
    f, c = F.DataFeeder({"x": 0}, {"x": F.dense_vector(DIM)}), Counters()
    column = [np.zeros(DIM, np.float32), np.zeros(DIM + 1, np.float32)]
    with pytest.raises(ValueError) as want:
        np.asarray(column, np.float32)
    with pytest.raises(ValueError) as got:
        f([(r,) for r in column])
    assert str(got.value) == str(want.value)
    assert (c.fresh, c.reused) == (0, 0)
    empty = f([])["x"].value            # no rows: nothing to stack
    assert empty.shape == (0, DIM) and empty.dtype == np.float32


def test_float64_rows_arrive_as_float32_rounded_as_asarray_rounds():
    f = _feeder()
    column = [np.full(DIM, 1 / 3 + i, np.float64) for i in range(4)]
    got = f([(r, 0) for r in column])["x"].value
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got, np.asarray(column, np.float32))
    assert got[0, 0] != column[0][0]    # it was rounded


def test_steady_state_makes_no_fresh_memory():
    """The shape of `SGD.train`'s traffic: a few batches alive at once
    (a queue, a hand, a step), the oldest let go as the next is fed.
    `fresh` stops at what is alive at once; `reused` counts the rest."""
    f, c = _feeder(), Counters()
    alive = []
    for k in range(40):
        alive.append(f(_rows(k)))
        if len(alive) > 5:
            alive.pop(0)
        for j, fed in enumerate(alive):
            np.testing.assert_array_equal(
                fed["x"].value, _want(k - len(alive) + 1 + j))
    assert c.fresh == 6 and c.reused == 34
