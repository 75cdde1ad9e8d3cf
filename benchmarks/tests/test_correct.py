"""`correct` comes out true for a sound run and false for the control and
for each fault a cell can have, at sizes a CPU test can hold. These skip
the harness's look for a chip and drive the rest of a run; a fault is
planted by wrapping what the kind builds, underneath the timed path."""

import jax
import pytest

from benchmarks import compare, harness, run as R
from benchmarks.kinds import train as T
from benchmarks.tests import tiny

CELL = "resnet50.train_bs256"
SEED = 2 ** 31 + 77


def measure(cell, trace=False, devices=None):
    return R.measure(cell, SEED, 0.5, trace, devices or jax.devices()[:1],
                     peak=tiny.PEAK)


def test_sound_run_is_correct():
    res = measure(tiny.cell(CELL))
    assert res["correct"], (res["compared"], res["problems"])
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["compared"] and all(
        v is not None and v <= lim for _n, v, lim in res["compared"])
    rate = [m for m in res["metrics"] if m != "setup_s"]
    assert len(rate) == 1 and res["metrics"][rate[0]]["value"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0


class Broken:
    """The trainer's step with a fault planted underneath the timed path."""

    def __init__(self, step, call):
        self._step_fn, self._call = step, call

    def __getattr__(self, name):
        return getattr(self._step_fn, name)

    def __call__(self, *args, **kw):
        return self._call(self._step_fn, *args, **kw)


def _state_unchanged(step, params, opt_state, state, *a, **kw):
    """The step returns its state as it got it."""
    keep = jax.tree_util.tree_map(jax.numpy.copy, (params, opt_state, state))
    return keep + tuple(step(params, opt_state, state, *a, **kw)[3:])


def _half_batch(step, params, opt_state, state, feed, *a, **kw):
    """Half of the batch left out, the mean taken over the rest."""
    half = jax.tree_util.tree_map(lambda x: x[: x.shape[0] // 2], dict(feed))
    return step(params, opt_state, state, half, *a, **kw)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch])
def test_training_fault_is_not_correct(fault, monkeypatch):
    build = T.build_trainer

    def broken(*args, **kw):
        trainer = build(*args, **kw)
        trainer.step_fn = Broken(trainer.step_fn, fault)
        return trainer

    monkeypatch.setattr(T, "build_trainer", broken)
    res = measure(tiny.cell(CELL))
    assert not res["correct"]
    over = [n for n, v, lim in res["compared"] if not v <= lim]
    assert over, res["compared"]


def test_fp8_control_is_not_correct():
    """The reference in the program's place, computed in fp8."""
    from benchmarks import traffic

    cell = tiny.cell(CELL)
    pool = traffic.Pool(cell.traffic, SEED)
    ref = T.reference_readings(cell, SEED, pool)
    low = T.reference_readings(cell, SEED, pool, mode="fp8")
    ok, rows = compare.judge(compare.training_numbers(low, ref), cell.limits)
    assert not ok, rows
    ok, rows = compare.judge(compare.training_numbers(ref, ref), cell.limits)
    assert ok, rows


def test_a_mesh_in_a_cells_file_runs_on_four_virtual_devices():
    """A cell whose file names `mesh: {"data": 4}` drives its first steps
    on four of the virtual CPU devices with an all-reduce in the step, and
    the exchange between the chips left out (one chip's quarter of the
    rows, planted in the reference put in the program's place) is not
    correct."""
    from paddle_tpu.parallel.dp import assert_collectives

    devices = jax.devices()[:4]
    assert len(devices) == 4
    cell = tiny.cell(CELL, mesh={"data": 4})
    clock, spans = harness.CompileClock(), harness.Spans()
    trainer, pool, feeder, prog, _ = T.setup(cell, SEED, devices, clock,
                                             spans)
    assert len(prog["loss"]) == T.FIRST_STEPS
    feed = feeder([pool.sample(i) for i in next(pool.batches(0))])
    _ma, compiled = T._step_memory(trainer, feed)
    counts = assert_collectives(compiled.as_text(), "dp4 step",
                                require=("all-reduce",))
    assert counts["all-reduce"] >= 1
    ref = T.reference_readings(cell, SEED, pool)
    ok, rows = compare.judge(compare.training_numbers(prog, ref),
                             cell.limits)
    assert ok, rows
    alone = T.reference_readings(cell, SEED, pool, fault="quarter_batch")
    ok, rows = compare.judge(compare.training_numbers(alone, ref),
                             cell.limits)
    assert not ok, rows
