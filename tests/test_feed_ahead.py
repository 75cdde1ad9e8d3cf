"""`SGD.train` takes its fed batches from a worker thread, a step ahead
(ISSUE 26): one worker a pass runs the reader and the feeder and hands
`(batch_id, feed)` over a bounded FIFO queue, `data.reader.Buffered`.
Held here, by counts and orders and never by a clock: the training is
what feeding the same batches inline gives, bit for bit; the feeder of
batch k+1 runs while batch k is still in its step; nothing runs further
ahead than the queue allows; a skipped batch is not fed; the reader's
and the feeder's exceptions arrive on the training thread as they are;
and no thread outlives the call, however it ends.

The transfer to the device runs a step ahead too, on the training thread
(ISSUE 32): batch k+1 is placed between step k's dispatch and its fetch
where the queue has it by then, and never waited for there; what the
placement takes from the queue (a batch, the pass's end, the worker's
exception) arrives where it did; the step gets the placed batch, the
evaluators the feeder's own."""

import gc
import threading
import weakref

import jax
import numpy as np
import pytest

from paddle_tpu import dsl
from paddle_tpu.core.arg import Arg
from paddle_tpu.core.config import OptimizationConf
from paddle_tpu.data import reader as rd
from paddle_tpu.data.feeder import DataFeeder, dense_vector, integer_value
from paddle_tpu.obs import flight_recorder as fr
from paddle_tpu.obs import metrics as om
from paddle_tpu.trainer import trainer as trainer_mod
from paddle_tpu.trainer import watchdog as wdg
from paddle_tpu.trainer.events import (
    BeginIteration, EndIteration, EndPass)
from paddle_tpu.trainer.trainer import FEED_AHEAD, SGD

WAIT_S = 60          # an Event that is not set by then fails the test
LOOPS = pytest.mark.parametrize("spd", [1, 3], ids=["plain", "chunks"])
PLACING = pytest.mark.parametrize("spd", [1, 2], ids=["plain", "chunks"])
OPT = OptimizationConf(learning_method="adam", learning_rate=1e-2)


def _conf():
    with dsl.model() as m:
        x = dsl.data("x", dim=8)
        y = dsl.data("label", dim=(), is_ids=True)
        h = dsl.fc(x, size=16, act="relu")
        o = dsl.fc(h, size=4, act="")
        dsl.classification_cost(o, y)
    return m.conf


def _batches(n, bs=8, seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.standard_normal((bs, 8)).astype(np.float32),
             rng.integers(0, 4, bs).astype(np.int32)) for _ in range(n)]


def _feeder(raw):
    """numpy in, numpy out: what the worker is meant to touch."""
    return {"x": Arg(value=np.array(raw[0])), "label": Arg(ids=raw[1])}


def _sgd(spd, **kw):
    return SGD(_conf(), OPT, seed=7, steps_per_dispatch=spd, **kw)


def _workers():
    return [t for t in threading.enumerate() if t.name == "feed-ahead"]


class Recorder:
    """A feeder and a handler that write down what happened, in order,
    on whichever thread it happened."""

    def __init__(self, feeder=_feeder):
        self.feeder, self.log = feeder, []
        self.threads = {}

    def feed(self, raw):
        self.log.append(("feed", raw[2], None))
        self.threads["feed"] = threading.current_thread()
        return self.feeder(raw)

    def handle(self, e):
        if isinstance(e, (BeginIteration, EndIteration)):
            self.log.append((type(e).__name__, e.pass_id, e.batch_id))
            self.threads[type(e).__name__] = threading.current_thread()


def _numbered(batches):
    """The batches with their number beside them, so that a feeder can
    tell which one it was handed."""
    return [(x, y, i) for i, (x, y) in enumerate(batches)]


def _inline(spd, batches, passes):
    """The same batches fed on this thread and stepped through
    `run_step`, or through `run_steps` in the chunks the loop makes."""
    t, costs = _sgd(spd), []
    for _ in range(passes):
        for i in range(0, len(batches), spd):
            feeds = [_feeder(raw) for raw in batches[i:i + spd]]
            if spd == 1:
                costs.append(t.run_step(feeds[0])[0])
            else:
                costs.extend(t.run_steps(feeds)[0])
    return t, costs


@LOOPS
def test_losses_and_parameters_are_those_of_feeding_inline(spd):
    batches = _batches(7)
    inline, want = _inline(spd, batches, passes=2)
    t, got = _sgd(spd), []
    t.train(reader=lambda: iter(batches), feeder=_feeder, num_passes=2,
            event_handler=lambda e: got.append(e.cost)
            if isinstance(e, EndIteration) else None)
    assert got == want              # bit for bit
    for name, value in inline.params.items():
        np.testing.assert_array_equal(np.asarray(t.params[name]),
                                      np.asarray(value))
    assert t.global_step == inline.global_step == 14
    assert not _workers()


def _fresh_memory(feeder):
    """The feeder's batches, each copied into memory of its own."""
    return lambda raw: jax.tree_util.tree_map(np.array, feeder(raw))


@pytest.mark.parametrize("spd", [1, 4], ids=["plain", "chunks"])
def test_batches_in_memory_taken_again_train_what_fresh_memory_trains(spd):
    """`DataFeeder` stacks array rows into memory it takes again once
    nothing refers to a batch (ISSUE 30). Over many more batches than
    the queue, the worker, the step, the chunk loop and the runtime
    hold at once, every loss, the evaluator's sum over the fed `x`
    (read AFTER the batch's step, in `_after_batch`) and the final
    parameters are those of a feeder whose every batch is a copy in
    fresh memory: no batch was written before its last reader was
    done with it. And memory WAS taken again, so the test held it."""
    n = 4 * (FEED_AHEAD + 4)
    rng = np.random.default_rng(30)
    rows = [(rng.standard_normal(8).astype(np.float32), int(rng.integers(4)))
            for _ in range(8 * n)]

    def run(wrap):
        feeder = wrap(DataFeeder(
            {"x": 0, "label": 1},
            {"x": dense_vector(8), "label": integer_value(4)}))
        t, costs, sums = _sgd(
            spd, evaluators=[{"type": "sum", "name": "fed", "input": "x"}],
        ), [], []

        def handler(e):
            if isinstance(e, EndIteration):
                costs.append(e.cost)
            if isinstance(e, EndPass):
                sums.append(e.evaluator_results["fed"])

        t.train(reader=rd.batched(lambda: iter(rows), 8), feeder=feeder,
                num_passes=2, event_handler=handler)
        return t, costs, sums

    reg = om.get_registry()
    reused0 = reg.counter("feeder.buffers_reused").get()
    t, got, fed = run(lambda feeder: feeder)
    reused = reg.counter("feeder.buffers_reused").get() - reused0
    fresh, want, fed_fresh = run(_fresh_memory)
    assert len(got) == 2 * n and got == want        # bit for bit
    assert fed == fed_fresh
    for name, value in fresh.params.items():
        np.testing.assert_array_equal(np.asarray(t.params[name]),
                                      np.asarray(value))
    assert reused >= n
    assert not _workers()


@LOOPS
def test_chunks_and_plain_steps_see_the_same_batches_in_order(spd):
    """Begin and End of every batch fire on the training thread, in the
    reader's order, Begin(k) before End(k); every feed is made on
    another thread, in the reader's order too."""
    rec = Recorder()
    _sgd(spd).train(reader=lambda: iter(_numbered(_batches(7))),
                    feeder=rec.feed, num_passes=2,
                    event_handler=rec.handle)
    by = {kind: [e[1:] for e in rec.log if e[0] == kind]
          for kind in ("feed", "BeginIteration", "EndIteration")}
    ids = [(p, b) for p in (0, 1) for b in range(7)]
    assert by["BeginIteration"] == by["EndIteration"] == ids
    assert [n for n, _ in by["feed"]] == list(range(7)) * 2
    for p, b in ids:
        assert rec.log.index(("BeginIteration", p, b)) < rec.log.index(
            ("EndIteration", p, b))
    here = threading.current_thread()
    assert rec.threads["BeginIteration"] is here
    assert rec.threads["EndIteration"] is here
    assert rec.threads["feed"] is not here
    assert rec.threads["feed"].name == "feed-ahead"


@LOOPS
def test_the_next_batch_is_fed_while_this_one_is_in_its_step(spd):
    """EndIteration(0) does not return until the feeder has been
    entered for batch 1: inline, that would wait for ever."""
    entered = threading.Event()
    seen = []

    def feeder(raw):
        if raw[2] == 1:
            entered.set()
        return _feeder(raw)

    def handler(e):
        if isinstance(e, EndIteration) and e.batch_id == 0:
            seen.append(entered.wait(WAIT_S))

    _sgd(spd).train(reader=lambda: iter(_numbered(_batches(6))),
                    feeder=feeder, event_handler=handler)
    assert seen == [True]


@LOOPS
def test_the_worker_runs_no_further_ahead_than_the_queue_allows(spd):
    """When batch k's step ends, batches 0..k have been taken: beside
    them the queue holds FEED_AHEAD at most, the worker one, and one
    is placed on the device for the next step."""
    fed, worst = [], []

    def feeder(raw):
        fed.append(raw[2])
        return _feeder(raw)

    def handler(e):
        if isinstance(e, BeginIteration) and e.batch_id == 0:
            # hold the step until the worker has fed all it may, and
            # then a little longer: room for one that would overrun
            for _ in range(20000):
                if len(fed) >= 1 + FEED_AHEAD + 1:
                    break               # nothing is placed before a step
                threading.Event().wait(0.0005)
            for _ in range(100):
                threading.Event().wait(0.0005)
            worst.append(len(fed) - 1)
        if isinstance(e, EndIteration):
            worst.append(len(fed) - (e.batch_id + 1))

    _sgd(spd).train(reader=lambda: iter(_numbered(_batches(12))),
                    feeder=feeder, event_handler=handler)
    assert fed == list(range(12))
    # a chunk takes its batches before any of them ends
    assert worst[0] <= FEED_AHEAD + 1
    assert max(worst) <= FEED_AHEAD + 1 + 1 + (spd - 1)
    assert FEED_AHEAD == 2


@LOOPS
def test_a_skipped_batch_is_read_and_not_fed(spd):
    rec = Recorder()
    read = []

    def reader():
        for raw in _numbered(_batches(7)):
            read.append(raw[2])
            yield raw

    _sgd(spd).train(reader=reader, feeder=rec.feed, num_passes=2,
                    event_handler=rec.handle, skip_batches=3)
    assert read == list(range(7)) * 2
    assert [e[1] for e in rec.log if e[0] == "feed"] == [
        3, 4, 5, 6, *range(7)]
    assert [e[1:] for e in rec.log if e[0] == "BeginIteration"] == [
        (0, 3), (0, 4), (0, 5), (0, 6), *((1, b) for b in range(7))]


class DiskDied(RuntimeError):
    pass


def _raises_in_worker(where):
    def reader():
        for raw in _numbered(_batches(6)):
            if where == "reader" and raw[2] == 4:
                raise DiskDied("reading batch 4")
            yield raw

    def feeder(raw):
        if where == "feeder" and raw[2] == 4:
            raise DiskDied("feeding batch 4")
        return _feeder(raw)

    return reader, feeder


@LOOPS
@pytest.mark.parametrize("where", ["reader", "feeder"])
def test_the_workers_exception_arrives_where_its_batch_would(spd, where):
    """With its own type and a traceback that leads into the function
    that raised, after every batch before it has been trained (a chunk
    that was being gathered is not dispatched, as before)."""
    reader, feeder = _raises_in_worker(where)
    rec = Recorder(feeder)
    with pytest.raises(DiskDied, match="batch 4") as ei:
        _sgd(spd).train(reader=reader, feeder=rec.feed,
                        event_handler=rec.handle)
    frames = []
    tb = ei.value.__traceback__
    while tb is not None:
        frames.append(tb.tb_frame.f_code.co_name)
        tb = tb.tb_next
    assert where in frames and "train" in frames
    begun = [e[2] for e in rec.log if e[0] == "BeginIteration"]
    ended = [e[2] for e in rec.log if e[0] == "EndIteration"]
    assert begun == [0, 1, 2, 3]
    assert ended == ([0, 1, 2, 3] if spd == 1 else [0, 1, 2])
    assert not _workers()


class Stop(Exception):
    pass


def _ended_by_handler(t, tmp_path, monkeypatch):
    def handler(e):
        if isinstance(e, EndIteration) and e.batch_id == 2:
            raise Stop
    with pytest.raises(Stop):
        t.train(reader=lambda: iter(_batches(12)), feeder=_feeder,
                event_handler=handler)


def _ended_by_begin_handler(t, tmp_path, monkeypatch):
    def handler(e):
        if isinstance(e, BeginIteration) and e.batch_id == 2:
            raise Stop
    with pytest.raises(Stop):
        t.train(reader=lambda: iter(_batches(12)), feeder=_feeder,
                event_handler=handler)


def _ended_by_preemption(t, tmp_path, monkeypatch):
    guard = trainer_mod._NullPreemptionGuard()
    monkeypatch.setattr(wdg, "PreemptionGuard", lambda: guard)
    trained = []

    def handler(e):
        if isinstance(e, EndIteration):
            trained.append(e.batch_id)
            if e.batch_id == 3:
                guard.preempted = True      # what SIGTERM's handler does
    with pytest.raises(wdg.Preempted) as ei:
        t.train(reader=lambda: iter(_batches(12)), feeder=_feeder,
                event_handler=handler, save_dir=str(tmp_path / "ckpt"))
    # what was fed ahead is not counted: trained batches only
    assert ei.value.batches_done == len(trained)
    assert t.global_step == len(trained)


def _ended_by_the_watchdog(t, tmp_path, monkeypatch):
    def feeder(raw):
        feed = _feeder(raw)
        if raw[2] >= 2:
            feed["x"] = Arg(value=np.full_like(raw[0], np.nan))
        return feed
    with pytest.raises(wdg.WatchdogAbort):
        t.train(reader=lambda: iter(_numbered(_batches(12))),
                feeder=feeder)


def _ended_by_the_reader(t, tmp_path, monkeypatch):
    t.train(reader=lambda: iter(_batches(5)), feeder=_feeder,
            num_passes=2)
    assert t.global_step == 10


@LOOPS
@pytest.mark.parametrize("end", [
    _ended_by_the_reader, _ended_by_handler, _ended_by_begin_handler,
    _ended_by_preemption, _ended_by_the_watchdog],
    ids=lambda f: f.__name__.lstrip("_"))
def test_no_worker_is_alive_after_the_call(spd, end, tmp_path,
                                           monkeypatch):
    assert not _workers()
    kw = {}
    if end is _ended_by_the_watchdog:
        kw["watchdog"] = wdg.WatchdogConfig(skip_budget=1)
    end(_sgd(spd, **kw), tmp_path, monkeypatch)
    assert not _workers()


@LOOPS
def test_a_resume_trains_every_batch_once(spd, tmp_path, monkeypatch):
    """Preempted with batches fed ahead in the queue: they are dropped,
    the checkpoint counts the trained ones, and the resumed call feeds
    exactly the rest."""
    guard = trainer_mod._NullPreemptionGuard()
    monkeypatch.setattr(wdg, "PreemptionGuard", lambda: guard)
    batches = _numbered(_batches(10))
    save_dir = str(tmp_path / "ckpt")
    rec = Recorder()

    def handler(e):
        rec.handle(e)
        if isinstance(e, EndIteration) and e.batch_id == 4:
            guard.preempted = True

    t = _sgd(spd)
    with pytest.raises(wdg.Preempted) as ei:
        t.train(reader=lambda: iter(batches), feeder=rec.feed,
                event_handler=handler, save_dir=save_dir)
    done = ei.value.batches_done
    assert done == len([e for e in rec.log if e[0] == "EndIteration"])
    guard.preempted = False
    t2, rec2 = _sgd(spd), Recorder()
    assert t2.resume(save_dir) == 0
    t2.train(reader=lambda: iter(batches), feeder=rec2.feed,
             event_handler=rec2.handle, save_dir=save_dir)
    trained = [e[2] for r in (rec, rec2) for e in r.log
               if e[0] == "EndIteration"]
    assert trained == list(range(10))
    assert [e[1] for e in rec2.log if e[0] == "feed"] == list(
        range(done, 10))
    straight = _sgd(spd)
    straight.train(reader=lambda: iter(batches), feeder=_feeder)
    for name, value in straight.params.items():
        np.testing.assert_array_equal(np.asarray(t2.params[name]),
                                      np.asarray(value))


def test_the_feed_reaches_the_step_as_numpy():
    """The worker hands over what the feeder made, numpy: no transfer,
    no JAX array, is made on its thread. The training thread places
    it, and the step receives device arrays."""
    handed, stepped = [], []
    t = _sgd(1)
    place, step = trainer_mod.shard_batch, t.step_fn._step

    def shard_batch(feed, *a, **kw):
        handed.append((threading.current_thread(),
                       jax.tree_util.tree_leaves(feed)))
        return place(feed, *a, **kw)

    def stepped_with(params, opt_state, state, feed, *a):
        stepped.extend(jax.tree_util.tree_leaves(feed))
        return step(params, opt_state, state, feed, *a)

    t.step_fn._step = stepped_with
    try:
        trainer_mod.shard_batch = shard_batch
        t.train(reader=lambda: iter(_batches(3)), feeder=_feeder)
    finally:
        trainer_mod.shard_batch = place
    assert len(handed) == 3
    assert all(th is threading.current_thread() for th, _ in handed)
    assert all(type(x) is np.ndarray for _, xs in handed for x in xs)
    assert len(stepped) == 6
    assert all(isinstance(x, jax.Array) for x in stepped)


# ---- the placement, a step ahead (ISSUE 32) ---------------------------


def _until(cond):
    for _ in range(int(WAIT_S / 0.0005)):
        if cond():
            return
        threading.Event().wait(0.0005)
    raise AssertionError("waited %d s in vain" % WAIT_S)


class Paced(Recorder):
    """A reader, a feeder and a handler under which batch k+1 is in the
    queue when step k is dispatched: BeginIteration(k) waits until the
    pass's worker has entered the feeder for batch k+2 (one worker, in
    order: it has put k+1 by then) or has ended."""

    def __init__(self, batches, feeder=_feeder, then=None):
        super().__init__(feeder)
        self.batches, self.then, self.entered = batches, then, -1

    def reader(self):
        self.entered = -1
        return iter(_numbered(self.batches))

    def feed(self, raw):
        self.entered = raw[2]
        return super().feed(raw)

    def handle(self, e):
        super().handle(e)
        if isinstance(e, BeginIteration):
            _until(lambda: self.entered >= e.batch_id + 2
                   or not _workers())
        if self.then is not None:
            self.then(e)


@pytest.fixture
def recorder():
    rec = fr.enable_flight_recorder()
    try:
        yield rec
    finally:
        fr.disable_flight_recorder()


def _steps(rec, t):
    """[{name: [spans]}] of a call's `train.step` roots, in order."""
    spans = [s for s in rec.spans() if s["trace_id"] == t.last_trace_id]
    roots = sorted((s for s in spans if s["name"] == "train.step"),
                   key=lambda s: s["t0_ns"])
    out = []
    for r in roots:
        kids = {}
        for s in spans:
            if s["parent_id"] == r["span_id"]:
                kids.setdefault(s["name"], []).append(s)
        out.append(kids)
    return out


def _placed_counts():
    reg = om.get_registry()
    return [reg.counter("trainer.feed_placed_" + k).get()
            for k in ("ahead", "late")]


@PLACING
def test_the_next_batch_is_placed_between_dispatch_and_fetch(
        spd, recorder):
    """Step k's dispatch has returned, its loss has not been asked for:
    there batch k+1 goes to the device. Only a pass's first batch (and
    a chunk's later ones) is placed by its own step, before its
    dispatch."""
    before = _placed_counts()
    paced = Paced(_batches(8))
    t = _sgd(spd)
    t.train(reader=paced.reader, feeder=paced.feed,
            event_handler=paced.handle)
    ahead, late = (a - b for a, b in zip(_placed_counts(), before))
    steps = _steps(recorder, t)
    assert len(steps) == 8 // spd
    for i, kids in enumerate(steps):
        (dispatch,), (fetch,) = kids["train.dispatch"], kids["train.fetch"]
        placed = kids.get("train.h2d", [])
        early = [s for s in placed if s["t1_ns"] <= dispatch["t0_ns"]]
        between = [s for s in placed if s["t0_ns"] >= dispatch["t1_ns"]
                   and s["t1_ns"] <= fetch["t0_ns"]]
        assert len(early) + len(between) == len(placed)
        # the chunk's first batch was placed by the step before
        assert len(early) == (spd if i == 0 else spd - 1)
        assert len(between) == (1 if i < len(steps) - 1 else 0)
    assert (ahead, late) == (len(steps) - 1, 8 - (len(steps) - 1))


@PLACING
def test_placement_never_waits_for_the_worker(spd, recorder):
    """The feeder of batch 2 returns only after EndIteration(1): a
    placement that waited for it after step 1's dispatch would keep
    that step's loss from being fetched, for ever. The batch is placed
    late, by its own step, and counted so."""
    released = threading.Event()
    held = []

    def feeder(raw):
        if raw[2] == 2:
            held.append(released.wait(WAIT_S))
        return _feeder(raw)

    def handler(e):
        if isinstance(e, EndIteration) and e.batch_id == 1:
            released.set()

    before = _placed_counts()
    t = _sgd(spd)
    t.train(reader=lambda: iter(_numbered(_batches(4))), feeder=feeder,
            event_handler=handler)
    assert held == [True] and t.global_step == 4
    ahead, late = (a - b for a, b in zip(_placed_counts(), before))
    assert ahead + late == 4 and late >= 2
    step = _steps(recorder, t)[2 // spd]
    first = min(step["train.h2d"], key=lambda s: s["t0_ns"])
    assert first["t1_ns"] <= step["train.dispatch"][0]["t0_ns"]
    assert first["t0_ns"] >= step["train.input_wait.feeder"][0]["t1_ns"]


@PLACING
def test_events_come_in_the_order_they_came_in(spd):
    """BeginIteration(k) -> step -> EndIteration(k) -> BeginIteration
    (k+1), a chunk's Begins before its Ends: a batch placed ahead
    begins no earlier for it."""
    paced = Paced(_batches(7))
    _sgd(spd).train(reader=paced.reader, feeder=paced.feed,
                    num_passes=2, event_handler=paced.handle)
    got = [(kind[0], p, b) for kind, p, b in paced.log if kind != "feed"]
    want = []
    for p in (0, 1):
        for i in range(0, 7, spd):
            chunk = range(i, min(i + spd, 7))
            want += [("B", p, b) for b in chunk]
            want += [("E", p, b) for b in chunk]
    assert got == want


@PLACING
@pytest.mark.parametrize("where", ["reader", "feeder"])
def test_an_exception_taken_at_placement_arrives_after_the_step(
        spd, where):
    """The worker has died of batch 4 before step 3 is dispatched, so
    the placement after that dispatch finds its exception in the
    queue: EndIteration(3) still comes first, and then the exception,
    as it is."""
    reader, feeder = _raises_in_worker(where)
    rec = Recorder(feeder)

    def handler(e):
        rec.handle(e)
        if isinstance(e, BeginIteration) and e.batch_id == 3:
            _until(lambda: not _workers())

    with pytest.raises(DiskDied, match="batch 4"):
        _sgd(spd).train(reader=reader, feeder=rec.feed,
                        event_handler=handler)
    events = [e for e in rec.log if e[0] != "feed"]
    assert events[-1] == ("EndIteration", 0, 3)
    assert [e[2] for e in events if e[0] == "BeginIteration"] == [
        0, 1, 2, 3]
    assert [e[2] for e in events if e[0] == "EndIteration"] == [
        0, 1, 2, 3]
    assert not _workers()


@PLACING
def test_the_end_of_a_pass_taken_at_placement_ends_it_after_the_step(spd):
    """The worker has ended before the pass's last step is dispatched:
    the placement takes the end from the queue, the step ends as any
    other, then the pass, and the next pass trains every batch."""
    rec = Recorder()
    ends = []

    def handler(e):
        rec.handle(e)
        if isinstance(e, EndPass):
            ends.append(len(rec.log))
        if isinstance(e, BeginIteration) and e.batch_id == 3:
            _until(lambda: not _workers())

    t = _sgd(spd)
    t.train(reader=lambda: iter(_numbered(_batches(4))), feeder=rec.feed,
            num_passes=2, event_handler=handler)
    ended = [e[1:] for e in rec.log if e[0] == "EndIteration"]
    assert ended == [(p, b) for p in (0, 1) for b in range(4)]
    assert t.global_step == 8 and len(ends) == 2
    assert rec.log[ends[0] - 1] == ("EndIteration", 0, 3)
    assert not _workers()


def _preempted(t, paced, tmp_path, monkeypatch):
    guard = trainer_mod._NullPreemptionGuard()
    monkeypatch.setattr(wdg, "PreemptionGuard", lambda: guard)

    def then(e):
        if isinstance(e, EndIteration) and e.batch_id == 3:
            guard.preempted = True
    paced.then = then
    with pytest.raises(wdg.Preempted) as ei:
        t.train(reader=paced.reader, feeder=paced.feed,
                event_handler=paced.handle,
                save_dir=str(tmp_path / "ckpt"))
    return ei


def _handler_raised(t, paced, tmp_path, monkeypatch):
    def then(e):
        if isinstance(e, EndIteration) and e.batch_id == 3:
            raise Stop
    paced.then = then
    with pytest.raises(Stop) as ei:
        t.train(reader=paced.reader, feeder=paced.feed,
                event_handler=paced.handle)
    return ei


def _watchdog_aborted(t, paced, tmp_path, monkeypatch):
    with pytest.raises(wdg.WatchdogAbort) as ei:
        t.train(reader=paced.reader, feeder=paced.feed,
                event_handler=paced.handle)
    return ei


@PLACING
@pytest.mark.parametrize(
    "end", [_preempted, _handler_raised, _watchdog_aborted],
    ids=lambda f: f.__name__.lstrip("_"))
def test_an_early_end_drops_the_placed_batch_with_the_queues(
        spd, end, tmp_path, monkeypatch):
    """When the call ends a batch is on the device for a step that
    will not come. Nothing keeps it once the call has returned, not
    even for one who keeps the exception and so the call's frames."""
    placed = []
    place = trainer_mod.shard_batch

    def shard_batch(feed, *a, **kw):
        out = place(feed, *a, **kw)
        placed.append([weakref.ref(x)
                       for x in jax.tree_util.tree_leaves(out)])
        return out

    def feeder(raw):
        feed = _feeder(raw)
        if end is _watchdog_aborted and raw[2] >= 2:
            feed["x"] = Arg(value=np.full_like(raw[0], np.nan))
        return feed

    monkeypatch.setattr(trainer_mod, "shard_batch", shard_batch)
    kw = {}
    if end is _watchdog_aborted:
        kw["watchdog"] = wdg.WatchdogConfig(skip_budget=1)
    t = _sgd(spd, **kw)
    paced = Paced(_batches(12), feeder)
    raised = end(t, paced, tmp_path, monkeypatch)
    begun = len([e for e in paced.log if e[0] == "BeginIteration"])
    assert begun == t.global_step == 4
    assert len(placed) == begun + 1     # one was placed ahead
    gc.collect()
    assert [r() for r in placed[-1]] == [None, None]
    assert raised.traceback and not _workers()


@PLACING
def test_a_resume_after_a_placement_trains_every_batch_once(
        spd, tmp_path, monkeypatch):
    """Preempted with batch 4 on the device: it is dropped with the
    queue's, the checkpoint counts the four trained, and the resumed
    call feeds and trains 4..9."""
    batches = _batches(10)
    save_dir = str(tmp_path / "ckpt")
    paced = Paced(batches)
    t = _sgd(spd)
    _preempted(t, paced, tmp_path, monkeypatch)
    monkeypatch.setattr(wdg, "PreemptionGuard",
                        trainer_mod._NullPreemptionGuard)
    t2, rec2 = _sgd(spd), Recorder()
    assert t2.resume(save_dir) == 0
    t2.train(reader=lambda: iter(_numbered(batches)), feeder=rec2.feed,
             event_handler=rec2.handle, save_dir=save_dir)
    trained = [e[2] for r in (paced, rec2) for e in r.log
               if e[0] == "EndIteration"]
    assert trained == list(range(10))
    assert [e[1] for e in rec2.log if e[0] == "feed"] == list(range(4, 10))
    straight = _sgd(spd)
    straight.train(reader=lambda: iter(batches), feeder=_feeder)
    for name, value in straight.params.items():
        np.testing.assert_array_equal(np.asarray(t2.params[name]),
                                      np.asarray(value))


@PLACING
def test_a_watchdog_rollback_keeps_the_placed_batch(spd, tmp_path):
    """Two batches of NaN in pass 1 spend the skip budget and roll the
    parameters back to pass 0's checkpoint, with the next batch on the
    device already: the data stream does not roll back, every batch
    begins and ends once, in order."""
    def feeder(raw):
        feed = _feeder(raw)
        if raw[2] in (3, 4) and fed.count(raw[2]) == 1:
            feed["x"] = Arg(value=np.full_like(raw[0], np.nan))
        fed.append(raw[2])
        return feed

    fed = []
    paced = Paced(_batches(6), feeder)
    t = _sgd(spd, watchdog=wdg.WatchdogConfig(skip_budget=1,
                                              good_batches=3))
    t.train(reader=paced.reader, feeder=paced.feed, num_passes=3,
            event_handler=paced.handle, save_dir=str(tmp_path / "ckpt"))
    report = t.last_watchdog_report
    assert report.rollbacks == 1 and not report.aborted
    ids = [(p, b) for p in range(3) for b in range(6)]
    assert [e[1:] for e in paced.log if e[0] == "BeginIteration"] == ids
    assert [e[1:] for e in paced.log if e[0] == "EndIteration"] == ids


class Kept:
    """An evaluator that keeps the feeds it is handed."""

    name = "kept"

    def __init__(self):
        self.feeds = []

    def add_batch(self, outs, feed):
        self.feeds.append(feed)

    def result(self):
        return len(self.feeds)


@PLACING
def test_evaluators_get_the_feeders_own_batch(spd, monkeypatch):
    """The step trains the placed batch; an evaluator reads labels and
    inputs from the batch the feeder made, on the host, the same
    objects: it gains no copy back from the device."""
    made, kept = [], Kept()

    def feeder(raw):
        made.append(_feeder(raw))
        return made[-1]

    t = _sgd(spd)
    monkeypatch.setattr(t, "_make_evaluators", lambda: [kept])
    t.train(reader=lambda: iter(_batches(5)), feeder=feeder)
    assert len(kept.feeds) == 5
    assert all(got is fed for got, fed in zip(kept.feeds, made))
    assert all(type(x) is np.ndarray for fed in kept.feeds
               for x in jax.tree_util.tree_leaves(fed))


@pytest.mark.parametrize("mesh", [False, True], ids=["one", "mesh"])
def test_run_step_from_an_external_loop_takes_numpy_or_placed(mesh):
    """`run_step(feed)` with the feeder's numpy batch, as paddle.v2's
    trainer and `train_batch` call it, trains what the same batch
    placed beforehand trains, bit for bit."""
    from paddle_tpu.core.mesh import make_mesh
    from paddle_tpu.parallel.dp import shard_batch

    kw = {}
    if mesh:
        kw["mesh"] = make_mesh({"data": 4}, devices=jax.devices()[:4])
    a, b = _sgd(1, **kw), _sgd(1, **kw)
    for raw in _batches(4):
        got = a.run_step(_feeder(raw))
        want = b.run_step(shard_batch(_feeder(raw), b.mesh))
        assert got[:2] == want[:2]
    assert a.train_batch(_feeder(raw)) == b.train_batch(_feeder(raw))
    for name, value in b.params.items():
        np.testing.assert_array_equal(np.asarray(a.params[name]),
                                      np.asarray(value))


def test_memory_taken_again_stops_growing_with_a_batch_placed_ahead():
    """Over 40 steps `DataFeeder` makes fresh memory only for what is
    alive at once: the queue's two, the worker's, the step's, the one
    placed ahead. Were a placed batch or its host copy held past its
    step, `fresh` would rise with every batch."""
    rng = np.random.default_rng(32)
    rows = [(rng.standard_normal(8).astype(np.float32), int(rng.integers(4)))
            for _ in range(8 * 40)]
    feeder = DataFeeder({"x": 0, "label": 1},
                        {"x": dense_vector(8), "label": integer_value(4)})
    fresh = om.get_registry().counter("feeder.buffers_fresh")
    before = fresh.get()
    t = _sgd(1)
    t.train(reader=rd.batched(lambda: iter(rows), 8), feeder=feeder)
    assert t.global_step == 40
    assert fresh.get() - before <= 6


# ---- data.reader.Buffered itself -------------------------------------


def _producers():
    return [t for t in threading.enumerate() if t.name == "buffered"]


def test_buffered_gives_the_sources_items_in_order_and_ends():
    it = rd.buffered(lambda: iter(range(100)), 3)()
    assert list(it) == list(range(100))
    assert not _producers()
    assert list(it) == []           # ended, and stays so


@pytest.mark.parametrize("taken", [0, 1, 5])
def test_a_consumer_that_closes_early_stops_and_joins_the_producer(taken):
    """Before: the producer blocked for ever in `put`, a thread and
    what it held leaked with every abandoned pass."""
    made = []

    def source():
        for i in range(1000):
            made.append(i)
            yield i

    it = rd.buffered(source, 2)()
    assert [next(it) for _ in range(taken)] == list(range(taken))
    it.close()
    assert not _producers()
    assert len(made) <= taken + 2 + 2   # the queue, the hand, one more
    with pytest.raises(StopIteration):
        next(it)
    it.close()                          # and again does nothing


def test_a_dropped_consumer_stops_its_producer_too():
    closed = threading.Event()

    def source():
        try:
            yield from range(1000)
        finally:
            closed.set()        # the source's own clean-up ran

    it = rd.buffered(source, 2)()
    assert next(it) == 0
    del it
    assert closed.wait(WAIT_S)
    assert not _producers()


def test_a_with_block_closes_the_consumer():
    with rd.Buffered(lambda: iter(range(1000)), 2) as it:
        assert next(it) == 0
        assert len(_producers()) == 1
    assert not _producers()


def test_buffered_never_holds_more_than_its_size():
    """The producer is let run until it can go no further; the source
    then has made what was taken, the queue's size and one in hand."""
    made = []
    full = threading.Event()

    def source():
        for i in range(50):
            made.append(i)
            if len(made) == 3 + 1:
                full.set()
            yield i

    it = rd.Buffered(source, 3)
    assert full.wait(WAIT_S)
    for _ in range(100):                # room for a producer gone wrong
        threading.Event().wait(0.0005)
    assert len(made) == 3 + 1
    for taken in range(50):
        assert next(it) == taken
        assert len(made) <= (taken + 1) + 3 + 1
    assert list(it) == []


def test_the_sources_exception_keeps_its_type_and_traceback():
    def source():
        yield 1
        raise DiskDied("after one")

    it = rd.Buffered(source, 4)
    assert next(it) == 1
    with pytest.raises(DiskDied, match="after one") as ei:
        next(it)
    names = []
    tb = ei.value.__traceback__
    while tb is not None:
        names.append(tb.tb_frame.f_code.co_name)
        tb = tb.tb_next
    assert "source" in names and "_produce" in names
    assert not _producers()


def test_a_source_that_cannot_be_called_raises_at_the_first_item():
    def source():
        raise DiskDied("no such file")

    with pytest.raises(DiskDied, match="no such file"):
        next(rd.Buffered(source, 2))
    assert not _producers()
