"""`SGD.train` takes its fed batches from a worker thread, a step ahead
(ISSUE 26): one worker a pass runs the reader and the feeder and hands
`(batch_id, feed)` over a bounded FIFO queue, `data.reader.Buffered`.
Held here, by counts and orders and never by a clock: the training is
what feeding the same batches inline gives, bit for bit; the feeder of
batch k+1 runs while batch k is still in its step; nothing runs further
ahead than the queue allows; a skipped batch is not fed; the reader's
and the feeder's exceptions arrive on the training thread as they are;
and no thread outlives the call, however it ends."""

import threading

import jax
import numpy as np
import pytest

from paddle_tpu import dsl
from paddle_tpu.core.arg import Arg
from paddle_tpu.core.config import OptimizationConf
from paddle_tpu.data import reader as rd
from paddle_tpu.data.feeder import DataFeeder, dense_vector, integer_value
from paddle_tpu.obs import metrics as om
from paddle_tpu.trainer import trainer as trainer_mod
from paddle_tpu.trainer import watchdog as wdg
from paddle_tpu.trainer.events import (
    BeginIteration, EndIteration, EndPass)
from paddle_tpu.trainer.trainer import FEED_AHEAD, SGD

WAIT_S = 60          # an Event that is not set by then fails the test
LOOPS = pytest.mark.parametrize("spd", [1, 3], ids=["plain", "chunks"])
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
    them the queue holds FEED_AHEAD at most and the worker one."""
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
                    break
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
    assert max(worst) <= FEED_AHEAD + 1 + (spd - 1)
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
    """The worker hands over what the feeder made: no transfer, no JAX
    array, is made on its thread."""
    got = []
    t = _sgd(1)
    step = t.run_step

    def run_step(feed, *a, **kw):
        got.extend(jax.tree_util.tree_leaves(feed))
        return step(feed, *a, **kw)

    t.run_step = run_step
    t.train(reader=lambda: iter(_batches(3)), feeder=_feeder)
    assert len(got) == 6
    assert all(type(x) is np.ndarray for x in got)


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
