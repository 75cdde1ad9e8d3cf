"""The training step is spanned from inside `SGD.train`: every step one
`train.step` root whose children are where the training thread's time
goes, read here from a ring-only flight recorder, and the same durations
feed `StepTimeline`. A batch's placement on the device is `train.h2d`,
beside `train.dispatch`: after the dispatch of the step before where the
queue had the batch by then, else before its own. The pass's worker,
which reads and feeds a step ahead, spans its own work as `feed_ahead.*`,
outside the steps."""

import logging
import os
import threading
import time

import jax
import numpy as np
import pytest

from paddle_tpu import dsl
from paddle_tpu.core import flags as _flags
from paddle_tpu.core.arg import Arg
from paddle_tpu.core.config import OptimizationConf
from paddle_tpu.core.mesh import make_mesh
from paddle_tpu.data.feeder import DataFeeder, dense_vector, integer_value
from paddle_tpu.obs import flight_recorder as fr
from paddle_tpu.obs import metrics as om
from paddle_tpu.obs import tracing
from paddle_tpu.obs.timeline import SPAN_PART
from paddle_tpu.trainer import SGD
from paddle_tpu.trainer.events import EndIteration

STEP_S = 0.02        # the handler's sleep a batch: a step well over the glue
CHILDREN = {"train.input_wait.feeder", "train.dispatch", "train.fetch",
            "train.handlers"}
AHEAD = {"feed_ahead.reader", "feed_ahead.feeder"}


@pytest.fixture
def recorder():
    rec = fr.enable_flight_recorder()
    try:
        yield rec
    finally:
        fr.disable_flight_recorder()


@pytest.fixture
def fence_every_4():
    prev = _flags.get_flag("timeline_sample_period")
    _flags.set_flag("timeline_sample_period", 4)
    try:
        yield
    finally:
        _flags.set_flag("timeline_sample_period", prev)


def _train(stall_at=None, feeder_s=0.0, step_s=STEP_S, **sgd):
    """12 steps (2 passes of 6 batches of 4) of a tiny classifier. The
    EndIteration handler sleeps `step_s` a batch, on the training
    thread; on the worker the feeder sleeps `feeder_s` a batch and the
    reader 10 x STEP_S at `stall_at`."""
    with dsl.model() as g:
        x = dsl.data("x", (4,))
        y = dsl.data("y", (1,), is_ids=True)
        o = dsl.fc(x, size=3, name="output")
        dsl.classification_cost(o, y)
    rng = np.random.default_rng(0)
    xs = rng.standard_normal((24, 4)).astype(np.float32)
    ys = np.argmax(xs[:, :3], axis=1).astype(np.int64)
    yielded = []

    def batches():
        for i in range(0, 24, 4):
            if len(yielded) == stall_at:
                time.sleep(10 * STEP_S)
            yielded.append(i)
            yield [(xs[j], int(ys[j])) for j in range(i, i + 4)]

    convert = DataFeeder({"x": 0, "y": 1},
                         {"x": dense_vector(4), "y": integer_value(3)})

    def feeder(raw):
        time.sleep(feeder_s)
        return convert(raw)

    def handler(event):
        if isinstance(event, EndIteration):
            time.sleep(step_s)

    t = SGD(g.conf, OptimizationConf(learning_method="sgd",
                                     learning_rate=0.1), seed=3, **sgd)
    t.train(reader=batches, feeder=feeder, num_passes=2,
            event_handler=handler)
    return t


def _trees(rec, trace_id):
    """[(root, children in order of start)] of one train() call."""
    spans = [s for s in rec.spans() if s["trace_id"] == trace_id]
    roots = sorted((s for s in spans if s["name"] == "train.step"),
                   key=lambda s: s["t0_ns"])
    return [(r, sorted((s for s in spans
                        if s["parent_id"] == r["span_id"]),
                       key=lambda s: s["t0_ns"])) for r in roots]


def _assert_trees(trees, share=0.05):
    """Each step's children lie inside its root and do not overlap, and
    over the call they leave under `share` of the steps uncovered (over
    the call and not step by step: since the worker, the two threads
    hand the interpreter's lock to and fro in the glue between spans,
    and on a loaded machine one such hand-over can cost a step
    milliseconds)."""
    walls = covered = 0
    for root, kids in trees:
        at = root["t0_ns"]
        for k in kids:
            assert k["t0_ns"] >= at, (k["name"], "overlaps what came before")
            assert k["t1_ns"] >= k["t0_ns"]
            at = k["t1_ns"]
        assert at <= root["t1_ns"]
        walls += root["t1_ns"] - root["t0_ns"]
        covered += sum(k["t1_ns"] - k["t0_ns"] for k in kids)
    assert walls - covered <= share * walls, (walls, covered)


def _but_placements(kids):
    """The children's names with every `train.h2d` taken out, each of
    which follows the wait for its own batch (placed late) or the
    dispatch (the next batch, placed ahead)."""
    names = [k["name"] for k in kids]
    for i, name in enumerate(names):
        if name == "train.h2d":
            assert i and names[i - 1] in ("train.input_wait.feeder",
                                          "train.dispatch")
    return [n for n in names if n != "train.h2d"]


def _assert_timeline_is_the_spans(t, trees):
    got = {p: 0 for p in set(SPAN_PART.values())}
    for _, kids in trees:
        for k in kids:
            got[SPAN_PART[k["name"]]] += k["t1_ns"] - k["t0_ns"]
    assert t.last_timeline.totals() == {p: ns * 1e-9
                                        for p, ns in got.items()}


def test_every_step_has_one_root_with_the_tables_children(
        recorder, fence_every_4):
    t = _train()
    trees = _trees(recorder, t.last_trace_id)
    assert [r["labels"]["step_num"] for r, _ in trees] == list(range(12))
    assert [(r["labels"]["pass_id"], r["labels"]["batch_id"])
            for r, _ in trees] == [(p, b) for p in (0, 1) for b in range(6)]
    for i, (root, kids) in enumerate(trees):
        fenced = (i + 1) % 4 == 0
        assert _but_placements(kids) == [
            "train.input_wait.feeder", "train.dispatch", "train.fetch",
            *(["train.fence"] if fenced else []), "train.handlers"]
        assert root["status"] == "ok" and root["parent_id"] == ""
    # a pass's first batch is placed by its own step
    for first in (trees[0], trees[6]):
        assert [k["name"] for k in first[1]][:2] == [
            "train.input_wait.feeder", "train.h2d"]
    _assert_trees(trees)
    # nothing for the wait of a pass that only found it over, and the
    # reader no longer runs on the training thread
    names = [s["name"] for s in recorder.spans()]
    assert names.count("train.input_wait.feeder") == 12
    assert "train.input_wait.reader" not in names
    _assert_timeline_is_the_spans(t, trees)
    assert t.last_timeline.steps == 12
    assert sum(t.last_timeline.fractions().values()) == pytest.approx(
        1.0, abs=1e-3)


@pytest.mark.parametrize("spd", [1, 4])
@pytest.mark.parametrize("mesh", [False, True], ids=["one", "mesh"])
def test_the_transfer_is_a_span_beside_dispatch(recorder, spd, mesh):
    """With a mesh or without: every batch is placed once, under a
    `train.h2d` that is its step's child and not the dispatch's, and
    what the step is handed needs no second transfer."""
    kw = {}
    if mesh:
        kw["mesh"] = make_mesh({"data": 4}, devices=jax.devices()[:4])
    t = _train(steps_per_dispatch=spd, **kw)
    spans = recorder.spans()
    trees = _trees(recorder, t.last_trace_id)
    assert sum(r["labels"].get("steps", 1) for r, _ in trees) == 12
    placed = [s for s in spans if s["name"] == "train.h2d"]
    assert len(placed) == 12
    roots = {r["span_id"] for r, _ in trees}
    assert {s["parent_id"] for s in placed} <= roots
    # a batch: 4 rows of 4 float32 and 4 int32 ids
    assert {s["labels"]["bytes"] for s in placed} == {4 * 4 * 4 + 4 * 4}
    for root, kids in trees:
        assert {k["name"] for k in kids} >= CHILDREN
        _but_placements(kids)


def test_an_external_loops_numpy_feed_is_placed_under_dispatch(recorder):
    """`run_step` from a loop that is not `SGD.train`'s: a numpy feed
    is placed by the step itself, inside `train.dispatch`; one that is
    on the device already is not placed again; both train the same."""
    t = _train()
    rng = np.random.default_rng(1)
    feed = {"x": Arg(value=rng.standard_normal((4, 4)).astype(np.float32)),
            "y": Arg(ids=np.arange(4, dtype=np.int32) % 3)}
    before = len(recorder.spans())
    cost = t.run_step(feed)[0]
    mid = len(recorder.spans())
    t.run_step(jax.tree_util.tree_map(jax.numpy.asarray, feed))
    spans = recorder.spans()
    first, second = spans[before:mid], spans[mid:]
    dispatch = next(s for s in first if s["name"] == "train.dispatch")
    h2d = [s for s in first if s["name"] == "train.h2d"]
    assert len(h2d) == 1 and h2d[0]["parent_id"] == dispatch["span_id"]
    assert h2d[0]["labels"]["bytes"] == 4 * 4 * 4 + 4 * 4
    assert "train.h2d" not in [s["name"] for s in second]
    assert np.isfinite(cost)


def test_a_chunk_of_steps_is_one_root(recorder, fence_every_4):
    t = _train(steps_per_dispatch=4)
    trees = _trees(recorder, t.last_trace_id)
    # 6 batches a pass: a chunk of 4 and the pass's last 2
    assert [r["labels"]["steps"] for r, _ in trees] == [4, 2, 4, 2]
    assert [r["labels"]["batch_id"] for r, _ in trees] == [0, 4, 0, 4]
    assert [r["labels"]["step_num"] for r, _ in trees] == [0, 4, 6, 10]
    for root, kids in trees:
        n = root["labels"]["steps"]
        names = _but_placements(kids)
        assert names[:n] == ["train.input_wait.feeder"] * n
        rest = names[n:]
        assert rest[:2] == ["train.dispatch", "train.fetch"]
        assert rest[-1] == "train.handlers"
        assert rest[2:-1] in ([], ["train.fence"])
    _assert_trees(trees)
    # global_step 4 and 12 are fence points; 6 and 10 are not
    assert [("train.fence" in [k["name"] for k in kids])
            for _, kids in trees] == [True, False, False, True]
    _assert_timeline_is_the_spans(t, trees)
    assert t.last_timeline.steps == 12


def test_no_sink_and_no_session_draws_no_random_id(monkeypatch):
    def no_urandom(n):
        raise AssertionError("os.urandom called with tracing off")

    reg = om.get_registry()
    assert reg.stream is None and reg.recorder is None
    monkeypatch.setattr(os, "urandom", no_urandom)
    with tracing.span("outer", step_num=3, k=1) as outer:
        with tracing.span("inner") as inner:
            pass
    assert inner.trace_id == outer.trace_id
    assert inner.parent_id == outer.span_id != inner.span_id
    assert 0 <= inner.dur_ns <= outer.dur_ns
    t = _train()
    assert t.last_timeline.steps == 12


def test_ids_are_random_only_inside_a_carriers_trace(monkeypatch):
    drawn = []
    urandom = os.urandom
    monkeypatch.setattr(os, "urandom",
                        lambda n: drawn.append(n) or urandom(n))
    with tracing.span("begun_here"):
        pass
    assert not drawn
    with tracing.attach({"trace_id": "a" * 32, "span_id": "b" * 16}):
        with tracing.span("joined") as s:
            pass
    assert drawn == [8] and s.trace_id == "a" * 32
    assert s.parent_id == "b" * 16


def test_a_stalled_reader_is_one_slow_step_with_its_split(
        recorder, caplog):
    slow = om.get_registry().counter("trainer.slow_steps")
    rows = om.get_registry().counter("trainer.rows")
    nbytes = om.get_registry().counter("trainer.feed_bytes")
    before = slow.get(), rows.get(), nbytes.get()
    # the worker is two batches of the second pass ahead when its
    # reader stalls: the training thread waits out the rest at step 8
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.trainer"):
        _train(stall_at=8)
    assert slow.get() - before[0] == 1
    assert rows.get() - before[1] == 48
    assert nbytes.get() - before[2] == 12 * (4 * 4 * 4 + 4 * 4)
    events = [e for e in recorder.snapshot() if e["kind"] == "slow_step"]
    assert len(events) == 1
    e = events[0]
    assert e["step_num"] == 8 and (e["pass_id"], e["batch_id"]) == (1, 2)
    assert e["input_wait_s"] >= 6 * STEP_S
    assert e["wall_s"] > 3 * e["median_s"]
    parts = sum(e[k] for k in ("input_wait_s", "h2d_s", "dispatch_s",
                               "fetch_s", "fence_s", "handlers_s"))
    assert parts == pytest.approx(e["wall_s"], rel=0.05)
    lines = [r.getMessage() for r in caplog.records
             if "slow step" in r.getMessage()]
    assert len(lines) == 1 and "input_wait_s" in lines[0]


def test_spans_lie_in_the_profilers_trace(tmp_path):
    """With a profiler session on and no sink, the spans are in the
    `.xplane.pb`: every `train.*` on one line (the training thread's),
    the workers' `feed_ahead.*` on others, their labels as arguments
    and their names bare."""
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _train()
    finally:
        jax.profiler.stop_trace()
    files = list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    assert len(files) == 1
    lines, ahead = {}, {}
    for plane in ProfileData.from_file(str(files[0])).planes:
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("train."):
                    lines.setdefault((plane.name, i), []).append(ev)
                if ev.name.startswith("feed_ahead."):
                    ahead.setdefault((plane.name, i), []).append(ev)
    assert len(lines) == 1
    assert ahead and not set(ahead) & set(lines)
    fed = [ev for evs in ahead.values() for ev in evs
           if ev.name == "feed_ahead.feeder"]
    assert sorted(dict(ev.stats)["batch_id"] for ev in fed) == sorted(
        list(range(6)) * 2)
    events = next(iter(lines.values()))
    roots = [ev for ev in events if ev.name == "train.step"]
    # 12 steps, and the two calls of the reader that found a pass over
    assert len(roots) == 14
    stats = [dict(ev.stats) for ev in roots]
    assert sorted(s["step_num"] for s in stats)[-1] == 12
    assert {s["pass_id"] for s in stats} == {0, 1}
    for name in CHILDREN | {"train.h2d"}:
        inside = [ev for ev in events if ev.name == name]
        assert len(inside) >= 12, name
        for ev in inside:
            assert any(r.start_ns <= ev.start_ns
                       and ev.start_ns + ev.duration_ns
                       <= r.start_ns + r.duration_ns for r in roots)


def test_the_workers_spans_join_the_trace_outside_the_steps(recorder):
    """`feed_ahead.*` are the worker's own: in the call's trace, under
    no step, one reader call a batch and one that finds the pass over,
    one feeder call a batch in the reader's order."""
    t = _train()
    spans = [s for s in recorder.spans() if s["name"] in AHEAD]
    assert {s["trace_id"] for s in spans} == {t.last_trace_id}
    assert {s["parent_id"] for s in spans} == {""}
    names = [s["name"] for s in spans]
    assert names.count("feed_ahead.reader") == 2 * (6 + 1)
    fed = sorted((s for s in spans if s["name"] == "feed_ahead.feeder"),
                 key=lambda s: s["t0_ns"])
    assert [s["labels"]["batch_id"] for s in fed] == list(range(6)) * 2


def test_the_input_wait_is_the_blocked_get_and_not_the_workers_feeder(
        recorder):
    """A feeder of 2 x STEP_S on the worker under steps of 6 x STEP_S
    on the training thread: once the queue has filled, the training
    thread's wait for a fed batch is the hand-over alone."""
    t = _train(feeder_s=2 * STEP_S, step_s=6 * STEP_S)
    fed = [s for s in recorder.spans() if s["name"] == "feed_ahead.feeder"]
    assert len(fed) == 12
    assert all(s["t1_ns"] - s["t0_ns"] >= 2 * STEP_S * 1e9 for s in fed)
    for root, kids in _trees(recorder, t.last_trace_id):
        waited = [k for k in kids if k["name"] == "train.input_wait.feeder"]
        assert len(waited) == 1
        if root["labels"]["batch_id"] >= 1:
            assert waited[0]["t1_ns"] - waited[0]["t0_ns"] < STEP_S * 1e9


@pytest.mark.parametrize("spd", [1, 4])
def test_the_feed_ahead_counters_add_up_to_the_steps(recorder, spd):
    reg = om.get_registry()
    names = ("trainer.feed_ahead_ready", "trainer.feed_ahead_waited",
             "trainer.feed_worker_s")
    before = [reg.counter(n).get() for n in names]
    _train(feeder_s=STEP_S / 4, steps_per_dispatch=spd)
    ready, waited, worker_s = (reg.counter(n).get() - b
                               for n, b in zip(names, before))
    assert ready + waited == 12
    assert waited >= 2          # each pass's first batch is waited for
    spans = [s for s in recorder.spans() if s["name"] in AHEAD]
    assert worker_s == pytest.approx(
        sum(s["t1_ns"] - s["t0_ns"] for s in spans) * 1e-9, rel=1e-6)
    assert worker_s >= 12 * STEP_S / 4


@pytest.mark.parametrize("spd", [1, 4])
def test_the_placement_counters_add_up_to_the_steps(recorder, spd):
    """Every step's batch was on the device when the step was
    dispatched (`ahead`) or was placed by the step itself (`late`: a
    pass's first at least); `trainer.h2d_s` is the `train.h2d` spans'
    own seconds."""
    reg = om.get_registry()
    names = ("trainer.feed_placed_ahead", "trainer.feed_placed_late",
             "trainer.h2d_s")
    before = [reg.counter(n).get() for n in names]
    _train(steps_per_dispatch=spd)
    ahead, late, h2d_s = (reg.counter(n).get() - b
                          for n, b in zip(names, before))
    assert ahead + late == 12
    assert late >= 2            # each pass's first batch
    assert ahead >= 2           # the handler's sleep lets the worker lead
    spans = [s for s in recorder.spans() if s["name"] == "train.h2d"]
    assert len(spans) == 12
    assert h2d_s == pytest.approx(
        sum(s["t1_ns"] - s["t0_ns"] for s in spans) * 1e-9, rel=1e-6)


def test_no_worker_outlives_the_call():
    _train(step_s=0.0)
    assert not [th for th in threading.enumerate()
                if th.name == "feed-ahead"]
