"""Set-up is timed from inside, with the tracing the program has: the
trainer's build under `trainer.build` and its parts, the age of the process
when the build began, and what the first `train.dispatch` of a trainer's
life spent tracing, lowering and compiling (or loading), from the compile
watch (`core.compile_cache.watch`), which also counts every later compile
and lets a slow step say that it recompiled.

Durations are held from BELOW only, by a `time.sleep` put where the test
wants the time to go: this machine is shared, and a test that holds a wall
time from above fails at random. Where a second must NOT have gone
somewhere, the test holds an order of instants instead."""

import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest
from jax._src import monitoring as jax_monitoring

from paddle_tpu import dsl
from paddle_tpu.core import compile_cache
from paddle_tpu.core import rng as _rng
from paddle_tpu.core.config import OptimizationConf
from paddle_tpu.data.feeder import DataFeeder, dense_vector, integer_value
from paddle_tpu.layers.basic import FCLayer
from paddle_tpu.obs import flight_recorder as fr
from paddle_tpu.obs import metrics as om
from paddle_tpu.obs import timeline as otl
from paddle_tpu.obs import tracing
from paddle_tpu.trainer import SGD

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PARTS = ("network", "optimizer", "params", "opt_state", "place")
FIRST = ("trace_s", "lower_s", "backend_s", "cache_hits", "cache_misses")
TRACE_SLEEP_S = 0.2


@pytest.fixture
def recorder():
    rec = fr.enable_flight_recorder()
    try:
        yield rec
    finally:
        fr.disable_flight_recorder()


def _model(width=3):
    with dsl.model() as g:
        x = dsl.data("x", (4,))
        y = dsl.data("y", (1,), is_ids=True)
        o = dsl.fc(x, size=width, name="output")
        dsl.classification_cost(o, y)
    return g.conf


def _trainer(width=3, **sgd):
    return SGD(_model(width), OptimizationConf(learning_method="sgd",
                                               learning_rate=0.1),
               seed=3, **sgd)


_XS = np.random.default_rng(0).standard_normal((64, 4)).astype(np.float32)
_YS = np.argmax(_XS[:, :3], axis=1).astype(np.int64)
_FEED = DataFeeder({"x": 0, "y": 1},
                   {"x": dense_vector(4), "y": integer_value(3)})


def _reader(sizes):
    """A pass of batches of these sizes."""
    def batches():
        at = 0
        for n in sizes:
            yield [(_XS[j % 64], int(_YS[j % 64])) for j in range(at, at + n)]
            at += n
    return batches


def _train(t, sizes=(4,) * 6, passes=1, handler=None):
    t.train(reader=_reader(sizes), feeder=_FEED, num_passes=passes,
            event_handler=handler)
    return t


def _first_dispatch_gauges():
    reg = om.get_registry()
    out = {k: reg.gauge("trainer.first_dispatch." + k).get() for k in FIRST}
    out["first_dispatch_s"] = reg.gauge("trainer.first_dispatch_s").get()
    return out


def test_watch_twice_registers_one_set_of_listeners():
    compile_cache.watch()

    def counts():
        return (len(jax_monitoring.get_event_duration_listeners()),
                len(jax_monitoring.get_event_listeners()),
                len(jax_monitoring.get_scalar_listeners()))

    before = counts()
    compile_cache.watch()
    compile_cache.enable()
    _trainer()                       # TrainStep.__init__ calls it too
    assert counts() == before
    for mine, have in (
            (compile_cache._on_duration,
             jax_monitoring.get_event_duration_listeners()),
            (compile_cache._on_event, jax_monitoring.get_event_listeners()),
            (compile_cache._on_start,
             jax_monitoring.get_scalar_listeners())):
        assert have.count(mine) == 1


def test_a_trainer_built_and_stepped_leaves_its_setup_in_the_registry():
    reg = om.get_registry()
    reg.reset_prefix("trainer.")
    reg.reset_prefix("process.")
    t = _trainer()
    built = reg.gauge("trainer.build_s")
    parts = {p: built.get(part=p) for p in PARTS}
    assert all(v is not None and v >= 0 for v in parts.values()), parts
    # the parts are disjoint stretches of the root
    assert built.get(part="all") >= sum(parts.values())
    assert t._build_s == {**parts, "all": built.get(part="all")}
    assert reg.gauge("process.start_to_build_s").get() > 0
    assert otl.process_age_s() >= reg.gauge("process.start_to_build_s").get()
    # nothing of the first dispatch before a step has been dispatched
    assert reg.gauge("trainer.first_dispatch_s").get() is None
    _train(t)
    got = _first_dispatch_gauges()
    assert all(v is not None for v in got.values()), got
    assert got["trace_s"] > 0 and got["lower_s"] > 0 and got["backend_s"] > 0
    # what the watch saw lies inside the dispatch and counts no second twice
    assert (got["trace_s"] + got["lower_s"] + got["backend_s"]
            <= got["first_dispatch_s"])


def test_a_sleep_at_trace_time_is_tracing_and_not_compiling(
        monkeypatch, recorder):
    """The forward of the `fc` layer sleeps as it is TRACED: the first
    dispatch's tracing seconds and the step's own hold the sleep; the
    backend's stage began only after it."""
    slept = []
    plain = FCLayer.forward

    def forward(self, params, inputs, ctx):
        t0 = time.monotonic_ns()
        time.sleep(TRACE_SLEEP_S)
        slept.append((t0, time.monotonic_ns()))
        return plain(self, params, inputs, ctx)

    monkeypatch.setattr(FCLayer, "forward", forward)
    reg = om.get_registry()
    was = reg.counter("compile.trace_s").get(fn="step")
    _train(_trainer(width=5), sizes=(4, 4))
    assert len(slept) == 1              # traced once, run from the cache
    got = _first_dispatch_gauges()
    assert got["trace_s"] >= TRACE_SLEEP_S
    assert reg.counter("compile.trace_s").get(fn="step") - was \
        >= TRACE_SLEEP_S
    assert got["first_dispatch_s"] >= (
        got["trace_s"] + got["lower_s"] + got["backend_s"])
    steps = {s["name"]: s for s in recorder.spans()
             if s["name"].startswith("compile.")
             and s["labels"]["fn"] == "step"}
    assert set(steps) == {"compile.trace", "compile.lower",
                          "compile.backend"}
    (s0, s1), = slept
    assert steps["compile.trace"]["t0_ns"] <= s0
    assert s1 <= steps["compile.trace"]["t1_ns"]
    assert steps["compile.lower"]["t0_ns"] >= s1
    assert steps["compile.backend"]["t0_ns"] >= s1


def test_later_steps_passes_and_calls_leave_the_first_dispatch_alone():
    t = _train(_trainer(width=6), sizes=(4,))
    first = _first_dispatch_gauges()
    assert first["first_dispatch_s"] > 0
    _train(t, sizes=(4, 4, 4), passes=2)     # more steps, a second pass
    assert _first_dispatch_gauges() == first
    _train(t, sizes=(2, 2))                  # a second call, and a recompile
    assert _first_dispatch_gauges() == first
    # a later lowering of the step (the harness asks memory_analysis())
    traced = om.get_registry().counter("compile.trace_s").get(fn="step")
    args = (t.params, t.opt_state, t.state, _FEED(_reader((3,))().__next__()),
            t.global_step, _rng.split_for_step(t.step_key, t.global_step))
    t.step_fn._step.lower(*args, *((1.0,) if t.step_fn.watchdog else ()))
    assert om.get_registry().counter("compile.trace_s").get(
        fn="step") > traced
    assert _first_dispatch_gauges() == first


def test_a_steady_state_recompile_names_itself(recorder):
    """Ten steps of one shape, then a batch of another: that step
    recompiles; where it is slow against the median its `slow_step` record
    says how much of it was compiling, and either way the watch counted one
    more program for `step`."""
    reg = om.get_registry()
    t = _trainer(width=7)
    _train(t, sizes=(4,) * 3)
    programs = reg.counter("compile.programs").get(fn="step")
    slow_before = len([e for e in recorder.snapshot()
                       if e.get("kind") == "slow_step"])
    _train(t, sizes=(4,) * 10 + (2,) + (4,) * 2)
    assert reg.counter("compile.programs").get(fn="step") == programs + 1
    slow = [e for e in recorder.snapshot()
            if e.get("kind") == "slow_step"][slow_before:]
    assert all("compile_s" in e for e in slow)
    mine = [e for e in slow if e["batch_id"] == 10]
    for e in mine:
        assert 0 < e["compile_s"] <= e["wall_s"]
    # a slow step that compiled nothing says so too
    for e in slow:
        if e["batch_id"] != 10:
            assert e["compile_s"] == 0


def test_compile_spans_hang_under_the_first_dispatch_and_build_under_its_root(
        recorder):
    t = _train(_trainer(width=8), sizes=(4, 4, 4))
    spans = [s for s in recorder.spans()]
    by_id = {s["span_id"]: s for s in spans}
    dispatches = sorted((s for s in spans if s["name"] == "train.dispatch"
                         and s["trace_id"] == t.last_trace_id),
                        key=lambda s: s["t0_ns"])
    first = dispatches[0]
    compiled = [s for s in spans if s["name"].startswith("compile.")
                and s["t1_ns"] >= first["t0_ns"]]
    assert {s["name"] for s in compiled if s["labels"]["fn"] == "step"} == {
        "compile.trace", "compile.lower", "compile.backend"}
    for s in compiled:
        # every compile of this call happened in its first dispatch
        assert s["parent_id"] == first["span_id"], s
        assert s["trace_id"] == first["trace_id"]
        assert first["t0_ns"] <= s["t0_ns"] <= s["t1_ns"] <= first["t1_ns"]
    for later in dispatches[1:]:
        assert not [s for s in spans if s["parent_id"] == later["span_id"]]
    # the build: one root, five children in the order of the build
    root = [s for s in spans if s["name"] == "trainer.build"][-1]
    kids = sorted((s for s in spans if s["parent_id"] == root["span_id"]
                   and s["name"].startswith("trainer.build.")),
                  key=lambda s: s["t0_ns"])
    assert [k["name"] for k in kids] == [
        "trainer.build." + p for p in PARTS]
    at = root["t0_ns"]
    for k in kids:
        assert at <= k["t0_ns"] <= k["t1_ns"] <= root["t1_ns"]
        at = k["t1_ns"]
    # what compiled while a part was being built hangs under that part
    for s in spans:
        if s["name"].startswith("compile.") and \
                root["t0_ns"] <= s["t0_ns"] and s["t1_ns"] <= root["t1_ns"]:
            assert by_id[s["parent_id"]]["name"].startswith("trainer.build")


def test_with_nothing_attached_no_event_is_made(monkeypatch):
    reg = om.get_registry()
    assert reg.stream is None and reg.recorder is None
    made = []
    monkeypatch.setattr(tracing, "emit_span",
                        lambda *a, **k: made.append(a))
    programs = reg.counter("compile.programs").get(fn="step")
    _train(_trainer(width=9), sizes=(4, 4))
    assert reg.counter("compile.programs").get(fn="step") == programs + 1
    assert made == []


def test_the_watch_counts_nested_stages_once():
    """Tracing `outer` traces `inner` inside it, an event of its own: the
    root's tracing seconds hold the sleep once, under the root's name."""
    reg = om.get_registry()

    @jax.jit
    def inner_of_the_watch_test(x):
        time.sleep(TRACE_SLEEP_S)
        return x * 2

    def outer_of_the_watch_test(x):
        return inner_of_the_watch_test(x) + 1

    t0 = time.monotonic_ns()
    jax.jit(outer_of_the_watch_test)(np.ones(3, np.float32))
    t1 = time.monotonic_ns()
    spent = compile_cache.spent(t0, t1)
    assert spent["trace_s"] >= TRACE_SLEEP_S
    assert (spent["trace_s"] + spent["lower_s"] + spent["backend_s"]
            <= (t1 - t0) * 1e-9)
    assert reg.counter("compile.trace_s").get(
        fn="outer_of_the_watch_test") >= TRACE_SLEEP_S
    assert reg.counter("compile.trace_s").get(
        fn="inner_of_the_watch_test") == 0
    assert reg.counter("compile.programs").get(
        fn="outer_of_the_watch_test") == 1
    # another thread's stretch, or another time's, holds nothing of it
    assert compile_cache.spent(t1 + 1, t1 + 2) == dict.fromkeys(
        compile_cache.SPENT, 0)


def test_a_root_under_the_floor_is_counted_and_not_spanned(recorder):
    """JAX sends a trace event of microseconds where it only found the
    jaxpr in a cache (the eager fold of every step's rng key): the seconds
    are counted, the ring is left to what took time."""
    event = "/jax/core/compile/jaxpr_trace_duration"
    counted = om.get_registry().counter("compile.trace_s")
    for secs in (compile_cache.SPAN_FLOOR_S / 100,
                 compile_cache.SPAN_FLOOR_S * 2):
        compile_cache._on_start(event, 0.0, fun_name="a_fold_of_the_key")
        compile_cache._on_duration(event, secs, fun_name="a_fold_of_the_key")
    assert counted.get(fn="a_fold_of_the_key") == pytest.approx(
        compile_cache.SPAN_FLOOR_S * 2.01)
    mine = [s for s in recorder.spans()
            if s["labels"].get("fn") == "a_fold_of_the_key"]
    assert [(s["name"], s["dur_s"]) for s in mine] == [
        ("compile.trace", compile_cache.SPAN_FLOOR_S * 2)]


def test_one_setup_event_a_trainer_on_the_stream(tmp_path):
    path = str(tmp_path / "events.jsonl")
    om.enable_event_stream(path)
    try:
        t = _train(_trainer(width=10), sizes=(4, 4), passes=2)
        _train(t, sizes=(4,))
    finally:
        om.get_registry().attach_stream(None)
    from paddle_tpu.testing_faults import read_metrics_records

    (setup,) = read_metrics_records(path, kind="setup")
    assert set(setup["build_s"]) == set(PARTS) | {"all"}
    assert setup["start_to_build_s"] > 0
    got = _first_dispatch_gauges()
    assert setup["first_dispatch_s"] == round(got["first_dispatch_s"], 6)
    for key in FIRST:
        assert setup[key] == round(got[key], 6)
    # without a stream the same numbers are the registry's text
    text = om.get_registry().render_text()
    for name in ("process.start_to_build_s", "trainer.build_s{part=all}",
                 "trainer.first_dispatch_s",
                 "trainer.first_dispatch.trace_s"):
        assert name in text


def _blocked_jax(tmp_path):
    for mod in ("jax", "jaxlib"):
        (tmp_path / f"{mod}.py").write_text(
            "raise ImportError('jax blocked for this test')\n")
    return dict(os.environ, PYTHONPATH=str(tmp_path) + os.pathsep + REPO)


def test_the_cli_prints_the_last_setup_with_jax_blocked(tmp_path):
    path = str(tmp_path / "events.jsonl")
    s = om.EventStream(path, flush_interval_s=3600)
    s.emit({"kind": "setup", "start_to_build_s": 1.0, "build_s": {"all": 9.0},
            "first_dispatch_s": 1.0})
    s.emit({"kind": "timeline", "pass_id": 0, "global_step": 3})
    s.emit({"kind": "setup", "start_to_build_s": 12.5,
            "build_s": {"all": 3.25, "place": 2.0}, "first_dispatch_s": 7.5,
            "trace_s": 2.5, "lower_s": 0.75, "backend_s": 3.0,
            "cache_hits": 19, "cache_misses": 1})
    s.close()
    env = _blocked_jax(tmp_path)
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu", "metrics", "--stream", path],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "last timeline: pass 0 step 3" in r.stdout
    assert ("last setup: before the build 12.50 s  build 3.25 s  first "
            "dispatch 7.50 s (trace 2.50 lower 0.75 compile or load 3.00; "
            "cache 19 hits 1 misses)") in r.stdout
    rj = subprocess.run(
        [sys.executable, "-m", "paddle_tpu", "metrics", "--stream", path,
         "--json"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    doc = json.loads(rj.stdout)
    assert doc["last_setup"]["build_s"] == {"all": 3.25, "place": 2.0}
    assert doc["last_setup"]["cache_misses"] == 1
    assert doc["by_kind"]["setup"] == 2


def test_obs_and_the_watchs_module_import_with_jax_blocked(tmp_path):
    code = ("import sys\n"
            "import paddle_tpu.obs\n"
            "from paddle_tpu.obs import timeline\n"
            "from paddle_tpu.core import compile_cache\n"
            "assert timeline.process_age_s() > 0\n"
            "assert compile_cache.spent(0, 1)['trace_s'] == 0\n"
            "assert not [m for m in sys.modules\n"
            "            if m.split('.')[0] in ('jax', 'jaxlib')]\n"
            "print('ok')\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       env=_blocked_jax(tmp_path), capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == "ok"
