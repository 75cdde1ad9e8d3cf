"""Unified telemetry (ISSUE 10): the metrics registry, the JSONL
event stream, the StatSet adapter, the trainer step timeline, the
serving `metricz` scrape, and the obs import-hygiene lint."""

import json
import os
import re
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from paddle_tpu.obs import metrics as om
from paddle_tpu.obs.timeline import StepTimeline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ===================================================== registry core
class TestCounters:
    def test_concurrent_increments_sum_exactly(self):
        """N threads x M increments lose nothing: the registry's
        whole point is being safe to call from the serving workers,
        the TCP handlers, and the training thread at once."""
        reg = om.MetricsRegistry()
        c = reg.counter("t.hits")
        N, M = 8, 10_000

        def worker():
            for _ in range(M):
                c.inc()

        threads = [threading.Thread(target=worker) for _ in range(N)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.get() == N * M

    def test_labeled_series_are_independent(self):
        reg = om.MetricsRegistry()
        c = reg.counter("t.shed")
        c.inc(reason="overloaded")
        c.inc(2, reason="deadline")
        assert c.get(reason="overloaded") == 1
        assert c.get(reason="deadline") == 2
        assert c.get(reason="quarantined") == 0
        snap = reg.snapshot()["counters"]
        assert snap["t.shed{reason=deadline}"] == 2

    def test_kind_conflict_raises(self):
        reg = om.MetricsRegistry()
        reg.counter("x")
        with pytest.raises(ValueError):
            reg.gauge("x")

    def test_gauge_set_max_keeps_high_water(self):
        reg = om.MetricsRegistry()
        g = reg.gauge("t.depth_hwm")
        for v in (3, 9, 5):
            g.set_max(v)
        assert g.get() == 9


class TestHistogram:
    def test_bucket_boundaries_are_upper_inclusive(self):
        """An observation EQUAL to a boundary lands in that
        boundary's bucket ("le" semantics); above the last bound goes
        to +inf."""
        reg = om.MetricsRegistry()
        h = reg.histogram("t.lat", buckets=(1.0, 2.0, 5.0))
        for v in (0.5, 1.0, 1.5, 2.0, 3.0, 5.0, 7.0):
            h.observe(v)
        assert h.buckets() == {
            "<=1": 2, "<=2": 2, "<=5": 2, "+inf": 1,
        }
        assert h.count() == 7
        assert h.min() == 0.5 and h.max() == 7.0
        assert abs(h.sum() - 20.0) < 1e-9

    def test_concurrent_observes_count_exactly(self):
        reg = om.MetricsRegistry()
        h = reg.histogram("t.conc", buckets=(0.5,))
        N, M = 6, 5000

        def worker():
            for _ in range(M):
                h.observe(0.25)

        threads = [threading.Thread(target=worker) for _ in range(N)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert h.count() == N * M
        assert h.buckets()["<=0.5"] == N * M

    def test_reset_prefix_zeroes_in_place(self):
        reg = om.MetricsRegistry()
        h = reg.histogram("stat.g.step")
        h.observe(1.0)
        reg.reset_prefix("stat.g.")
        assert h.count() == 0
        h.observe(2.0)  # held reference keeps working post-reset
        assert h.count() == 1


# ==================================================== event stream
class TestEventStream:
    def test_writes_parseable_jsonl(self, tmp_path):
        path = str(tmp_path / "ev.jsonl")
        s = om.EventStream(path, flush_interval_s=30)
        s.emit({"kind": "watchdog", "event": "skip", "global_step": 7})
        s.emit({"kind": "timeline", "pass_id": 0})
        s.close()
        recs = [json.loads(ln) for ln in open(path)]
        assert [r["kind"] for r in recs] == ["watchdog", "timeline"]
        assert recs[0]["global_step"] == 7
        assert all("ts" in r for r in recs)

    def test_rotation_keeps_one_previous_generation(self, tmp_path):
        path = str(tmp_path / "rot.jsonl")
        s = om.EventStream(path, flush_interval_s=30, rotate_bytes=256)
        for i in range(50):
            s.emit({"kind": "k", "i": i, "pad": "x" * 40})
            if i % 5 == 4:
                s.flush()
        s.close()
        assert os.path.exists(path + ".1")
        assert os.path.getsize(path) <= 256 + 4096  # one batch over
        # both generations parse, and the newest file holds the tail
        tail = [json.loads(ln) for ln in open(path)]
        assert tail[-1]["i"] == 49

    def test_flush_at_exit_without_close(self, tmp_path):
        """A process that enables the stream, emits, and exits
        WITHOUT closing still leaves a complete stream (the atexit
        drain) — the preemptible-worker contract."""
        path = str(tmp_path / "exit.jsonl")
        code = (
            "from paddle_tpu.obs import metrics as om\n"
            f"om.enable_event_stream({path!r}, flush_interval_s=60)\n"
            "om.get_registry().event('watchdog', event='skip',"
            " global_step=3)\n"
            "om.get_registry().event('timeline', pass_id=1)\n"
        )
        r = subprocess.run(
            [sys.executable, "-c", code], cwd=REPO,
            capture_output=True, text=True, timeout=120,
        )
        assert r.returncode == 0, r.stderr
        recs = [json.loads(ln) for ln in open(path)]
        assert len(recs) == 2
        assert recs[0]["event"] == "skip"

    def test_registry_event_noop_without_stream(self):
        reg = om.MetricsRegistry()
        reg.event("watchdog", event="skip")  # must not raise

    def test_shared_reader_filters(self, tmp_path):
        from paddle_tpu.testing_faults import read_metrics_records

        path = str(tmp_path / "mf.jsonl")
        s = om.EventStream(path, flush_interval_s=30)
        s.emit({"kind": "watchdog", "event": "skip", "global_step": 1})
        s.emit({"kind": "watchdog", "event": "rollback",
                "global_step": 2})
        s.emit({"kind": "timeline", "pass_id": 0})
        s.close()
        assert len(read_metrics_records(path)) == 3
        assert len(read_metrics_records(path, kind="watchdog")) == 2
        skips = read_metrics_records(path, kind="watchdog",
                                     event="skip")
        assert [e["global_step"] for e in skips] == [1]


# ================================================== StatSet adapter
class TestStatSetAdapter:
    def test_report_text_format_unchanged(self):
        from paddle_tpu.core.stat import StatSet

        reg = om.MetricsRegistry()
        ss = StatSet("fmt", registry=reg)
        with ss.timer("train_step"):
            time.sleep(0.002)
        rep = ss.report()
        assert rep.splitlines()[0] == "=== StatSet[fmt] ==="
        assert re.search(
            r"train_step\s+count=\s+1 total=\s*\d+\.\d{4}s "
            r"avg=\s*\d+\.\d{3}ms max=\s*\d+\.\d{3}ms", rep
        ), rep

    def test_no_duplicate_plumbing_same_numbers(self):
        """StatInfo is a VIEW: the registry histogram and the StatSet
        report read the same state."""
        from paddle_tpu.core.stat import StatSet

        reg = om.MetricsRegistry()
        ss = StatSet("v", registry=reg)
        st = ss.stat("x")
        st.add(0.5)
        st.add(1.5)
        assert st.count == 2 and abs(st.total - 2.0) < 1e-9
        assert st.max == 1.5 and st.min == 0.5 and st.avg == 1.0
        h = reg.histogram("stat.v.x")
        assert h.count() == 2 and abs(h.sum() - 2.0) < 1e-9

    def test_reset_clears_per_pass(self):
        from paddle_tpu.core.stat import StatSet

        reg = om.MetricsRegistry()
        ss = StatSet("r", registry=reg)
        with ss.timer("fwd_conv"):
            pass
        ss.reset()
        assert "fwd_conv" not in ss.report()
        with ss.timer("fwd_conv"):  # reusable after reset
            pass
        assert ss.stat("fwd_conv").count == 1


# ============================================== trainer integration
class TestTrainerTimeline:
    def _train(self, tmp_path, stream=None):
        from paddle_tpu import dsl
        from paddle_tpu.core.config import OptimizationConf
        from paddle_tpu.data import reader as R
        from paddle_tpu.data.feeder import (
            DataFeeder,
            dense_vector,
            integer_value,
        )
        from paddle_tpu.trainer import SGD

        with dsl.model() as g:
            x = dsl.data("x", (4,))
            y = dsl.data("y", (1,), is_ids=True)
            o = dsl.fc(x, size=3, name="output")
            dsl.classification_cost(o, y)
        rng = np.random.default_rng(0)
        xs = rng.standard_normal((24, 4)).astype(np.float32)
        ys = np.argmax(xs[:, :3], axis=1).astype(np.int64)
        data = [(xs[i], int(ys[i])) for i in range(24)]

        def reader():
            yield from data

        feeder = DataFeeder(
            {"x": 0, "y": 1},
            {"x": dense_vector(4), "y": integer_value(3)},
        )
        t = SGD(g.conf, OptimizationConf(
            learning_method="sgd", learning_rate=0.1), seed=3)
        t.train(reader=R.batched(reader, 4), feeder=feeder,
                num_passes=2)
        return t

    def test_timeline_fractions_and_counters(self, tmp_path):
        t = self._train(tmp_path)
        tl = t.last_timeline
        assert tl.steps == 12
        fr = tl.fractions()
        for k in ("data_wait_frac", "host_overhead_frac",
                  "device_frac", "checkpoint_stall_frac"):
            assert 0.0 <= fr[k] <= 1.0
        assert sum(fr.values()) == pytest.approx(1.0, abs=0.01)
        # mirrored into the process registry
        reg = om.get_registry()
        assert reg.counter("trainer.steps").get() >= 12
        assert reg.counter("trainer.host_dispatch_s").get() > 0

    def test_timeline_event_per_pass_on_stream(self, tmp_path):
        path = str(tmp_path / "tl.jsonl")
        om.enable_event_stream(path, flush_interval_s=30)
        try:
            self._train(tmp_path)
            om.get_registry().stream.flush()
            recs = [json.loads(ln) for ln in open(path)
                    if ln.strip()]
            tls = [r for r in recs if r["kind"] == "timeline"]
            assert [r["pass_id"] for r in tls[-2:]] == [0, 1]
            assert tls[-1]["global_step"] == 12
            assert "device_frac" in tls[-1]
        finally:
            om.get_registry().attach_stream(None)


class TestStepTimelineUnit:
    def test_fence_sampling(self):
        tl = StepTimeline(sample_period=4,
                          registry=om.MetricsRegistry())
        fences = [tl.fence_now(i) for i in range(1, 9)]
        assert fences == [False, False, False, True] * 2
        assert StepTimeline(
            sample_period=0, registry=om.MetricsRegistry()
        ).fence_now(4) is False

    def test_fractions_empty_are_zero(self):
        tl = StepTimeline(registry=om.MetricsRegistry())
        assert set(tl.fractions().values()) == {0.0}


# ============================================ serving metricz scrape
class _EchoModel:
    can_host = False
    engine = None
    named_hooks = {}

    def run_batch(self, ids, lens, hooks, host):
        return [
            {"tokens": ids[i, : lens[i]].tolist(), "score": 0.0}
            for i in range(ids.shape[0])
        ]


class TestServingMetricz:
    def test_metricz_over_tcp(self):
        from paddle_tpu.serving.server import (
            InferenceServer,
            ServeConfig,
        )
        from paddle_tpu.serving.tcp import ServeClient, ServingTCPServer

        server = InferenceServer(ServeConfig(max_queue=8, max_batch=2))
        server.add_model("echo", _EchoModel())
        tcp = ServingTCPServer(server)
        try:
            with ServeClient(f"127.0.0.1:{tcp.port}") as cl:
                out = cl.call("echo", [3, 4, 5], timeout=30)
                assert out["ok"], out
                m = cl.metricz(timeout=30)
            assert m["ok"]
            counters = m["metricz"]["counters"]
            assert counters.get("serving.admitted{model=echo}", 0) >= 1
            assert counters.get("serving.batches{model=echo}", 0) >= 1
            gauges = m["metricz"]["gauges"]
            assert gauges.get("serving.queue_depth_hwm", 0) >= 1
            # admitted-latency histogram present
            hists = m["metricz"]["histograms"]
            assert any(
                k.startswith("serving.admitted_latency_s")
                for k in hists
            )
            # server-side stats ride along
            assert m["stats"]["completed"] >= 1
        finally:
            tcp.stop()
            server.shutdown(drain=True)


# ============================================ master-client counters
class TestMasterClientCounters:
    def test_retry_and_deadline_counters(self):
        from paddle_tpu.data.master_client import (
            MasterClient,
            MasterRetryTimeout,
        )

        def totals():
            snap = om.get_registry().snapshot()["counters"]
            return (
                sum(v for k, v in snap.items()
                    if k.startswith("master_client.retries")),
                sum(v for k, v in snap.items()
                    if k.startswith("master_client.retry_timeouts")),
            )

        r0, t0 = totals()
        # a port nothing listens on: every attempt fails fast
        c = MasterClient("127.0.0.1:1", retry_seconds=0.3,
                         connect_timeout=0.2)
        with pytest.raises(MasterRetryTimeout):
            c.start_pass()
        r1, t1 = totals()
        assert r1 > r0 and t1 > t0


# =================================================== import hygiene
# (the lint itself: test_framework_lint.py::TestAstPasses, the fence)
class TestObsImportHygiene:
    def test_obs_importable_without_jax(self):
        """The registry imports (and the CLI metrics path runs) in a
        process where jax is BLOCKED — the serving-front-end /
        data-worker guarantee the lint protects."""
        code = (
            "import sys\n"
            "sys.modules['jax'] = None\n"  # any import attempt dies
            "import paddle_tpu.obs\n"
            "from paddle_tpu.obs import metrics, timeline\n"
            "from paddle_tpu.obs import tracing, flight_recorder\n"
            "from paddle_tpu.core import stat\n"
            "from paddle_tpu.trainer import watchdog\n"
            "r = metrics.get_registry()\n"
            "r.counter('ok').inc()\n"
            "rec = flight_recorder.FlightRecorder(registry=r)\n"
            "r.attach_recorder(rec)\n"
            "with tracing.span('no-jax', registry=r):\n"
            "    pass\n"
            "assert rec.spans()[0]['name'] == 'no-jax'\n"
            "print('OK', r.counter('ok').get())\n"
        )
        r = subprocess.run(
            [sys.executable, "-c", code], cwd=REPO,
            capture_output=True, text=True, timeout=120,
        )
        assert r.returncode == 0, r.stderr
        assert "OK 1" in r.stdout
