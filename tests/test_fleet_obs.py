"""Fleet observability plane (ISSUE 17): router-side acceptance.

The process-level half, on CPU throughout:

- satellite 1: a replica the poller cannot scrape is COUNTED
  (`fleet.scrape_errors{replica=}`), charges the same breaker that
  transport failures charge (N consecutive failed scrapes rotate it
  out), and past the threshold its stale telemetry is discarded so a
  dead replica cannot keep looking cheap on its last queue depth.
- satellite 2: admin frames (metricz/tracez/flightz) carry their own
  bounded timeout, independent of the long request-socket timeout —
  a black-holed replica cannot hang the poller.
- the `flightz` TCP frame: ring dump answered outside the admission
  queue, shaped for the incident stitch.
- rollout observability: `rollout()` returns a structured
  RolloutReport and emits per-phase events into the flight ring.
- the E2E headline: a 2-replica fleet with one replica in SLO breach
  produces EXACTLY ONE rate-limited `paddle-tpu-fleet-incident/v1`
  bundle that passes the bundle lint, names the offending replica,
  and stitches rings such that `tools/fleet_view.py` extracts a
  cross-process critical path.
- the jax-free `python -m paddle_tpu fleetz` operator surface.
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from paddle_tpu import testing_faults  # noqa: E402
from paddle_tpu.obs import aggregate as agg  # noqa: E402
from paddle_tpu.obs import flight_recorder as fr  # noqa: E402
from paddle_tpu.obs import metrics as om  # noqa: E402
from paddle_tpu.serving.fleet import (  # noqa: E402
    FleetConfig,
    FleetRouter,
    RolloutReport,
)
from paddle_tpu.serving.server import (  # noqa: E402
    InferenceServer,
    ServeConfig,
)
from paddle_tpu.serving.tcp import (  # noqa: E402
    ServeClient,
    ServingTCPServer,
)

import fleet_view  # noqa: E402


class ToyModel:
    can_host = False
    engine = None
    named_hooks = {}

    def __init__(self, delay_s=0.005, tag="v1"):
        self.delay_s = delay_s
        self.tag = tag

    def run_batch(self, ids, lens, hooks, host):
        time.sleep(self.delay_s)
        return [
            {"tokens": [int(lens[i])], "score": 0.0, "tag": self.tag}
            for i in range(ids.shape[0])
        ]


class _Replica:
    def __init__(self, delay_s=0.005, max_queue=32, max_batch=4,
                 tag="v1"):
        self.srv = InferenceServer(ServeConfig(
            max_queue=max_queue, max_batch=max_batch,
            default_deadline_s=30.0))
        self.srv.add_model("m", ToyModel(delay_s, tag=tag))

        def load_model(name, new_tag):
            return ToyModel(delay_s, tag=new_tag or "swapped")

        self.tcp = ServingTCPServer(self.srv, model_loader=load_model)
        self.addr = f"127.0.0.1:{self.tcp.port}"

    def close(self):
        self.tcp.stop()
        self.srv.shutdown(drain=False)


def _counter_total(family):
    return agg.family_total(
        om.get_registry().snapshot()["counters"], family)


# ================================================ satellite 1: scrapes
class TestScrapeFailuresFeedBreaker:
    def test_scrape_failures_counted_and_rotate_replica_out(self):
        """No request traffic at all: consecutive FAILED SCRAPES
        alone must open the breaker, count per-replica, and poison
        the stale cost."""
        rep = _Replica()
        before = _counter_total("fleet.scrape_errors")
        cfg = FleetConfig(poll_interval_s=0.03, breaker_threshold=3,
                          breaker_reset_s=30.0, monitor=False)
        router = FleetRouter({"r0": rep.addr}, cfg)
        try:
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                if router.states()["r0"]["breaker"] == "closed" \
                        and router.handle("r0").telemetry:
                    break
                time.sleep(0.01)
            assert router.states()["r0"]["breaker"] == "closed"
            rep.close()  # now every scrape fails
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                st = router.states()["r0"]
                if st["breaker"] != "closed" and st["stale"]:
                    break
                time.sleep(0.01)
            st = router.states()["r0"]
            assert st["breaker"] != "closed"
            assert st["scrape_failures"] >= cfg.breaker_threshold
            assert st["stale"] is True
            h = router.handle("r0")
            assert h.telemetry == {} and h.metricz == {}
            assert h.cost() >= 1e6  # poisoned to the back of the order
            assert (_counter_total("fleet.scrape_errors") - before
                    >= cfg.breaker_threshold)
        finally:
            router.close()

    def test_successful_scrape_resets_consecutive_count(self):
        rep = _Replica()
        router = FleetRouter(
            {"r0": rep.addr},
            FleetConfig(poll_interval_s=0.03, monitor=False))
        try:
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                if router.handle("r0").metricz:
                    break
                time.sleep(0.01)
            h = router.handle("r0")
            assert h.scrape_failures == 0 and h.stale is False
            # the scraped snapshot is a full registry snapshot —
            # merge-ready, not just the stats dict
            assert "histograms" in h.metricz
        finally:
            router.close()
            rep.close()


# ============================================ satellite 2: admin frames
class TestAdminFrameTimeout:
    def test_black_holed_metricz_fails_within_admin_timeout(self):
        """A replica that accepts but never answers must not hang an
        admin scrape for the full request timeout: admin frames get
        their own bounded deadline."""
        rep = _Replica()
        host, port = rep.addr.split(":")
        proxy = testing_faults.FlakyProxy((host, int(port)))
        try:
            proxy.black_hole()
            c = ServeClient(f"127.0.0.1:{proxy.port}", retries=0,
                            admin_timeout=0.3)
            for frame in (c.metricz, c.tracez, c.flightz):
                t0 = time.monotonic()
                with pytest.raises(OSError):
                    frame()
                assert time.monotonic() - t0 < 2.0
            c.close()
            # per-call override narrows it further
            c = ServeClient(f"127.0.0.1:{proxy.port}", retries=0)
            t0 = time.monotonic()
            with pytest.raises(OSError):
                c.metricz(timeout=0.2)
            assert time.monotonic() - t0 < 1.5
            c.close()
        finally:
            proxy.close()
            rep.close()

    def test_healthy_admin_frames_still_answer(self):
        rep = _Replica()
        try:
            with ServeClient(rep.addr, admin_timeout=2.0) as c:
                assert c.metricz()["ok"]
                assert c.tracez()["ok"]
                assert c.flightz()["ok"]
        finally:
            rep.close()


# ==================================================== flightz frame
class TestFlightzFrame:
    def test_flightz_without_recorder(self):
        rep = _Replica()
        try:
            with ServeClient(rep.addr) as c:
                fz = c.flightz()["flightz"]
            assert fz["enabled"] is False
            assert fz["events"] == [] and fz["capacity"] == 0
            assert fz["pid"] == os.getpid()  # in-process replica
        finally:
            rep.close()

    def test_flightz_dumps_the_ring(self):
        rep = _Replica()
        rec = fr.enable_flight_recorder(dump_dir=None, capacity=32)
        try:
            rec.record({"kind": "note", "msg": "hello"})
            with ServeClient(rep.addr) as c:
                fz = c.flightz()["flightz"]
            assert fz["enabled"] is True and fz["capacity"] == 32
            assert any(e.get("kind") == "note" for e in fz["events"])
        finally:
            fr.disable_flight_recorder()
            rep.close()


# ==================================================== rollout report
class TestRolloutObservability:
    def test_rollout_report_and_phase_events(self):
        reps = [_Replica(delay_s=0.002), _Replica(delay_s=0.002)]
        router = FleetRouter(
            {"r0": reps[0].addr, "r1": reps[1].addr},
            FleetConfig(poll_interval_s=0.05, monitor=False))
        rec = fr.enable_flight_recorder(dump_dir=None, capacity=256)
        try:
            time.sleep(0.12)
            rep = router.rollout("m", tag="v2")
            assert isinstance(rep, RolloutReport)
            assert rep.ok and rep.model == "m" and rep.tag == "v2"
            assert rep.duration_s > 0
            # mapping-style access still reads per-replica responses
            assert set(rep.keys()) == {"r0", "r1"}
            assert all(r["ok"] for r in rep.values())
            assert rep["r0"]["swapped"] == "m"
            # the phase timeline: each replica walks
            # drain_begin -> drain_end -> swap -> undrain, in order
            for name in ("r0", "r1"):
                seq = [p["phase"] for p in rep.phases
                       if p["replica"] == name]
                assert seq == ["drain_begin", "drain_end", "swap",
                               "undrain"], seq
                pr = rep.per_replica[name]
                assert pr["drain_s"] >= 0 and pr["swap_s"] > 0
                assert pr["total_s"] >= pr["swap_s"]
            # phases carry durations where the ISSUE asks for them
            by = {(p["phase"], p["replica"]): p for p in rep.phases}
            assert "dur_s" in by[("drain_end", "r0")]
            assert by[("swap", "r1")]["tag"] == "v2"
            # ...and were emitted as events into the flight ring AS
            # THEY HAPPENED, not reconstructed after the fact
            kinds = [e for e in rec.snapshot()
                     if e.get("kind") == "rollout"]
            assert len(kinds) >= 8
            assert {e["phase"] for e in kinds} == {
                "drain_begin", "drain_end", "swap", "undrain"}
        finally:
            fr.disable_flight_recorder()
            router.close()
            for r in reps:
                r.close()

    def test_failed_rollout_still_undrains(self):
        rep = _Replica()
        router = FleetRouter(
            {"r0": rep.addr},
            FleetConfig(poll_interval_s=0.05, monitor=False))
        rec = fr.enable_flight_recorder(dump_dir=None, capacity=64)
        try:
            with pytest.raises(RuntimeError, match="refused"):
                router.rollout("ghost")
            assert router.states()["r0"]["draining"] is False
            evs = [e for e in rec.snapshot()
                   if e.get("kind") == "rollout"]
            assert any(e["phase"] == "swap_failed" for e in evs)
            assert any(e["phase"] == "undrain" for e in evs)
        finally:
            fr.disable_flight_recorder()
            router.close()
            rep.close()


# ==================================================== E2E incident
@pytest.mark.faults
class TestFleetIncidentE2E:
    def test_slo_breach_writes_one_stitched_bundle(self, tmp_path):
        """The acceptance headline: a 2-replica fleet where one
        replica breaches the p99 SLO. The burn monitor must fire,
        write EXACTLY ONE rate-limited incident bundle naming the
        slow replica, the bundle must pass the bundle lint, and
        `tools/fleet_view.py` must extract a critical path whose
        spans come from more than one process."""
        incident_dir = str(tmp_path / "incidents")
        procs, addrs = {}, {}
        for name, delay in (("slow", 0.3), ("fast", 0.004)):
            p, port = testing_faults.start_serving_replica(
                REPO, REPLICA_MODE="toy", TOY_DELAY_S=delay,
                MODEL_TAG="v1")
            assert port is not None, p.boot_line
            procs[name] = p
            addrs[name] = f"127.0.0.1:{port}"
        cfg = FleetConfig(
            poll_interval_s=0.05,
            monitor=True,
            slo_p99_ms=100.0,
            burn_windows=((0.9, 2.7, 14.4),),
            burn_min_decisions=20,
            incident_dir=incident_dir,
            incident_min_interval_s=3600.0,  # one bundle, full stop
            incident_max_bundles=4,
        )
        # the router's own ring: the "router half" of the stitch
        fr.enable_flight_recorder(dump_dir=None, capacity=512)
        router = FleetRouter(dict(addrs), cfg)
        try:
            time.sleep(0.15)
            stop = threading.Event()

            def load():
                while not stop.is_set():
                    try:
                        router.call("m", [1, 2], deadline_ms=20000,
                                    trace=True)
                    except Exception:  # noqa: BLE001
                        pass

            workers = [threading.Thread(target=load, daemon=True)
                       for _ in range(3)]
            for w in workers:
                w.start()
            deadline = time.monotonic() + 25
            while time.monotonic() < deadline:
                if os.path.isdir(incident_dir) \
                        and os.listdir(incident_dir):
                    break
                time.sleep(0.05)
            # keep burning a little: the rate limit, not alert
            # clearance, is what must hold the count at one
            time.sleep(0.5)
            stop.set()
            for w in workers:
                w.join(10)
            files = [f for f in os.listdir(incident_dir)
                     if f.startswith("incident-")
                     and f.endswith(".json")]
            assert len(files) == 1, files
            path = os.path.join(incident_dir, files[0])

            # the bundle validates against the bundle lint
            assert fr.check_bundle(path) == []

            with open(path) as f:
                doc = json.load(f)
            assert doc["schema"] == "paddle-tpu-fleet-incident/v1"
            assert doc["reason"] == "burn_rate"
            # the alert that fired is the p99 SLO breach, and the
            # bundle names the replica that caused it
            assert any(a["alert"] == "p99_slo" for a in doc["alerts"])
            assert doc["offending"] == "slow"
            # the cross-process stitch: both replica rings present
            # with span events gathered over flightz
            assert set(doc["replicas"]) == {"slow", "fast"}
            for name in ("slow", "fast"):
                ring = doc["replicas"][name]
                assert ring.get("enabled") is True, ring
                assert ring["pid"] != os.getpid()
                assert any(e.get("kind") == "span"
                           for e in ring["events"])
            # the merged fleet view rode along
            assert "serving.admitted_latency_s" in str(
                doc["fleet"]["merged"]["histograms"].keys())
            assert doc["history"], "scrape history missing"

            # the monitor's own accounting
            mon = router.monitor
            assert mon.last_incident_path == path
            assert mon.burn.alerts_total >= 1
            assert mon.state()["burn"]["alerts_total"] >= 1
            # the storm was rate-limited, not absent
            assert _counter_total("fleet.incidents_suppressed") >= 1

            # fleet_view extracts a critical path spanning processes
            report = fleet_view.analyze(path, top=5)
            assert report["schema"] == "paddle-tpu-fleet-incident/v1"
            assert report["offending"] == "slow"
            cross = [t for t in report["traces"]
                     if t["cross_process"]]
            assert cross, report["traces"][:3]
            best = cross[0]
            assert len(best["processes"]) >= 2
            assert "router" in best["processes"]
            assert best["critical_path"], best
            # rendering never crashes on a real bundle
            text = fleet_view.render(report)
            assert "cross-process" in text
            assert "offending=slow" in text
        finally:
            fr.disable_flight_recorder()
            router.close()
            for p in procs.values():
                testing_faults.kill_process(p)


# ==================================================== fleetz CLI
class TestFleetzCLI:
    def _run(self, argv, env=None):
        return subprocess.run(
            [sys.executable, "-m", "paddle_tpu", "fleetz"] + argv,
            cwd=REPO, env=env or dict(os.environ),
            capture_output=True, text=True, timeout=120)

    def test_fleetz_jax_free_json(self, tmp_path):
        """The operator surface: scrape a live fleet twice from a
        process in which jax CANNOT be imported, and report merged
        health per replica + fleet quantiles."""
        reps = [_Replica(delay_s=0.002), _Replica(delay_s=0.002)]
        try:
            # traffic between the CLI's two scrapes so the delta
            # carries admitted counts and latency buckets
            stop = threading.Event()

            def drive():
                with ServeClient(reps[0].addr) as c0, \
                        ServeClient(reps[1].addr) as c1:
                    while not stop.is_set():
                        c0.call("m", [1], deadline_ms=5000)
                        c1.call("m", [1], deadline_ms=5000)
            t = threading.Thread(target=drive, daemon=True)
            t.start()
            blocker = tmp_path / "jax.py"
            blocker.write_text(
                "raise ImportError('jax blocked for this test')\n")
            env = dict(os.environ,
                       PYTHONPATH=str(tmp_path) + os.pathsep + REPO)
            r = self._run(
                ["--addr", f"a={reps[0].addr}",
                 "--addr", f"b={reps[1].addr}",
                 "--interval", "0.4", "--json"], env=env)
            stop.set()
            t.join(10)
            assert r.returncode == 0, r.stderr
            doc = json.loads(r.stdout)
            assert doc["fleet"]["replicas_up"] == 2
            assert doc["fleet"]["admitted_rate_rps"] > 0
            assert doc["fleet"]["p99_ms"] is not None
            rows = {x["replica"]: x for x in doc["replicas"]}
            assert rows["a"]["up"] and rows["b"]["up"]
            assert rows["a"]["admitted"] > 0
            assert doc["alerts"] == []
        finally:
            for rep in reps:
                rep.close()

    def test_fleetz_flags_down_replica_nonzero_exit(self):
        rep = _Replica()
        dead = "127.0.0.1:1"  # nothing listens on port 1
        try:
            r = self._run(["--addr", f"up={rep.addr}",
                           "--addr", f"down={dead}",
                           "--interval", "0.05", "--timeout", "0.5",
                           "--json"])
            assert r.returncode == 1, r.stdout + r.stderr
            doc = json.loads(r.stdout)
            assert {"alert": "replica_down", "replica": "down"} \
                in doc["alerts"]
            assert doc["fleet"]["replicas_down"] == 1
        finally:
            rep.close()
