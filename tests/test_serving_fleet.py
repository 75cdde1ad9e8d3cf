"""Serving fleet tier (ISSUE 16): router, verified AOT cache, drain.

The acceptance surface of the fleet PR, on CPU throughout:

- `_Breaker` probe races: two threads in half-open admit exactly one
  probe; a failed probe re-opens with the backoff window reset.
- The export envelope v2: version byte, typed `CompiledArtifactError`
  on truncation, and `testing_faults.corrupt_file` at several offsets
  with every corruption detected BEFORE anything reaches XLA.
- The verified cache: store/load round trip on the fast executable
  path, digest and audit-policy gates refusing tampered or
  policy-violating entries, and SIGKILL-mid-store leaving no
  half-visible entry (atomic rename publish).
- `ServeClient` connect retry riding over a replica restart, with
  `retries=0` preserving fail-fast.
- `ServingTCPServer.stop(drain=True)` landing in-flight responses.
- The `FleetRouter` (in-process replicas): spill-before-shed when one
  replica is overloaded, and a zero-downtime rollout a polling client
  cannot see.
- faults tier (subprocess replicas): SIGKILL one of three replicas
  under load with zero admitted requests lost, breaker rotation
  within the reset window, and a restarted replica booting from the
  verified cache and rejoining rotation via the half-open probe; the
  boot gate refusing corrupt/policy-violating cache entries.
"""

import json
import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from paddle_tpu import inference, testing_faults  # noqa: E402
from paddle_tpu.serving.fleet import (  # noqa: E402
    FleetConfig,
    FleetRouter,
)
from paddle_tpu.serving.server import (  # noqa: E402
    InferenceServer,
    ServeConfig,
    _Breaker,
)
from paddle_tpu.serving.tcp import (  # noqa: E402
    ServeClient,
    ServingTCPServer,
)


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


def _toy_server(delay_s=0.005, max_queue=32, max_batch=4, tag="v1"):
    srv = InferenceServer(ServeConfig(max_queue=max_queue,
                                      max_batch=max_batch,
                                      default_deadline_s=30.0))
    srv.add_model("m", ToyModel(delay_s, tag=tag))
    return srv


# ==================================================== breaker probes
class TestBreakerProbeRace:
    def _opened(self, reset_s=0.05):
        b = _Breaker(threshold=1, reset_s=reset_s, model="t")
        b.record(False)
        assert b.state == "open"
        time.sleep(reset_s + 0.02)
        assert b.state == "half-open"
        return b

    def test_concurrent_try_probe_admits_exactly_one(self):
        """ISSUE 16 satellite: the half-open probe slot is
        check-and-set under the breaker lock — N racing threads win
        it exactly once."""
        for _ in range(20):  # the race needs repetitions to bite
            b = self._opened()
            barrier = threading.Barrier(8)
            wins = []

            def racer():
                barrier.wait()
                if b.try_probe():
                    wins.append(1)

            ts = [threading.Thread(target=racer) for _ in range(8)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            assert len(wins) == 1

    def test_failed_probe_reopens_with_backoff_reset(self):
        """A failed probe buys a FULL fresh quarantine: opened_at
        moves to the failure time, so the breaker is strictly open
        again (not instantly half-open off the stale timestamp)."""
        b = self._opened(reset_s=0.15)
        assert b.try_probe()
        b.record(False)
        # the old opened_at is already > reset_s in the past; only a
        # reset backoff window explains state == "open" here
        assert b.state == "open"
        assert not b.admits()
        assert b.try_probe() is False
        time.sleep(0.17)
        assert b.state == "half-open"
        assert b.try_probe()
        b.record(True)
        assert b.state == "closed"

    def test_probe_slot_released_on_success_and_failure(self):
        for ok in (True, False):
            b = self._opened()
            assert b.try_probe()
            assert not b.try_probe()  # slot held
            b.record(ok)
            assert b.probing is False


# ==================================================== envelope gauntlet
@pytest.fixture(scope="module")
def cache_entry(tmp_path_factory):
    """One verified-cache entry shared by the envelope + cache tests
    (compiling even the small program costs ~0.3s)."""
    cache = str(tmp_path_factory.mktemp("vcache"))
    fn = testing_faults.replica_program_fn(4, 16)
    x = np.ones((1, 8), np.float32)
    meta = inference.store_verified(cache, "prog", fn, (x,))
    return {"cache": cache, "key": "prog", "meta": meta, "x": x,
            "fn": fn}


def _entry_file(cache_entry, name):
    return os.path.join(cache_entry["cache"], cache_entry["key"], name)


class TestEnvelope:
    def test_version_byte_present(self, cache_entry):
        blob = open(_entry_file(cache_entry, "program.shlo"),
                    "rb").read()
        magic = inference._EXPORT_MAGIC
        assert blob.startswith(magic)
        assert blob[len(magic)] == inference._EXPORT_VERSION

    def test_truncations_raise_typed_error(self, cache_entry,
                                           tmp_path):
        """Every truncation point — inside the magic, at the version
        byte, inside the digest, inside the payload — raises
        CompiledArtifactError (a ValueError naming the artifact),
        never a bare struct/unpickle crash from inside XLA."""
        blob = open(_entry_file(cache_entry, "program.shlo"),
                    "rb").read()
        hdr = len(inference._EXPORT_MAGIC) + 1 + 32
        for cut in (3, len(inference._EXPORT_MAGIC),
                    len(inference._EXPORT_MAGIC) + 1, hdr - 5, hdr):
            with pytest.raises(inference.CompiledArtifactError,
                               match="model.shlo") as ei:
                inference.load_compiled(blob[:cut],
                                        source="model.shlo",
                                        require_envelope=True)
            assert ei.value.reason in ("truncated", "corrupt")
        assert isinstance(ei.value, ValueError)

    def test_corruption_at_every_offset_detected(self, cache_entry,
                                                 tmp_path):
        """ISSUE 16 satellite: corrupt_file at several offsets —
        magic, version byte, digest, early/middle/late payload — and
        every single corruption is detected before execution."""
        blob = open(_entry_file(cache_entry, "program.shlo"),
                    "rb").read()
        magic_len = len(inference._EXPORT_MAGIC)
        hdr = magic_len + 1 + 32
        offsets = (0, magic_len, magic_len + 1, magic_len + 10,
                   hdr, hdr + (len(blob) - hdr) // 2, len(blob) - 4)
        for off in offsets:
            p = tmp_path / f"model_{off}.shlo"
            p.write_bytes(blob)
            testing_faults.corrupt_file(str(p), offset=off, nbytes=4)
            with pytest.raises(ValueError, match="model_") as ei:
                inference.load_compiled(p.read_bytes(),
                                        source=p.name,
                                        require_envelope=True)
            assert isinstance(ei.value,
                              inference.CompiledArtifactError)
            assert ei.value.reason in ("corrupt", "version")

    def test_clean_blob_loads(self, cache_entry):
        blob = open(_entry_file(cache_entry, "program.shlo"),
                    "rb").read()
        call = inference.load_compiled(blob, source="model.shlo",
                                       require_envelope=True)
        out = np.asarray(call(cache_entry["x"]))
        assert out.shape == (1,)


# ==================================================== verified cache
class TestVerifiedCache:
    def test_roundtrip_fast_path(self, cache_entry):
        prog = inference.load_verified(cache_entry["cache"],
                                       cache_entry["key"])
        assert prog.via == "exec"  # deserialize, no recompile
        got = np.asarray(prog(cache_entry["x"]))
        import jax

        want = np.asarray(jax.jit(cache_entry["fn"])(cache_entry["x"]))
        np.testing.assert_allclose(got, want, rtol=1e-6)
        assert prog.audit["ok"]
        assert prog.meta["schema"] == inference.CACHE_META_SCHEMA

    def test_missing_entry(self, cache_entry):
        with pytest.raises(inference.VerifiedCacheError) as ei:
            inference.load_verified(cache_entry["cache"], "nope")
        assert ei.value.reason == "missing"

    @pytest.mark.parametrize("victim", ["program.exec",
                                        "program.shlo",
                                        "program.hlo.txt"])
    def test_digest_gate_refuses_tampered_file(self, cache_entry,
                                               tmp_path, victim):
        import shutil

        entry = tmp_path / "c" / "prog"
        shutil.copytree(
            os.path.join(cache_entry["cache"], cache_entry["key"]),
            entry)
        testing_faults.corrupt_file(str(entry / victim), offset=None,
                                    nbytes=4)
        with pytest.raises(inference.VerifiedCacheError) as ei:
            inference.load_verified(str(tmp_path / "c"), "prog")
        assert ei.value.reason == "digest"
        assert victim in str(ei.value)

    def test_digest_gate_refuses_truncation(self, cache_entry,
                                            tmp_path):
        import shutil

        entry = tmp_path / "c" / "prog"
        shutil.copytree(
            os.path.join(cache_entry["cache"], cache_entry["key"]),
            entry)
        testing_faults.truncate_file(str(entry / "program.exec"), 0.5)
        with pytest.raises(inference.VerifiedCacheError) as ei:
            inference.load_verified(str(tmp_path / "c"), "prog")
        assert ei.value.reason == "digest"

    def test_meta_tamper_refused(self, cache_entry, tmp_path):
        import shutil

        entry = tmp_path / "c" / "prog"
        shutil.copytree(
            os.path.join(cache_entry["cache"], cache_entry["key"]),
            entry)
        (entry / "meta.json").write_text("{not json")
        with pytest.raises(inference.VerifiedCacheError) as ei:
            inference.load_verified(str(tmp_path / "c"), "prog")
        assert ei.value.reason == "meta"

    def test_audit_policy_gate_at_boot(self, cache_entry):
        """The hlo_audit policy gate is live at LOAD time: a stricter
        boot policy than the entry was stored under refuses the boot
        even though every digest is clean."""
        with pytest.raises(inference.VerifiedCacheError) as ei:
            inference.load_verified(cache_entry["cache"],
                                    cache_entry["key"],
                                    policy={"total_bytes_max": 1})
        assert ei.value.reason == "audit"
        assert "total_bytes" in str(ei.value)

    def test_audit_policy_gate_at_store(self, tmp_path):
        """A program that already violates the policy is never
        published — store raises and the cache dir holds no entry."""
        fn = testing_faults.replica_program_fn(2, 8)
        with pytest.raises(inference.VerifiedCacheError) as ei:
            inference.store_verified(
                str(tmp_path), "bad", fn,
                (np.ones((1, 8), np.float32),),
                policy={"total_bytes_max": 1})
        assert ei.value.reason == "audit"
        assert not inference.has_verified(str(tmp_path), "bad")
        leftovers = [f for f in os.listdir(tmp_path)
                     if not f.startswith(".tmp-")]
        assert leftovers == []


# ==================================================== client retry
class TestClientRetry:
    def test_retry_rides_over_late_server(self):
        """ISSUE 16 satellite: the connect loop retries refused
        connects with backoff, so the router survives the window
        where a restarted replica is not yet listening."""
        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        port = probe.getsockname()[1]
        probe.close()

        holder = {}

        def late_start():
            time.sleep(0.3)
            holder["srv"] = _toy_server()
            holder["tcp"] = ServingTCPServer(holder["srv"], port=port)

        t = threading.Thread(target=late_start, daemon=True)
        t.start()
        try:
            c = ServeClient(f"127.0.0.1:{port}", retries=8,
                            backoff_s=0.05)
            out = c.call("m", [1, 2, 3], deadline_ms=10000)
            assert out["ok"] and out["tokens"] == [3]
            c.close()
        finally:
            t.join()
            holder["tcp"].stop()
            holder["srv"].shutdown(drain=False)

    def test_retries_zero_fails_fast(self):
        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        port = probe.getsockname()[1]
        probe.close()
        c = ServeClient(f"127.0.0.1:{port}", retries=0)
        t0 = time.monotonic()
        with pytest.raises(ConnectionRefusedError):
            c.call("m", [1])
        assert time.monotonic() - t0 < 1.0


# ==================================================== drain semantics
class TestDrain:
    def test_stop_drain_lands_inflight_response(self):
        """ISSUE 16 satellite: stop(drain=True) waits for admitted
        frames to get their response bytes out before closing the
        connection — "zero admitted requests lost" by construction,
        not timing."""
        srv = _toy_server(delay_s=0.3)
        tcp = ServingTCPServer(srv)
        got = {}

        def caller():
            c = ServeClient(f"127.0.0.1:{tcp.port}")
            got["resp"] = c.call("m", [1, 2], deadline_ms=10000,
                                 timeout=10)
            c.close()

        t = threading.Thread(target=caller)
        t.start()
        time.sleep(0.1)  # request admitted, dispatch in flight
        tcp.stop(drain=True, timeout=10.0)
        srv.shutdown(drain=True)
        t.join(10)
        assert got["resp"]["ok"] and got["resp"]["tokens"] == [2]

    def test_stop_accepting_idempotent_and_refuses_new(self):
        srv = _toy_server()
        tcp = ServingTCPServer(srv)
        tcp.stop_accepting()
        tcp.stop_accepting()  # idempotent
        assert not tcp._thread.is_alive()  # accept loop joined
        with pytest.raises(OSError):
            socket.create_connection(("127.0.0.1", tcp.port),
                                     timeout=0.5)
        tcp.stop(drain=True)
        srv.shutdown(drain=False)


# ==================================================== in-process fleet
class _Replica:
    """In-process replica: real TCP server, real InferenceServer."""

    def __init__(self, delay_s=0.005, max_queue=32, max_batch=4,
                 tag="v1"):
        self.srv = _toy_server(delay_s, max_queue, max_batch, tag)

        def load_model(name, new_tag):
            return ToyModel(delay_s, tag=new_tag or "swapped")

        self.tcp = ServingTCPServer(self.srv, model_loader=load_model)
        self.addr = f"127.0.0.1:{self.tcp.port}"

    def close(self):
        self.tcp.stop()
        self.srv.shutdown(drain=False)


class TestFleetRouterInProcess:
    def test_spill_before_shed(self):
        """An overloaded replica's shed is a routing hint: the
        request lands on the sibling, and only when EVERY replica
        refuses does the fleet shed."""
        slow = _Replica(delay_s=0.5, max_queue=1, max_batch=1)
        fast = _Replica(delay_s=0.002)
        router = FleetRouter({"slow": slow.addr, "fast": fast.addr},
                             FleetConfig(poll_interval_s=0.05))
        try:
            time.sleep(0.12)
            # saturate: more concurrent requests than the slow
            # replica can queue — everything must still complete
            results = []
            lock = threading.Lock()

            def one():
                r = router.call("m", [1, 2, 3], deadline_ms=20000,
                                trace=False)
                with lock:
                    results.append(r)

            ts = [threading.Thread(target=one) for _ in range(12)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(30)
            assert len(results) == 12
            assert all(r.get("ok") for r in results), results
        finally:
            router.close()
            slow.close()
            fast.close()

    def test_rollout_zero_downtime(self):
        """Hot-swap across a 2-replica fleet while a client polls at
        fixed rate: zero refused/failed responses, and the tag
        observed transitions v1 -> v2 with no gap."""
        reps = [_Replica(delay_s=0.002), _Replica(delay_s=0.002)]
        router = FleetRouter(
            {"r0": reps[0].addr, "r1": reps[1].addr},
            FleetConfig(poll_interval_s=0.05))
        try:
            time.sleep(0.12)
            stop = threading.Event()
            seen = []
            failures = []
            lock = threading.Lock()

            def poller():
                while not stop.is_set():
                    r = router.call("m", [1, 2], deadline_ms=5000,
                                    trace=False)
                    with lock:
                        if r.get("ok"):
                            seen.append(r.get("tag"))
                        else:
                            failures.append(r)
                    time.sleep(0.005)

            t = threading.Thread(target=poller)
            t.start()
            time.sleep(0.1)
            res = router.rollout("m", tag="v2")
            time.sleep(0.15)
            stop.set()
            t.join(10)
            assert failures == [], failures[:3]
            assert all(r.get("ok") and r.get("swapped") == "m"
                       for r in res.values()), res
            assert seen[0] == "v1" and seen[-1] == "v2"
            # monotonic transition: once v2 appears, v1 never returns
            # ON THE SAME REPLICA is not observable here, but the
            # fleet-level guarantee is: no response is ever lost and
            # the final state is uniformly v2
            assert "v2" in seen
        finally:
            router.close()
            for r in reps:
                r.close()

    def test_rollout_unknown_model_raises(self):
        rep = _Replica()
        router = FleetRouter({"r0": rep.addr},
                             FleetConfig(poll_interval_s=0.05))
        try:
            with pytest.raises(RuntimeError, match="refused"):
                router.rollout("ghost")
        finally:
            router.close()
            rep.close()

    def test_swap_without_loader_refused(self):
        srv = _toy_server()
        tcp = ServingTCPServer(srv)  # no model_loader
        try:
            c = ServeClient(f"127.0.0.1:{tcp.port}")
            r = c._roundtrip({"admin": "swap_model", "model": "m"})
            assert not r["ok"] and r["error"] == "no_loader"
            c.close()
        finally:
            tcp.stop()
            srv.shutdown(drain=False)


# ==================================================== faults tier
@pytest.mark.faults
class TestFleetFaults:
    def _prep_cache(self, tmp_path):
        cache = str(tmp_path / "vcache")
        fn = testing_faults.replica_program_fn(4, 16)
        inference.store_verified(cache, "fleet", fn,
                                 (np.zeros((1, 8), np.float32),))
        return cache

    def test_sigkill_zero_loss_rotation_and_cache_rejoin(self,
                                                         tmp_path):
        """The acceptance headline: 3 replicas under sustained load,
        SIGKILL one mid-stream — zero admitted requests lost (every
        call spilled or completed), the dead replica rotates out
        within one breaker window, and its replacement boots from the
        verified AOT cache and rejoins rotation via the half-open
        probe."""
        cache = self._prep_cache(tmp_path)
        procs = {}
        addrs = {}
        for i in range(3):
            p, port = testing_faults.start_serving_replica(
                REPO, REPLICA_MODE="toy", TOY_DELAY_S=0.002,
                MODEL_TAG="v1")
            assert port is not None, p.boot_line
            procs[f"r{i}"] = p
            addrs[f"r{i}"] = f"127.0.0.1:{port}"
        fcfg = FleetConfig(poll_interval_s=0.05, breaker_reset_s=0.4)
        router = FleetRouter(dict(addrs), fcfg)
        try:
            time.sleep(0.15)
            stop = threading.Event()
            lock = threading.Lock()
            ok, lost = [0], []

            def load():
                while not stop.is_set():
                    try:
                        r = router.call("m", [1, 2, 3],
                                        deadline_ms=5000, trace=False)
                    except Exception as e:  # noqa: BLE001
                        with lock:
                            lost.append(repr(e))
                        continue
                    with lock:
                        if r.get("ok"):
                            ok[0] += 1
                        else:
                            lost.append(r)

            workers = [threading.Thread(target=load, daemon=True)
                       for _ in range(4)]
            for w in workers:
                w.start()
            time.sleep(0.4)
            testing_faults.kill_process(procs["r1"])
            # rotation within one breaker window (threshold=3
            # transport failures, then open)
            deadline = time.monotonic() + fcfg.breaker_reset_s * 3
            while time.monotonic() < deadline:
                if router.states()["r1"]["breaker"] != "closed":
                    break
                time.sleep(0.01)
            assert router.states()["r1"]["breaker"] != "closed"
            time.sleep(0.4)  # keep serving through the outage
            stop.set()
            for w in workers:
                w.join(10)
            assert lost == [], lost[:5]
            assert ok[0] > 50

            # replacement boots FROM THE VERIFIED CACHE and rejoins
            p, port = testing_faults.start_serving_replica(
                REPO, REPLICA_MODE="cache", CACHE_DIR=cache,
                CACHE_KEY="fleet", MODEL_TAG="v2")
            assert port is not None, p.boot_line
            assert p.boot_line.startswith("BOOT cache")
            procs["r1"] = p
            router.set_address("r1", f"127.0.0.1:{port}")
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if router.states()["r1"]["breaker"] == "closed":
                    break
                time.sleep(0.02)
            assert router.states()["r1"]["breaker"] == "closed"
            # the rejoined replica actually serves
            with ServeClient(f"127.0.0.1:{port}") as c:
                out = c.call("m", [1, 2], deadline_ms=10000,
                             timeout=30)
            assert out["ok"] and out["tag"] == "v2"
        finally:
            router.close()
            for p in procs.values():
                testing_faults.kill_process(p)

    def test_cache_gate_refuses_corrupt_entry_at_boot(self, tmp_path):
        """Acceptance: a tampered artifact is refused at replica boot
        — the process exits nonzero printing BOOT_REFUSED, serves
        nothing."""
        cache = self._prep_cache(tmp_path)
        testing_faults.corrupt_file(
            os.path.join(cache, "fleet", "program.exec"),
            offset=None, nbytes=4)
        p, port = testing_faults.start_serving_replica(
            REPO, REPLICA_MODE="cache", CACHE_DIR=cache,
            CACHE_KEY="fleet")
        assert port is None
        assert p.boot_line and "BOOT_REFUSED" in p.boot_line
        assert "digest" in p.boot_line or "sha256" in p.boot_line
        assert p.wait(timeout=30) == 3

    def test_cache_gate_refuses_policy_violation_at_boot(self,
                                                         tmp_path):
        """Acceptance: a boot policy the entry's HLO violates refuses
        the boot even with clean digests — the audit gate is live at
        every boot, not just at store."""
        cache = self._prep_cache(tmp_path)
        p, port = testing_faults.start_serving_replica(
            REPO, REPLICA_MODE="cache", CACHE_DIR=cache,
            CACHE_KEY="fleet",
            CACHE_POLICY=json.dumps({"total_bytes_max": 1}))
        assert port is None
        assert p.boot_line and "BOOT_REFUSED" in p.boot_line
        assert "policy" in p.boot_line or "audit" in p.boot_line
        assert p.wait(timeout=30) == 3

    def test_sigkill_mid_store_leaves_no_entry(self, tmp_path):
        """Atomic publish: SIGKILL during store_verified leaves only
        ignored .tmp-* garbage, never a half-visible entry — and a
        subsequent store of the same key succeeds."""
        cache = str(tmp_path / "vcache")
        src = (
            "import sys, numpy as np\n"
            f"sys.path.insert(0, {REPO!r})\n"
            "import jax\n"
            "jax.config.update('jax_platforms', 'cpu')\n"
            "from paddle_tpu import inference, testing_faults\n"
            "print('GO', flush=True)\n"
            # ~8 s of compile on this box: 0.8 s in is mid-compile
            # with room (64 layers took ~1.2 s and raced the kill)
            "fn = testing_faults.replica_program_fn(1000, 256)\n"
            "inference.store_verified(\n"
            f"    {cache!r}, 'k', fn,\n"
            "    (np.zeros((1, 8), np.float32),))\n"
        )
        proc = subprocess.Popen(
            [sys.executable, "-c", src], cwd=REPO,
            # the kill has to land mid-compile: the persistent cache
            # would hand the program over in milliseconds
            env=dict(os.environ, JAX_PLATFORMS="cpu",
                     JAX_ENABLE_COMPILATION_CACHE="false"),
            stdout=subprocess.PIPE, text=True)
        assert proc.stdout.readline().startswith("GO")
        time.sleep(0.8)  # mid-compile / mid-write
        testing_faults.kill_process(proc)
        assert not inference.has_verified(cache, "k")
        # the torn temp dir (if any) does not block a clean re-store
        fn = testing_faults.replica_program_fn(2, 8)
        inference.store_verified(cache, "k", fn,
                                 (np.zeros((1, 8), np.float32),))
        prog = inference.load_verified(cache, "k")
        assert prog.via == "exec"
