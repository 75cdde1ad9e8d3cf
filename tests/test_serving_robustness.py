"""Serving robustness: the continuous-batching server under violence.

ROADMAP item 3's acceptance surface, driven by `testing_faults`:
overload sheds explicitly with the queue bounded and admitted p99
inside the deadline; FlakyProxy RST/delay/mid-response cuts on client
connections neither wedge the server nor leak in-flight requests;
SIGKILL of the serving worker mid-request fails the client fast;
drain-on-shutdown terminates every admitted request; a hook-bearing
generation request completes via the host-stepped fallback; and the
registry carries queue depth, occupancy, the time split and the shed
count at three offered loads.

Everything runs on CPU — serving robustness is a correctness
property, not a hardware property.
"""

import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from paddle_tpu.serving.server import (  # noqa: E402
    InferenceServer,
    ServeConfig,
    ServeError,
    ServeRejected,
)


# ---------------------------------------------------------------- toys
class ToyModel:
    """Deterministic-latency model: serving-logic tests measure the
    scheduler, not XLA."""

    can_host = False
    engine = None
    named_hooks = {}

    def __init__(self, delay_s=0.02):
        self.delay_s = delay_s

    def run_batch(self, ids, lens, hooks, host):
        time.sleep(self.delay_s)
        return [
            {"tokens": [int(lens[i])], "score": 0.0}
            for i in range(ids.shape[0])
        ]


class FlakyJitModel(ToyModel):
    """Rung-1 (jitted) dispatch always fails; rung 2 (host) works —
    the degradation ladder's fallback edge without jax in the loop."""

    can_host = True

    def run_batch(self, ids, lens, hooks, host):
        if not host:
            raise RuntimeError("decode program exploded")
        return super().run_batch(ids, lens, hooks, True)


def _bigram_model(vocab=6, eos=1, beam=3, max_len=6, seed=0,
                  named_hooks=None):
    import jax.numpy as jnp

    from paddle_tpu import dsl
    from paddle_tpu.beam_search import BeamSearchDecoder
    from paddle_tpu.core.config import ParameterConf
    from paddle_tpu.serving.models import GenerationModel

    def step(word):
        emb = dsl.embedding(word, size=vocab, vocab_size=vocab,
                            param=ParameterConf(name="srv_bigram"))
        return dsl.mixed(vocab, [(emb, "identity")], act="softmax",
                         bias=False, name="prob")

    dec = BeamSearchDecoder(step, n_static=0, bos_id=0, eos_id=eos,
                            beam_size=beam, max_length=max_len)
    rng = np.random.default_rng(seed)
    table = rng.standard_normal((vocab, vocab)).astype(np.float32) * 2
    params = {"srv_bigram": jnp.asarray(table)}
    return dec, params, GenerationModel(dec, params,
                                        named_hooks=named_hooks)


# ======================================================== SLO behavior
class TestOverloadProtection:
    def test_sheds_explicitly_and_holds_p99(self):
        """Offered load far above capacity: excess is EXPLICITLY
        rejected (never queued unboundedly), queue depth stays within
        the bound, and the p99 of requests that were admitted and
        completed stays within the configured deadline — the
        deadline-aware batch former drops budget-short work before
        dispatch."""
        deadline = 0.4
        cfg = ServeConfig(max_queue=8, max_batch=4,
                          default_deadline_s=deadline)
        srv = InferenceServer(cfg)
        srv.add_model("toy", ToyModel(delay_s=0.02))
        reqs, shed = [], 0
        t0 = time.monotonic()
        while time.monotonic() - t0 < 1.0:
            try:
                reqs.append(srv.submit("toy", [1, 2, 3]))
            except ServeRejected as e:
                assert e.reason == "overloaded"
                shed += 1
            time.sleep(0.0005)
        srv.shutdown(drain=True)
        st = srv.stats()
        assert shed > 0, "no explicit shedding at 10x overload"
        assert st["max_queue_depth"] <= cfg.max_queue
        states = [r.state for r in reqs]
        assert all(s != "pending" for s in states), "leaked requests"
        lat = sorted(r.latency_s for r in reqs if r.state == "done")
        assert lat, "nothing completed under overload"
        p99 = lat[int(0.99 * (len(lat) - 1))]
        assert p99 <= deadline, f"admitted p99 {p99:.3f}s > deadline"

    def test_expired_work_dropped_before_dispatch(self):
        """A request whose deadline passes while queued is rejected
        with reason 'deadline' without ever reaching the model."""
        calls = []

        class Counting(ToyModel):
            def run_batch(self, ids, lens, hooks, host):
                calls.append(ids.shape[0])
                return super().run_batch(ids, lens, hooks, host)

        cfg = ServeConfig(max_queue=32, max_batch=2,
                          default_deadline_s=10.0)
        srv = InferenceServer(cfg)
        srv.add_model("toy", Counting(delay_s=0.05))
        blocker = srv.submit("toy", [1])  # occupies the worker
        time.sleep(0.02)  # let the blocker dispatch alone
        doomed = srv.submit("toy", [2], deadline_s=0.01)
        time.sleep(0.03)  # expires while the blocker dispatch runs
        with pytest.raises(ServeRejected) as ei:
            doomed.result(timeout=10)
        assert ei.value.reason == "deadline"
        assert blocker.result(timeout=10)["tokens"] == [1]
        srv.shutdown()
        assert sum(calls) == 1  # the doomed request never dispatched


class TestCircuitBreaker:
    def test_quarantine_and_halfopen_recovery(self):
        class Sick(ToyModel):
            def __init__(self):
                super().__init__(delay_s=0.0)
                self.fail = True

            def run_batch(self, ids, lens, hooks, host):
                if self.fail:
                    raise RuntimeError("poisoned decode program")
                return super().run_batch(ids, lens, hooks, host)

        cfg = ServeConfig(max_queue=16, breaker_threshold=2,
                          breaker_reset_s=0.3)
        srv = InferenceServer(cfg)
        sick = Sick()
        srv.add_model("m", sick)
        for _ in range(cfg.breaker_threshold):
            with pytest.raises(ServeError):
                srv.submit("m", [1]).result(timeout=10)
        # breaker open: instant explicit rejection, no dispatch
        with pytest.raises(ServeRejected) as ei:
            srv.submit("m", [1])
        assert ei.value.reason == "quarantined"
        assert srv.stats()["models"]["m"]["breaker"] == "open"
        # heal the model; after reset_s the half-open probe closes it
        time.sleep(cfg.breaker_reset_s + 0.05)
        sick.fail = False
        assert srv.submit("m", [1]).result(timeout=10)["tokens"] == [1]
        assert srv.stats()["models"]["m"]["breaker"] == "closed"
        srv.shutdown()

    def test_jit_failure_degrades_to_host_rung(self):
        """Rung 2 of the ladder: a jitted dispatch failure retries
        host-stepped within the same dispatch; the request completes
        (path=host) instead of failing."""
        srv = InferenceServer(ServeConfig(max_queue=8))
        srv.add_model("m", FlakyJitModel(delay_s=0.0))
        out = srv.submit("m", [1, 2]).result(timeout=10)
        assert out["path"] == "host" and out["tokens"] == [2]
        srv.shutdown()


class TestDrain:
    def test_drain_under_load_leaks_nothing(self):
        cfg = ServeConfig(max_queue=64, max_batch=4,
                          default_deadline_s=5.0)
        srv = InferenceServer(cfg)
        srv.add_model("toy", ToyModel(delay_s=0.01))
        reqs = [srv.submit("toy", [i % 7 + 1]) for i in range(40)]
        srv.shutdown(drain=True)  # concurrent with in-flight work
        states = [r.state for r in reqs]
        assert all(s != "pending" for s in states), states
        assert sum(s == "done" for s in states) > 0
        # post-drain admission is an explicit rejection
        with pytest.raises(ServeRejected) as ei:
            srv.submit("toy", [1])
        assert ei.value.reason == "shutting_down"

    def test_nondrain_shutdown_rejects_queued(self):
        srv = InferenceServer(ServeConfig(max_queue=64, max_batch=1))
        srv.add_model("toy", ToyModel(delay_s=0.05))
        reqs = [srv.submit("toy", [1]) for _ in range(10)]
        srv.shutdown(drain=False)
        states = [r.state for r in reqs]
        assert all(s != "pending" for s in states)
        assert any(s == "rejected:shutting_down" for s in states)


# ============================================= generation + hooks path
class TestGenerationServing:
    def test_host_decode_matches_jitted_program(self):
        """Rungs 1 and 2 are interchangeable: identical beams, lengths
        and scores with and without hooks (pure_callback works on the
        CPU backend, so the jitted hook path is the reference)."""
        from paddle_tpu.beam_search import BeamHooks
        from paddle_tpu.serving.host_decode import host_generate

        dec, params, _ = _bigram_model()
        s1, l1, sc1 = dec.generate(params, statics=[], batch_size=3)
        s2, l2, sc2 = host_generate(dec, params, batch_size=3)
        np.testing.assert_array_equal(np.asarray(s1), s2)
        np.testing.assert_array_equal(np.asarray(l1), l2)
        np.testing.assert_allclose(np.asarray(sc1), sc2, rtol=1e-5)

        banned = 2

        def adjust(logp, t):
            lp = np.asarray(logp).copy()
            lp[:, :, banned] = -1e30
            return lp

        dec.hooks = BeamHooks(adjust=adjust)
        s3, l3, sc3 = dec.generate(params, statics=[], batch_size=3)
        dec.hooks = BeamHooks()
        s4, l4, sc4 = host_generate(dec, params, batch_size=3,
                                    hooks=BeamHooks(adjust=adjust))
        np.testing.assert_array_equal(np.asarray(s3), s4)
        np.testing.assert_allclose(np.asarray(sc3), sc4, rtol=1e-5)
        assert banned not in s4[:, 0]

    def test_hook_bearing_request_completes_via_host_fallback(self):
        """VERDICT Missing #1 closed: a generation request carrying a
        beamSearchCandidateAdjust-style hook COMPLETES — served by the
        host-stepped rung, which never touches pure_callback, so it is
        viable on runtimes that reject host callbacks. This test
        replaces the bench record's `hooks_on: unavailable` row as the
        hook-availability record."""
        from paddle_tpu.beam_search import BeamHooks

        banned = 2

        def adjust(logp, t):
            lp = np.asarray(logp).copy()
            lp[:, :, banned] = -1e30
            return lp

        dec, params, model = _bigram_model(
            named_hooks={"ban2": BeamHooks(adjust=adjust)}
        )
        srv = InferenceServer(ServeConfig(max_queue=16, max_batch=4))
        srv.add_model("gen", model)
        plain = srv.submit("gen", [1, 2, 3]).result(timeout=120)
        hooked = srv.submit("gen", [1, 2, 3],
                            hooks_name="ban2").result(timeout=120)
        srv.shutdown()
        assert plain["path"] == "jit"
        assert hooked["path"] == "host"
        assert banned not in hooked["tokens"]
        assert hooked["tokens"], "empty generation"

    def test_dispatch_program_keys_stay_bounded(self):
        """Variable-length arrivals collapse onto len-bucket ×
        batch-bucket dispatch keys — the decode-program cache cannot
        grow per arrival shape."""
        dec, params, model = _bigram_model()
        # generous deadline: this test pins cache boundedness, not
        # latency — on a loaded CI box the first-dispatch compiles can
        # exceed the 2s default and deadline-reject queued requests
        cfg = ServeConfig(max_queue=64, max_batch=4, buckets=(8, 16),
                          default_deadline_s=120.0)
        srv = InferenceServer(cfg)
        srv.add_model("gen", model)
        reqs = [
            srv.submit("gen", list(range(1, n + 1)))
            for n in (1, 2, 3, 5, 7, 9, 11, 13, 15, 4, 6, 8)
        ]
        for r in reqs:
            r.result(timeout=120)
        keys = srv.stats()["models"]["gen"]["dispatch_keys"]
        srv.shutdown()
        # 2 len buckets x at most 3 batch buckets (1,2,4), hooks=False
        assert keys <= 6


class TestMultiModelCoDispatch:
    def test_merged_models_codispatch_and_match_direct_forward(self):
        import jax.numpy as jnp

        from paddle_tpu import dsl
        from paddle_tpu.core.arg import Arg
        from paddle_tpu.serving.models import MultiForwardHost

        def make_conf(classes):
            with dsl.model() as g:
                w = dsl.data("w", (1,), is_seq=True, is_ids=True)
                emb = dsl.embedding(w, size=8, vocab_size=20,
                                    name="emb")
                pooled = dsl.seq_pool(emb, pool_type="average",
                                      name="pool")
                dsl.fc(pooled, size=classes, act="softmax", name="out")
                g.conf.output_layer_names.append("out")
            return g.conf

        host = MultiForwardHost({"a": make_conf(3), "b": make_conf(5)})
        srv = InferenceServer(ServeConfig(max_queue=32, max_batch=4))
        srv.add_model("a", host.sub("a"))
        srv.add_model("b", host.sub("b"))
        ra = [srv.submit("a", [1, 2, 3, 4]) for _ in range(3)]
        rb = [srv.submit("b", [5, 6]) for _ in range(3)]
        oa = [r.result(timeout=120) for r in ra]
        ob = [r.result(timeout=120) for r in rb]
        st = srv.stats()
        srv.shutdown()
        assert len(oa[0]["scores"]) == 3 and len(ob[0]["scores"]) == 5
        # one merged program served both models' batches
        assert st["batches_codispatch"] >= 1
        # correctness vs a direct merged-net forward
        ids = np.zeros((1, 8), np.int32)
        ids[0, :4] = [1, 2, 3, 4]
        feed = {
            "a/w": Arg(ids=jnp.asarray(ids),
                       seq_lens=jnp.asarray([4], jnp.int32)),
            "b/w": Arg(ids=jnp.zeros((1, 1), jnp.int32),
                       seq_lens=jnp.ones((1,), jnp.int32)),
        }
        outs, _ = host.net.forward(host.params, feed,
                                   outputs=["a/out"], train=False)
        np.testing.assert_allclose(
            np.asarray(oa[0]["scores"]),
            np.asarray(outs["a/out"].value)[0], rtol=1e-5,
        )


# ================================================= network-level faults
class TestTCPFaults:
    def _serving(self, delay_s=0.02):
        from paddle_tpu.serving.tcp import ServingTCPServer

        srv = InferenceServer(ServeConfig(max_queue=32, max_batch=4))
        srv.add_model("toy", ToyModel(delay_s=delay_s))
        tcp = ServingTCPServer(srv)
        return srv, tcp

    def test_flaky_clients_do_not_wedge_or_leak(self):
        """RST'd, delayed, and mid-response-cut client connections
        (FlakyProxy on the CLIENT side) leave the server fully
        serviceable and every in-flight request terminal."""
        from paddle_tpu.serving.tcp import ServeClient
        from paddle_tpu.testing_faults import FlakyProxy

        srv, tcp = self._serving()
        try:
            with FlakyProxy(("127.0.0.1", tcp.port)) as proxy:
                addr = f"127.0.0.1:{proxy.port}"
                # healthy through the proxy
                c = ServeClient(addr)
                assert c.call("toy", [1, 2], deadline_ms=3000)["ok"]
                # RST after the request is on the wire: the server
                # processes it, the client's read fails — no hang
                proxy.reset_next(1)
                c2 = ServeClient(addr)
                with pytest.raises((ConnectionError, OSError)):
                    c2.call("toy", [1, 2, 3], deadline_ms=3000,
                            timeout=10)
                proxy.heal()
                # torn mid-response: 2 bytes of frame then RST
                proxy.cut_after(2)
                c3 = ServeClient(addr)
                with pytest.raises((ConnectionError, OSError)):
                    c3.call("toy", [1], deadline_ms=3000, timeout=10)
                proxy.heal()
                # delayed connections still land
                proxy.delay(0.2)
                c4 = ServeClient(addr)
                assert c4.call("toy", [1, 2, 3, 4],
                               deadline_ms=5000)["ok"]
                proxy.cut_existing()
            # after all faults: a direct client is served immediately
            from paddle_tpu.serving.tcp import ServeClient as SC

            c5 = SC(f"127.0.0.1:{tcp.port}")
            out = c5.call("toy", [9] * 5, deadline_ms=3000)
            assert out["ok"] and out["tokens"] == [5]
        finally:
            tcp.stop()
            srv.shutdown(drain=True)
        st = srv.stats()
        assert st["queue_depth"] == 0
        # every admitted request reached a terminal state
        assert st["admitted"] == (
            st["completed"] + st["shed_deadline"] + st["failed"]
            + st["shed_shutdown"]
        )


SERVE_CONF_SRC = textwrap.dedent(
    """
    import time

    from paddle_tpu.serving.server import InferenceServer, ServeConfig

    class SlowToy:
        can_host = False
        engine = None
        named_hooks = {}
        def __init__(self, delay_s):
            self.delay_s = delay_s
        def run_batch(self, ids, lens, hooks, host):
            time.sleep(self.delay_s)
            return [{"tokens": [int(lens[i])], "score": 0.0}
                    for i in range(ids.shape[0])]

    def get_server():
        srv = InferenceServer(ServeConfig(max_queue=16, max_batch=4,
                                          default_deadline_s=30.0))
        srv.add_model("fast", SlowToy(0.01))
        srv.add_model("slow", SlowToy(3.0))
        return srv
    """
)


class TestServeCLI:
    def _spawn(self, tmp_path):
        conf = tmp_path / "serve_conf.py"
        conf.write_text(SERVE_CONF_SRC)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu", "serve",
             "--config", str(conf)],
            cwd=REPO, env=env, stdout=subprocess.PIPE, text=True,
        )
        line = proc.stdout.readline()
        assert line.startswith("LISTENING"), line
        return proc, int(line.split()[1])

    def test_serve_roundtrip_and_graceful_drain(self, tmp_path):
        from paddle_tpu.serving.tcp import ServeClient

        proc, port = self._spawn(tmp_path)
        try:
            c = ServeClient(f"127.0.0.1:{port}")
            out = c.call("fast", [1, 2, 3], deadline_ms=10000)
            assert out["ok"] and out["tokens"] == [3]
            # SIGTERM = graceful: drains and reports stats
            proc.send_signal(__import__("signal").SIGTERM)
            assert proc.wait(timeout=30) == 0
            rest = proc.stdout.read()
            assert "DRAINED" in rest
            stats = json.loads(rest.split("DRAINED ", 1)[1])
            assert stats["completed"] >= 1
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()

    def test_sigterm_drain_delivers_inflight_response(self, tmp_path):
        """Graceful drain keeps established connections open: a client
        whose request is mid-service when SIGTERM lands still receives
        its response (only the listener closes immediately)."""
        from paddle_tpu.serving.tcp import ServeClient

        proc, port = self._spawn(tmp_path)
        try:
            c = ServeClient(f"127.0.0.1:{port}")
            got = []

            def inflight():
                got.append(c.call("slow", [1, 2, 3],
                                  deadline_ms=60000, timeout=60))

            th = threading.Thread(target=inflight)
            th.start()
            time.sleep(0.5)  # the 3s model is mid-service
            proc.send_signal(__import__("signal").SIGTERM)
            th.join(timeout=40)
            assert not th.is_alive()
            assert got and got[0]["ok"] and got[0]["tokens"] == [3], got
            assert proc.wait(timeout=30) == 0
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()

    def test_sigkill_mid_request_fails_client_fast(self, tmp_path):
        """SIGKILL of the serving worker while a request is in flight:
        the client sees a connection error promptly (RST/EOF), not a
        deadline-length hang."""
        from paddle_tpu.serving.tcp import ServeClient
        from paddle_tpu.testing_faults import kill_process

        proc, port = self._spawn(tmp_path)
        try:
            c = ServeClient(f"127.0.0.1:{port}")
            assert c.call("fast", [1], deadline_ms=10000)["ok"]
            err, elapsed = [], []

            def doomed():
                t0 = time.monotonic()
                try:
                    c.call("slow", [1, 2], deadline_ms=60000,
                           timeout=60)
                except (ConnectionError, OSError) as e:
                    err.append(e)
                elapsed.append(time.monotonic() - t0)

            th = threading.Thread(target=doomed)
            th.start()
            time.sleep(0.5)  # request is mid-service (3s model)
            kill_process(proc)
            th.join(timeout=30)
            assert not th.is_alive(), "client wedged after SIGKILL"
            assert err, "client saw no connection error"
            assert elapsed[0] < 10, f"took {elapsed[0]:.1f}s to fail"
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()


# ========================================================= telemetry
class TestServeTelemetry:
    @pytest.mark.parametrize("load", [
        "one_at_a_time", "burst_of_max_batch", "burst_past_the_queue",
    ])
    def test_registry_under_load(self, load):
        """What the server publishes into the obs registry while it
        serves, read as an operator's dashboard reads it (deltas over
        the load): queue-depth high-water mark, mean batch occupancy,
        the admitted requests' time split, the latency histogram, and
        under overload the shed count, with no admitted request
        lost."""
        from paddle_tpu.obs import aggregate as agg
        from paddle_tpu.obs import metrics as om

        reg = om.get_registry()
        name = f"toy-{load}"  # series of this case alone

        def read():
            return {
                "latency": reg.counter(
                    "serving.request_latency_s").get(),
                "queue_wait": reg.counter(
                    "serving.request_queue_wait_s").get(),
                "dispatch": reg.counter(
                    "serving.request_dispatch_s").get(),
                "shed": reg.counter("serving.shed").get(
                    reason="overloaded"),
            }

        reg.gauge("serving.queue_depth_hwm").reset()
        base = read()
        cfg = ServeConfig(max_queue=8, max_batch=4,
                          default_deadline_s=30.0)
        srv = InferenceServer(cfg)
        srv.add_model(name, ToyModel(delay_s=0.02))
        admitted, shed = [], 0
        try:
            if load == "one_at_a_time":
                for _ in range(4):
                    admitted.append(srv.submit(name, [1, 2]))
                    admitted[-1].result(timeout=30)
            else:
                n = (cfg.max_batch if load == "burst_of_max_batch"
                     else 5 * cfg.max_queue)
                for _ in range(n):
                    try:
                        admitted.append(srv.submit(name, [1, 2]))
                    except ServeRejected as e:
                        assert e.reason == "overloaded"
                        shed += 1
                for r in admitted:
                    r.result(timeout=30)
        finally:
            srv.shutdown(drain=True)
        now = read()
        d = {k: now[k] - base[k] for k in base}
        assert reg.gauge("serving.queue_depth_hwm").get(default=0) >= 1
        batches = reg.counter("serving.batches").get(model=name)
        served = reg.counter("serving.batch_requests").get(model=name)
        assert batches >= 1 and served / batches >= 1.0
        lat, wait, disp = d["latency"], d["queue_wait"], d["dispatch"]
        assert lat > 0
        for share in (wait / lat, disp / lat,
                      max(1.0 - (wait + disp) / lat, 0.0)):
            assert 0.0 <= share <= 1.0, (wait, disp, lat)
        hist = reg.snapshot()["histograms"][
            f"serving.admitted_latency_s{{model={name}}}"]
        assert agg.quantile(hist, 0.5) <= agg.quantile(hist, 0.99)
        # nothing admitted is lost: every admitted request was served
        assert all(r.state == "done" for r in admitted)
        assert served == len(admitted) == hist["count"]
        assert d["shed"] == shed
        if load == "burst_past_the_queue":
            assert shed > 0
            assert len(admitted) + shed == 5 * cfg.max_queue
        else:
            assert shed == 0


class TestLoadCompiledFaults:
    def test_truncated_and_corrupt_blob_raise_clear_valueerror(
        self, tmp_path
    ):
        """PR-8 satellite: `inference.load_compiled` on a torn or
        bit-flipped StableHLO artifact raises ValueError NAMING the
        artifact instead of crashing inside XLA."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu import dsl, inference
        from paddle_tpu.core.arg import non_seq
        from paddle_tpu.network import Network
        from paddle_tpu.testing_faults import corrupt_file, truncate_file
        from paddle_tpu.trainer.trainer import Inferencer

        with dsl.model() as g:
            x = dsl.data("x", 4)
            dsl.fc(x, size=2, name="out")
        net = Network(g.conf)
        params = net.init_params(jax.random.key(0))
        inf = Inferencer(net, params, outputs=["out"])
        feed = {"x": non_seq(jnp.ones((2, 4), jnp.float32))}
        blob = inference.export_compiled(inf, feed)

        # intact roundtrip still works (envelope is transparent)
        fn = inference.load_compiled(blob)
        out = fn(inf.params, inf.state, feed)
        assert np.asarray(out["out"].value).shape == (2, 2)

        path = str(tmp_path / "model.shlo")
        with open(path, "wb") as f:
            f.write(blob)
        truncate_file(path, keep_fraction=0.5)
        with pytest.raises(ValueError, match="model.shlo"):
            inference.load_compiled(open(path, "rb").read(),
                                    source=path)

        with open(path, "wb") as f:
            f.write(blob)
        corrupt_file(path)
        with pytest.raises(ValueError, match="model.shlo"):
            inference.load_compiled(open(path, "rb").read(),
                                    source=path)
