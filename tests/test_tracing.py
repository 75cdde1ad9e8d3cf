"""End-to-end distributed tracing + flight recorder (ISSUE 11).

Pins the tentpole contracts:

- span/context/carrier semantics (obs/tracing.py), jax-free;
- ONE trace_id end-to-end through the serving path — client span over
  the server's serve.request / queued / batch_form / dispatch tree,
  with durations that reconcile with the measured request latency,
  and per-token decode spans when the host rung runs;
- cross-process propagation under faults: a master-client RPC retried
  through FlakyProxy keeps one trace_id with per-attempt SIBLING
  spans under one RPC parent; a SIGKILL'd client's serving request
  still leaves a complete span record for the admitted phase;
- the flight recorder: ring bound, bundle schema, exactly ONE bundle
  per anomaly storm (rate limit + bounded dump dir), and
  tools/trace_view.py rendering a bundle into a critical path;
- the trainer's sampled-step span trees and the `metrics --spans`
  CLI mode.
"""

import copy
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from paddle_tpu.core import flags as _flags  # noqa: E402
from paddle_tpu.obs import flight_recorder as fr  # noqa: E402
from paddle_tpu.obs import metrics as om  # noqa: E402
from paddle_tpu.obs import tracing  # noqa: E402


@pytest.fixture
def global_recorder():
    """Ring-only flight recorder on the GLOBAL registry (the serving
    stack publishes there), detached afterwards."""
    rec = fr.enable_flight_recorder()
    try:
        yield rec
    finally:
        fr.disable_flight_recorder()


def _spans_by_name(rec):
    out = {}
    for s in rec.spans():
        out.setdefault(s["name"], []).append(s)
    return out


def _wait_spans(rec, name, n=1, timeout=10.0):
    """Span emission runs AFTER a request's result() unblocks (the
    scheduler publishes telemetry outside its lock) — poll briefly."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        by = _spans_by_name(rec)
        if len(by.get(name, ())) >= n:
            return by
        time.sleep(0.01)
    return _spans_by_name(rec)


# ===================================================== span semantics
class TestSpanAPI:
    def test_nesting_and_parentage(self):
        reg = om.MetricsRegistry()
        rec = fr.FlightRecorder(registry=reg)
        reg.attach_recorder(rec)
        with tracing.span("outer", registry=reg) as outer:
            with tracing.span("inner", registry=reg) as inner:
                assert inner.trace_id == outer.trace_id
                assert inner.parent_id == outer.span_id
        evs = rec.snapshot()
        assert [e["name"] for e in evs] == ["inner", "outer"]
        assert all(e["kind"] == "span" for e in evs)
        assert evs[1]["parent_id"] == ""

    def test_exception_marks_error_status(self):
        reg = om.MetricsRegistry()
        rec = fr.FlightRecorder(registry=reg)
        reg.attach_recorder(rec)
        with pytest.raises(ValueError):
            with tracing.span("boom", registry=reg):
                raise ValueError("x")
        assert rec.snapshot()[0]["status"] == "error"

    def test_carrier_inject_extract_attach(self):
        assert tracing.current() is None
        assert tracing.inject() is None
        carrier = {"trace_id": "t" * 32, "span_id": "s" * 16}
        with tracing.attach(carrier):
            assert tracing.current() == ("t" * 32, "s" * 16)
            assert tracing.inject() == carrier
        assert tracing.current() is None
        # malformed carriers degrade to untraced, never raise
        for bad in (None, 7, "x", {}, {"trace_id": 3}):
            assert tracing.extract(bad) is None
            with tracing.attach(bad):
                assert tracing.current() is None

    def test_spans_reach_event_stream(self, tmp_path):
        path = str(tmp_path / "sp.jsonl")
        om.enable_event_stream(path, flush_interval_s=30)
        try:
            with tracing.span("streamed", tag="v"):
                pass
            om.get_registry().stream.flush()
        finally:
            om.get_registry().attach_stream(None)
        recs = [json.loads(ln) for ln in open(path)]
        sp = next(r for r in recs if r.get("kind") == "span")
        assert sp["name"] == "streamed"
        assert sp["labels"] == {"tag": "v"}
        assert sp["dur_s"] >= 0 and "ts" in sp


# ===================================================== flight recorder
class TestFlightRecorder:
    def test_ring_is_bounded(self):
        reg = om.MetricsRegistry()
        rec = fr.FlightRecorder(registry=reg, capacity=16)
        reg.attach_recorder(rec)
        for i in range(100):
            reg.event("k", i=i)
        evs = rec.snapshot()
        assert len(evs) == 16
        assert evs[-1]["i"] == 99 and evs[0]["i"] == 84

    def test_bundle_schema_and_rate_limit(self, tmp_path):
        reg = om.MetricsRegistry()
        rec = fr.FlightRecorder(
            dump_dir=str(tmp_path), registry=reg,
            min_interval_s=60.0, max_bundles=8,
        )
        reg.attach_recorder(rec)
        reg.event("watchdog", event="skip", global_step=3)
        p1 = rec.maybe_dump("watchdog_skip", global_step=3)
        assert p1 and os.path.exists(p1)
        # storm: every further trigger inside the window is suppressed
        for _ in range(10):
            assert rec.maybe_dump("watchdog_skip") is None
        files = [f for f in os.listdir(str(tmp_path))
                 if f.endswith(".json")]
        assert len(files) == 1
        assert reg.counter("flight.dumps_suppressed").get(
            reason="watchdog_skip") == 10
        doc = json.load(open(p1))
        assert doc["schema"] == fr.BUNDLE_SCHEMA
        assert doc["reason"] == "watchdog_skip"
        assert doc["context"] == {"global_step": 3}
        assert any(e["kind"] == "watchdog" for e in doc["events"])
        assert doc["profile"] == {"captured": False}
        # the static bundle lint accepts the real artifact
        assert fr.check_bundle(p1) == []

    def test_dump_dir_is_bounded(self, tmp_path):
        reg = om.MetricsRegistry()
        rec = fr.FlightRecorder(
            dump_dir=str(tmp_path), registry=reg,
            min_interval_s=0.0, max_bundles=3,
        )
        for i in range(7):
            assert rec.maybe_dump(f"r{i}") is not None
        files = sorted(f for f in os.listdir(str(tmp_path))
                       if f.endswith(".json"))
        assert len(files) == 3
        assert files[-1].startswith("flight-00007")


# ============================================== serving end-to-end
class _EchoModel:
    can_host = False
    engine = None
    named_hooks = {}

    def __init__(self, delay_s=0.0):
        self.delay_s = delay_s

    def run_batch(self, ids, lens, hooks, host):
        if self.delay_s:
            time.sleep(self.delay_s)
        return [
            {"tokens": ids[i, : lens[i]].tolist(), "score": 0.0}
            for i in range(ids.shape[0])
        ]


def _serve_pair(delay_s=0.0, **cfg_kw):
    from paddle_tpu.serving.server import InferenceServer, ServeConfig
    from paddle_tpu.serving.tcp import ServingTCPServer

    cfg_kw.setdefault("max_queue", 16)
    cfg_kw.setdefault("max_batch", 2)
    server = InferenceServer(ServeConfig(**cfg_kw))
    server.add_model("echo", _EchoModel(delay_s=delay_s))
    tcp = ServingTCPServer(server)
    return server, tcp


# ======================================================= bundle lint
_SPAN = {"kind": "span", "name": "a", "trace_id": "t", "span_id": "s",
         "parent_id": "", "ts": 1.0, "dur_s": 0.1, "status": "ok"}


@pytest.fixture(scope="module")
def written_bundles(tmp_path_factory):
    """One bundle of each kind as the program writes it: a flight
    bundle dumped by a FlightRecorder whose ring holds real spans,
    and an incident bundle stitched by a FleetRouter's monitor over a
    live replica's flightz frame. Both pass the lint as written."""
    from paddle_tpu.serving.fleet import FleetConfig, FleetRouter

    tmp = tmp_path_factory.mktemp("bundles")
    rec = fr.enable_flight_recorder(dump_dir=str(tmp / "flight"),
                                    min_interval_s=0.0)
    server, tcp = _serve_pair()
    router = FleetRouter(
        {"r0": f"127.0.0.1:{tcp.port}"},
        FleetConfig(poll_interval_s=0.05, monitor=True,
                    incident_dir=str(tmp / "incidents"),
                    incident_min_interval_s=0.0),
    )
    try:
        router.call("echo", [1, 2], deadline_ms=20000, trace=True)
        _wait_spans(rec, "serve.request")
        flight = rec.maybe_dump("test", why="lint")
        incident = router.monitor._maybe_incident(
            router, [{"alert": "p99_slo", "replica": "r0"}])
    finally:
        router.close()
        tcp.stop()
        server.shutdown(drain=False)
        fr.disable_flight_recorder()
    docs = {}
    for kind, path in (("flight", flight), ("incident", incident)):
        assert path and fr.check_bundle(path) == [], (kind, path)
        with open(path) as f:
            docs[kind] = json.load(f)
    assert any(e.get("kind") == "span"
               for e in docs["incident"]["replicas"]["r0"]["events"])
    return docs


def _first_span(doc):
    return next(e for e in doc["events"] if e.get("kind") == "span")


class TestBundleLint:
    """`flight_recorder.check_bundle` (the `bundle` pass of
    tools/framework_lint.py): every field the two schemas require is
    required, by name."""

    @pytest.mark.parametrize("kind,field", [
        *(("flight", f) for f in fr.BUNDLE_REQUIRED_FIELDS),
        *(("incident", f) for f in fr.INCIDENT_REQUIRED_FIELDS),
        *(("span", f) for f in fr.SPAN_EVENT_FIELDS),
    ])
    def test_each_missing_field_is_named(self, written_bundles,
                                         tmp_path, kind, field):
        doc = copy.deepcopy(
            written_bundles["flight" if kind == "span" else kind])
        del (_first_span(doc) if kind == "span" else doc)[field]
        p = tmp_path / "b.json"
        p.write_text(json.dumps(doc))
        v = fr.check_bundle(str(p))
        assert any(repr(field) in x for x in v), v

    _SPOILED = {  # case: (bundle kind, what is done to it, violation)
        "flight_schema": (
            "flight", lambda d: d.update(schema="wrong/v0"), "schema"),
        "incident_schema": (
            "incident",
            lambda d: d.update(schema=d["schema"][:-1] + "0"), "schema"),
        "event_without_kind": (
            "flight", lambda d: d["events"].append({"no": "kind"}),
            "has no 'kind'"),
    }

    @pytest.mark.parametrize("case", [*_SPOILED, "not_json"])
    def test_malformed_is_refused(self, written_bundles, tmp_path,
                                  case):
        p = tmp_path / "b.json"
        if case == "not_json":
            p.write_text("not json")
            want = "unreadable bundle"
        else:
            kind, spoil, want = self._SPOILED[case]
            doc = copy.deepcopy(written_bundles[kind])
            spoil(doc)
            p.write_text(json.dumps(doc))
        v = fr.check_bundle(str(p))
        assert any(want in x for x in v), v

    def test_bundle_lint_cli(self, tmp_path):
        """`framework_lint.py bundle F...` exits 0 on well-formed
        bundles, 1 with the violation printed otherwise."""
        ok = tmp_path / "ok.json"
        ok.write_text(json.dumps({
            "schema": "paddle-tpu-flight-bundle/v1", "reason": "t",
            "ts": 1.0, "pid": 1, "seq": 1, "events": [_SPAN],
            "metrics": {}, "profile": {"captured": False},
        }))
        cmd = [sys.executable, "tools/framework_lint.py", "bundle"]
        r = subprocess.run(cmd + [str(ok)], cwd=REPO,
                           capture_output=True, text=True)
        assert r.returncode == 0, r.stderr
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"schema": "nope"}))
        r = subprocess.run(cmd + [str(ok), str(bad)], cwd=REPO,
                           capture_output=True, text=True)
        assert r.returncode == 1 and "schema" in r.stderr

    def test_bundle_lint_incident(self, tmp_path):
        """`check_bundle` dispatches on the incident schema tag and
        validates the cross-process stitch: required fields, typed
        alerts, the fleet stanza, and span events in EVERY ring (the
        router's own plus each replica's flightz dump)."""
        good = {
            "schema": "paddle-tpu-fleet-incident/v1",
            "reason": "burn_rate", "ts": 1.0, "pid": 1, "seq": 1,
            "alerts": [{"alert": "p99_slo", "p99_short_ms": 9.0}],
            "offending": "r1",
            "states": {}, "events": [_SPAN],
            "replicas": {"r1": {"pid": 2, "enabled": True,
                                "events": [_SPAN]}},
            "fleet": {"merged": {"counters": {}}, "delta": None,
                      "rates": None},
        }
        p = tmp_path / "incident-00001-burn_rate.json"
        p.write_text(json.dumps(good))
        assert fr.check_bundle(str(p)) == []
        # missing required field
        bad = dict(good)
        del bad["fleet"]
        p.write_text(json.dumps(bad))
        assert any("'fleet'" in x for x in fr.check_bundle(str(p)))
        # untyped alert entries
        p.write_text(json.dumps(dict(good, alerts=[{"oops": 1}])))
        assert any("alert" in x for x in fr.check_bundle(str(p)))
        # a replica ring with a malformed span event is caught too
        torn = dict(_SPAN)
        del torn["dur_s"]
        p.write_text(json.dumps(dict(
            good, replicas={"r1": {"events": [torn]}})))
        assert any("dur_s" in x for x in fr.check_bundle(str(p)))


class TestServeTraceEndToEnd:
    def test_one_trace_id_client_to_dispatch_reconciles(
        self, global_recorder
    ):
        """ISSUE 11 acceptance: one trace_id spans client ->
        admission -> batch formation -> dispatch, and the span
        durations reconcile with the request's measured latency."""
        from paddle_tpu.serving.tcp import ServeClient

        server, tcp = _serve_pair(delay_s=0.05)
        try:
            with ServeClient(f"127.0.0.1:{tcp.port}") as cl:
                out = cl.call("echo", [3, 4, 5], timeout=30,
                              trace=True)
            assert out["ok"], out
            assert out["trace_id"]
            by = _wait_spans(global_recorder, "serve.dispatch")
            for name in ("client.request", "serve.request",
                         "serve.queued", "serve.batch_form",
                         "serve.dispatch"):
                assert len(by[name]) == 1, by.keys()
            # one trace, correctly parented
            tids = {s["trace_id"] for ss in by.values() for s in ss}
            assert tids == {out["trace_id"]}
            client = by["client.request"][0]
            root = by["serve.request"][0]
            assert root["parent_id"] == client["span_id"]
            for child in ("serve.queued", "serve.batch_form",
                          "serve.dispatch"):
                assert by[child][0]["parent_id"] == root["span_id"]
            # durations reconcile: the phases cover the admitted
            # request up to dispatch end; the root covers them; the
            # client span covers the root; the wire latency matches
            # the root's duration
            phases = sum(by[n][0]["dur_s"] for n in
                         ("serve.queued", "serve.batch_form",
                          "serve.dispatch"))
            assert by["serve.dispatch"][0]["dur_s"] >= 0.05
            assert phases <= root["dur_s"] + 0.02
            assert root["dur_s"] >= 0.8 * phases
            assert client["dur_s"] >= root["dur_s"] - 0.002
            assert abs(root["dur_s"] * 1e3 - out["latency_ms"]) < 50
        finally:
            tcp.stop()
            server.shutdown(drain=True)

    def test_tracez_reports_slow_exemplars(self, global_recorder):
        from paddle_tpu.serving.tcp import ServeClient

        server, tcp = _serve_pair(delay_s=0.03)
        try:
            with ServeClient(f"127.0.0.1:{tcp.port}") as cl:
                out = cl.call("echo", [1, 2], timeout=30, trace=True)
                deadline = time.monotonic() + 10
                tz = cl.tracez(top=5, timeout=30)
                while not tz["tracez"] and time.monotonic() < deadline:
                    time.sleep(0.02)  # exemplars publish post-lock
                    tz = cl.tracez(top=5, timeout=30)
            assert tz["ok"]
            ex = tz["tracez"]
            assert len(ex) >= 1
            assert ex[0]["latency_ms"] >= 30
            assert ex[0]["trace_id"] == out["trace_id"]
            assert {"queued_ms", "dispatch_ms", "model",
                    "path"} <= set(ex[0])
        finally:
            tcp.stop()
            server.shutdown(drain=True)

    def test_untraced_request_emits_no_spans(self, global_recorder):
        from paddle_tpu.serving.tcp import ServeClient

        server, tcp = _serve_pair()
        try:
            with ServeClient(f"127.0.0.1:{tcp.port}") as cl:
                out = cl.call("echo", [1], timeout=30)
            assert out["ok"]
            assert "trace_id" not in out
            assert global_recorder.spans() == []
        finally:
            tcp.stop()
            server.shutdown(drain=True)

    def test_anonymous_sampling_via_flag(self, global_recorder):
        server, tcp = _serve_pair()
        _flags.set_flag("trace_serve_period", 2)
        try:
            pend = [server.submit("echo", [1, 2]) for _ in range(4)]
            for p in pend:
                p.result(timeout=30)
            roots = _wait_spans(global_recorder, "serve.request",
                                n=2).get("serve.request", [])
            assert len(roots) == 2  # every 2nd anonymous request
        finally:
            _flags.set_flag("trace_serve_period", 0)
            tcp.stop()
            server.shutdown(drain=True)

    def test_decode_rung_spans_under_dispatch(self, global_recorder):
        """The host-stepped per-token decode rung emits decode.token
        spans nested under the batch's dispatch span — the tail of
        the client -> ... -> decode chain."""
        from paddle_tpu import dsl
        from paddle_tpu.beam_search import BeamSearchDecoder, BeamHooks
        from paddle_tpu.core.config import ParameterConf
        from paddle_tpu.serving.models import GenerationModel
        from paddle_tpu.serving.server import (
            InferenceServer,
            ServeConfig,
        )
        import jax.numpy as jnp

        vocab, max_len = 16, 4

        def step(word):
            emb = dsl.embedding(
                word, size=vocab, vocab_size=vocab,
                param=ParameterConf(name="trace_bigram"),
            )
            return dsl.mixed(vocab, [(emb, "identity")],
                             act="softmax", bias=False, name="prob")

        dec = BeamSearchDecoder(step, n_static=0, bos_id=0, eos_id=1,
                                beam_size=2, max_length=max_len)
        rng = np.random.default_rng(0)
        params = {"trace_bigram": jnp.asarray(
            rng.standard_normal((vocab, vocab)).astype(np.float32)
        )}
        model = GenerationModel(
            dec, params,
            named_hooks={"noop": BeamHooks()},  # forces the host rung
        )
        server = InferenceServer(ServeConfig(max_queue=8, max_batch=1))
        server.add_model("gen", model)
        try:
            req = server.submit(
                "gen", [2, 3], deadline_s=120.0, hooks_name="noop",
                trace={"trace_id": tracing.new_trace_id(),
                       "span_id": ""},
            )
            out = req.result(timeout=120)
            assert out["path"] == "host"
            by = _wait_spans(global_recorder, "serve.dispatch")
            toks = by.get("decode.token", [])
            assert 1 <= len(toks) <= max_len
            disp = by["serve.dispatch"][0]
            assert all(t["parent_id"] == disp["span_id"]
                       for t in toks)
            assert all(t["trace_id"] == disp["trace_id"]
                       for t in toks)
        finally:
            server.shutdown(drain=True)

    def test_decode_chunk_spans_under_multi_token_dispatch(
        self, global_recorder
    ):
        """Under multi-token dispatch (ISSUE 18) the host rung's
        per-token decode.token spans become per-CHUNK decode.chunk
        spans carrying a `tokens` label, still parented under the
        batch's dispatch span — so trace_view critical paths and the
        serve-row span split keep reconciling: the decode rung's time
        is covered by chunk spans instead of token spans, never
        double-counted by both."""
        from paddle_tpu import dsl
        from paddle_tpu.beam_search import BeamSearchDecoder, BeamHooks
        from paddle_tpu.core.config import ParameterConf
        from paddle_tpu.serving.models import GenerationModel
        from paddle_tpu.serving.server import (
            InferenceServer,
            ServeConfig,
        )
        import jax.numpy as jnp

        vocab, max_len, k_tok = 16, 6, 4

        def step(word):
            emb = dsl.embedding(
                word, size=vocab, vocab_size=vocab,
                param=ParameterConf(name="trace_bigram_mt"),
            )
            return dsl.mixed(vocab, [(emb, "identity")],
                             act="softmax", bias=False, name="prob")

        dec = BeamSearchDecoder(step, n_static=0, bos_id=0, eos_id=1,
                                beam_size=2, max_length=max_len,
                                tokens_per_dispatch=k_tok)
        rng = np.random.default_rng(0)
        table = rng.standard_normal((vocab, vocab)).astype(np.float32)
        table[:, 1] = -50.0  # no eos: full max_len walk, 2 chunks
        params = {"trace_bigram_mt": jnp.asarray(table)}
        model = GenerationModel(
            dec, params,
            # empty hooks force the host rung but carry no callbacks,
            # so the chunked path is eligible
            named_hooks={"noop": BeamHooks()},
        )
        server = InferenceServer(ServeConfig(max_queue=8, max_batch=1))
        server.add_model("gen", model)
        try:
            req = server.submit(
                "gen", [2, 3], deadline_s=120.0, hooks_name="noop",
                trace={"trace_id": tracing.new_trace_id(),
                       "span_id": ""},
            )
            out = req.result(timeout=120)
            assert out["path"] == "host"
            by = _wait_spans(global_recorder, "serve.dispatch")
            chunks = by.get("decode.chunk", [])
            assert by.get("decode.token", []) == []
            # ceil(6/4) = 2 chunks covering all max_len tokens
            assert len(chunks) == 2
            assert sorted(c["labels"]["tokens"] for c in chunks) \
                == [2, 4]
            disp = by["serve.dispatch"][0]
            assert all(c["parent_id"] == disp["span_id"]
                       for c in chunks)
            assert all(c["trace_id"] == disp["trace_id"]
                       for c in chunks)
        finally:
            server.shutdown(drain=True)


# ===================================== cross-process / fault coverage
@pytest.mark.faults
class TestTracePropagationUnderFaults:
    def test_master_rpc_retries_are_sibling_spans(
        self, global_recorder
    ):
        """A master RPC retried through FlakyProxy keeps ONE trace_id,
        with each attempt a sibling child span under the one RPC
        parent — a retry storm reads as one operation."""
        from conftest import start_master
        from paddle_tpu.data.master_client import MasterClient
        from paddle_tpu.testing_faults import FlakyProxy

        master, port = start_master()
        carrier = {"trace_id": tracing.new_trace_id(),
                   "span_id": tracing.new_span_id()}
        try:
            with FlakyProxy(("127.0.0.1", port)) as proxy:
                proxy.reset_next(2)  # first two attempts get RST
                c = MasterClient(f"127.0.0.1:{proxy.port}",
                                 retry_seconds=30.0,
                                 trace_carrier=carrier)
                c.start_pass()
                c.close()
            by = _spans_by_name(global_recorder)
            rpcs = by["master.start_pass"]
            assert len(rpcs) == 1
            rpc = rpcs[0]
            assert rpc["trace_id"] == carrier["trace_id"]
            assert rpc["parent_id"] == carrier["span_id"]
            assert rpc["status"] == "ok"
            atts = by["master.attempt"]
            assert len(atts) == 3  # 2 RST'd + 1 clean
            assert all(a["parent_id"] == rpc["span_id"] for a in atts)
            assert all(a["trace_id"] == carrier["trace_id"]
                       for a in atts)
            ok = [a for a in atts if a["status"] == "ok"]
            failed = [a for a in atts if a["status"] != "ok"]
            assert len(ok) == 1 and len(failed) == 2
            # sibling attempts carry their attempt index labels
            assert sorted(a["labels"]["attempt"] for a in atts) \
                == [0, 1, 2]
        finally:
            from paddle_tpu.data.master_client import MasterClient as MC

            MC(f"127.0.0.1:{port}", retry_seconds=2).shutdown()
            master.wait(timeout=10)

    def test_untraced_master_rpc_emits_nothing(self, global_recorder):
        from paddle_tpu.data.master_client import (
            MasterClient,
            MasterRetryTimeout,
        )

        c = MasterClient("127.0.0.1:1", retry_seconds=0.3,
                         connect_timeout=0.2)
        with pytest.raises(MasterRetryTimeout):
            c.start_pass()
        assert global_recorder.spans() == []

    def test_sigkilled_client_leaves_complete_span_record(
        self, global_recorder
    ):
        """SIGKILL the CLIENT mid-request: the server still finishes
        the admitted request, and its span record for the admitted
        phase (request root + queued/batch_form/dispatch) is
        complete on this side."""
        server, tcp = _serve_pair(delay_s=0.5)
        carrier = {"trace_id": tracing.new_trace_id(),
                   "span_id": tracing.new_span_id()}
        client_src = (
            "import json, sys\n"
            "sys.path.insert(0, %r)\n"
            "from paddle_tpu.serving.tcp import send_msg\n"
            "import socket\n"
            "s = socket.create_connection(('127.0.0.1', %d))\n"
            "send_msg(s, {'model': 'echo', 'ids': [1, 2, 3],\n"
            "             'deadline_ms': 60000, 'trace': %s})\n"
            "print('SENT', flush=True)\n"
            "import time; time.sleep(60)\n"
        ) % (REPO, tcp.port, json.dumps(carrier))
        proc = subprocess.Popen(
            [sys.executable, "-c", client_src], cwd=REPO,
            stdout=subprocess.PIPE, text=True,
        )
        try:
            assert proc.stdout.readline().strip() == "SENT"
            proc.send_signal(signal.SIGKILL)  # client vanishes
            proc.wait()
            deadline = time.monotonic() + 30
            by = {}
            while time.monotonic() < deadline:
                by = _spans_by_name(global_recorder)
                if "serve.request" in by:
                    break
                time.sleep(0.05)
            root = by["serve.request"][0]
            assert root["trace_id"] == carrier["trace_id"]
            assert root["parent_id"] == carrier["span_id"]
            assert root["status"] == "ok"
            assert root["dur_s"] >= 0.5  # covered the full dispatch
            for child in ("serve.queued", "serve.batch_form",
                          "serve.dispatch"):
                assert by[child][0]["parent_id"] == root["span_id"]
            assert server.stats()["completed"] == 1  # nothing leaked
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
            tcp.stop()
            server.shutdown(drain=True)

    def test_breaker_open_emits_exactly_one_bundle(self, tmp_path):
        """An injected breaker-open dumps exactly ONE flight bundle
        (rate-limited, bounded dir) that trace_view renders into a
        critical path — the no-dump-storm acceptance test."""
        import trace_view
        from paddle_tpu.serving.server import (
            InferenceServer,
            ServeConfig,
            ServeError,
            ServeRejected,
        )

        dump_dir = str(tmp_path / "flight")
        rec = fr.enable_flight_recorder(
            dump_dir=dump_dir, min_interval_s=300.0, max_bundles=4,
        )

        class Bad:
            can_host = False
            engine = None
            named_hooks = {}

            def run_batch(self, *a):
                raise RuntimeError("poisoned program")

        server = InferenceServer(ServeConfig(
            max_queue=8, max_batch=1, breaker_threshold=2,
            breaker_reset_s=60.0,
        ))
        server.add_model("bad", Bad())
        try:
            # a storm: failures open the breaker, then quarantine
            # sheds keep arriving — still one bundle
            for _ in range(8):
                try:
                    r = server.submit(
                        "bad", [1, 2], deadline_s=5.0,
                        trace={"trace_id": tracing.new_trace_id(),
                               "span_id": ""},
                    )
                    r.result(timeout=10)
                except (ServeError, ServeRejected):
                    pass
            server.shutdown(drain=True)
            bundles = [f for f in os.listdir(dump_dir)
                       if f.endswith(".json")]
            assert len(bundles) == 1, bundles
            path = os.path.join(dump_dir, bundles[0])
            doc = json.load(open(path))
            assert doc["reason"] == "breaker_open"
            assert doc["context"] == {"model": "bad"}
            # the bundle renders into per-request critical paths
            report = trace_view.analyze([path], top=5)
            assert report["trace_count"] >= 2
            top = report["traces"][0]
            assert top["root"] == "serve.request"
            seg_names = {s["name"] for s in top["critical_path"]}
            assert "serve.queued" in seg_names
            assert "serve.dispatch" in seg_names
            # the bundle lint accepts it
            assert fr.check_bundle(path) == []
        finally:
            fr.disable_flight_recorder()


class TestBreakerOpenOnRescuedDispatch:
    def test_host_fallback_rescue_still_fires_breaker_dump(
        self, tmp_path
    ):
        """A jit failure rescued by the host fallback still counts
        toward the breaker; when that count OPENS it, the flight dump
        must fire even though the dispatch ultimately succeeded (the
        success path, not just the except path, checks for opens)."""
        from paddle_tpu.serving.server import (
            InferenceServer,
            ServeConfig,
        )

        rec = fr.enable_flight_recorder(
            dump_dir=str(tmp_path), min_interval_s=300.0,
        )

        class JitPoisoned:
            can_host = True
            engine = None
            named_hooks = {}

            def run_batch(self, ids, lens, hooks, host):
                if not host:
                    raise RuntimeError("jit program poisoned")
                return [{"tokens": [1], "score": 0.0}
                        for _ in range(ids.shape[0])]

        server = InferenceServer(ServeConfig(
            max_queue=8, max_batch=1, breaker_threshold=1,
            breaker_reset_s=60.0, host_fallback=True,
        ))
        server.add_model("jp", JitPoisoned())
        try:
            out = server.submit("jp", [1, 2]).result(timeout=30)
            assert out["path"] == "host"  # the rescue worked
            # the dump fires after the request has completed, and its
            # bundle is written as a `.tmp` then renamed: wait for the
            # renamed bundle itself, not for the directory to fill
            def bundles():
                return [f for f in os.listdir(str(tmp_path))
                        if f.endswith(".json")]

            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and not bundles():
                time.sleep(0.02)
            assert len(bundles()) == 1, os.listdir(str(tmp_path))
            doc = json.load(open(os.path.join(str(tmp_path),
                                              bundles()[0])))
            assert doc["reason"] == "breaker_open"
            assert doc["context"] == {"model": "jp"}
        finally:
            fr.disable_flight_recorder()
            server.shutdown(drain=True)


class TestAnomalyWatch:
    """The serving-side dump triggers, unit-level: thresholds come
    from flags, firing goes through the (rate-limited) recorder."""

    def test_shed_spike_fires_once_per_window(self, tmp_path):
        from paddle_tpu.serving.server import _AnomalyWatch

        rec = fr.enable_flight_recorder(
            dump_dir=str(tmp_path), min_interval_s=300.0,
        )
        prev = (_flags.get_flag("serve_shed_rate_threshold"),
                _flags.get_flag("serve_shed_window_s"))
        _flags.set_flag("serve_shed_rate_threshold", 0.5)
        _flags.set_flag("serve_shed_window_s", 0.05)
        try:
            w = _AnomalyWatch()
            # 30 decisions, 60% shed, then roll the window
            for i in range(30):
                w.admission(shed=(i % 5 < 3))
            time.sleep(0.06)
            w.admission(shed=True)  # closes the window -> evaluates
            bundles = [f for f in os.listdir(str(tmp_path))
                       if f.endswith(".json")]
            assert len(bundles) == 1
            doc = json.load(open(os.path.join(str(tmp_path),
                                              bundles[0])))
            assert doc["reason"] == "shed_spike"
            assert doc["context"]["shed_rate"] >= 0.5
        finally:
            _flags.set_flag("serve_shed_rate_threshold", prev[0])
            _flags.set_flag("serve_shed_window_s", prev[1])
            fr.disable_flight_recorder()

    def test_p99_slo_breach_fires(self, tmp_path):
        from paddle_tpu.serving.server import _AnomalyWatch

        rec = fr.enable_flight_recorder(
            dump_dir=str(tmp_path), min_interval_s=300.0,
        )
        prev = _flags.get_flag("serve_p99_slo_ms")
        _flags.set_flag("serve_p99_slo_ms", 100)
        try:
            w = _AnomalyWatch()
            for _ in range(25):
                w.latency(0.05)  # under the SLO: no dump
            assert not os.listdir(str(tmp_path))
            for _ in range(25):
                w.latency(0.5)  # p99 over 100ms
            bundles = os.listdir(str(tmp_path))
            assert len(bundles) == 1
            doc = json.load(open(os.path.join(str(tmp_path),
                                              bundles[0])))
            assert doc["reason"] == "slo_breach"
            assert doc["context"]["p99_ms"] > 100
        finally:
            _flags.set_flag("serve_p99_slo_ms", prev)
            fr.disable_flight_recorder()

    def test_slo_disabled_by_default(self):
        from paddle_tpu.serving.server import _AnomalyWatch

        w = _AnomalyWatch()
        for _ in range(50):
            w.latency(10.0)  # would breach any real SLO; flag is 0


# ======================================================= trainer spans
class TestTrainerStepSpans:
    def test_every_step_emits_a_span_tree(self, global_recorder):
        """(tests/test_train_spans.py holds the tree's shape; this is
        the stream's view: one trace a train() call, a root a step.)"""
        from paddle_tpu import dsl
        from paddle_tpu.core.config import OptimizationConf
        from paddle_tpu.data import reader as R
        from paddle_tpu.data.feeder import (
            DataFeeder,
            dense_vector,
            integer_value,
        )
        from paddle_tpu.trainer import SGD

        prev = _flags.get_flag("timeline_sample_period")
        _flags.set_flag("timeline_sample_period", 4)
        try:
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
        finally:
            _flags.set_flag("timeline_sample_period", prev)
        by = _spans_by_name(global_recorder)
        steps = by["train.step"]
        assert len(steps) == 12  # every step, fenced or not
        assert {s["trace_id"] for s in steps} == {t.last_trace_id}
        kids = [s for s in global_recorder.spans()
                if s["parent_id"] == steps[3]["span_id"]]
        # a batch's placement (`train.h2d`) is this step's child or
        # the step's before: tests/test_train_spans.py holds where
        assert {k["name"] for k in kids} - {"train.h2d"} == {
            "train.input_wait.feeder",
            "train.dispatch", "train.fetch", "train.fence",
            "train.handlers",
        }
        assert len(by["train.h2d"]) == 12
        assert len(by["train.fence"]) == 3  # 12 steps / period 4
        assert steps[-1]["labels"]["step_num"] == 11
        assert steps[-1]["labels"]["batch_id"] == 5


# ========================================================== CLI modes
class TestSpanCLI:
    def _write_stream(self, path):
        s = om.EventStream(path, flush_interval_s=30)
        tid = tracing.new_trace_id()
        root = tracing.new_span_id()
        s.emit({"kind": "span", "name": "serve.request",
                "trace_id": tid, "span_id": root, "parent_id": "",
                "ts": 100.0, "dur_s": 0.2, "status": "ok",
                "labels": {}})
        for i, (name, t0, d) in enumerate([
            ("serve.queued", 100.0, 0.15),
            ("serve.dispatch", 100.15, 0.05),
        ]):
            s.emit({"kind": "span", "name": name, "trace_id": tid,
                    "span_id": f"c{i}", "parent_id": root, "ts": t0,
                    "dur_s": d, "status": "ok", "labels": {}})
        s.emit({"kind": "timeline", "pass_id": 0})
        s.close()
        return tid

    def test_metrics_spans_mode_is_jax_free(self, tmp_path):
        """`python -m paddle_tpu metrics --stream F --spans` prints
        the per-span-name p50/p99 table + slowest traces with jax
        BLOCKED (the jax-free CLI contract)."""
        path = str(tmp_path / "ev.jsonl")
        tid = self._write_stream(path)
        blocker = str(tmp_path / "jax.py")
        with open(blocker, "w") as f:
            f.write("raise ImportError('jax blocked for this test')\n")
        env = dict(os.environ,
                   PYTHONPATH=str(tmp_path) + os.pathsep + REPO)
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu", "metrics",
             "--stream", path, "--spans"],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=120,
        )
        assert r.returncode == 0, r.stderr
        assert "serve.request" in r.stdout
        assert tid[:16] in r.stdout
        rj = subprocess.run(
            [sys.executable, "-m", "paddle_tpu", "metrics",
             "--stream", path, "--spans", "--json"],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=120,
        )
        doc = json.loads(rj.stdout)
        assert doc["span_count"] == 3
        names = {r["name"] for r in doc["by_name"]}
        assert names == {"serve.request", "serve.queued",
                         "serve.dispatch"}
        slow = doc["slowest_traces"][0]
        assert slow["trace_id"] == tid and slow["spans"] == 3

    def test_trace_view_on_stream(self, tmp_path):
        import trace_view

        path = str(tmp_path / "ev.jsonl")
        tid = self._write_stream(path)
        report = trace_view.analyze([path], top=5)
        assert report["trace_count"] == 1
        t = report["traces"][0]
        assert t["trace_id"] == tid
        assert t["dur_ms"] == 200.0
        names = [s["name"] for s in t["critical_path"]]
        assert names == ["serve.queued", "serve.dispatch"]
        fracs = sum(s["frac"] for s in t["critical_path"])
        assert fracs == pytest.approx(1.0, abs=0.01)
        # --trace prefix selection + text rendering
        report2 = trace_view.analyze([path], trace_id=tid[:8])
        assert report2["traces"][0]["trace_id"] == tid
        text = trace_view.render(report)
        assert "serve.queued" in text and "100.0%" not in text
