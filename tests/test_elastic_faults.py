"""Elastic training under injected faults.

The fault-tolerance tier the reference built on etcd (go/master
task re-lease service.go:313, snapshot recovery service.go:166-207,
per-shard pserver checkpoints go/pserver/service.go:76-126) — here
exercised end to end: a trainer SIGKILLed mid-pass under the networked
master, torn checkpoint shards, a master reachable only through a
fault-injecting proxy. Faults come from `paddle_tpu.testing_faults`;
checkpoints from `paddle_tpu.trainer.async_checkpoint`.

Everything here runs on the CPU mesh in tier-1 — elasticity is a
correctness property, not a hardware property.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# fault-injection tier: run_suite.sh runs this in its own
# timeout-guarded shard (pytest.ini `faults` marker)
pytestmark = pytest.mark.faults


# =====================================================================
# (a) SIGKILL a trainer mid-pass under the networked master
# =====================================================================
#
# Worker: a REAL SGD trainer (tiny fc classifier) feeding from the
# elastic reader over a networked MasterClient. If HANG_AT is set, the
# record decode hook hangs forever when it sees that record id — the
# worker then holds a chunk lease until the parent SIGKILLs it.
TRAINER_WORKER_SRC = """
import json, os, pickle, sys, time
sys.path.insert(0, os.environ["REPO"])
import jax
jax.config.update("jax_platforms", "cpu")

from paddle_tpu import dsl
from paddle_tpu.core.config import OptimizationConf
from paddle_tpu.data import reader as R
from paddle_tpu.data.feeder import DataFeeder, dense_vector, integer_value
from paddle_tpu.data.master_client import MasterClient
from paddle_tpu.trainer import EndIteration, SGD

addr = os.environ["ADDR"]
out = open(os.environ["OUT_FILE"], "a")
hang_at = os.environ.get("HANG_AT")

class LoggingClient(MasterClient):
    # record which chunk ids THIS worker acked (exactly-once audit)
    def get_task(self):
        t = super().get_task()
        if t is not None:
            self._leases = getattr(self, "_leases", {})
            self._leases[t[0]] = json.loads(t[1])["chunk"]
        return t

    def task_done(self, task_id):
        ok = super().task_done(task_id)
        if ok:
            out.write(json.dumps(
                {"acked_chunk": self._leases[task_id]}) + "\\n")
            out.flush()
        return ok

def decode(raw):
    rec = pickle.loads(raw)
    if hang_at is not None and rec[2] == int(hang_at):
        time.sleep(3600)  # crash point: parent SIGKILLs us mid-lease
    return rec[:2]

with dsl.model() as g:
    x = dsl.data("x", (4,))
    y = dsl.data("y", (1,), is_ids=True)
    outl = dsl.fc(x, size=2, name="output")
    dsl.classification_cost(outl, y)
trainer = SGD(g.conf, OptimizationConf(
    learning_method="sgd", learning_rate=0.1), seed=7)
feeder = DataFeeder({"x": 0, "y": 1},
                    {"x": dense_vector(4), "y": integer_value(2)})

def handler(e):
    if isinstance(e, EndIteration):
        out.write(json.dumps({"loss": e.cost}) + "\\n")
        out.flush()

reader = R.batched(R.elastic(LoggingClient(addr), decode=decode), 4,
                   drop_last=False)
trainer.train(reader=reader, feeder=feeder, num_passes=1,
              event_handler=handler)
assert MasterClient(addr).pass_finished()
out.write(json.dumps({"done": True}) + "\\n")
out.flush()
"""


def _write_record_file(tmp_path, n=48, dim=4):
    """Pickled (x, y, record_id) tuples in small recordio chunks."""
    import pickle

    from paddle_tpu.native.recordio import RecordWriter, count_chunks

    rng = np.random.default_rng(0)
    W = rng.standard_normal((dim, 2))
    path = str(tmp_path / "train.rec")
    with RecordWriter(path, max_chunk_bytes=600) as w:
        for i in range(n):
            x = rng.standard_normal(dim).astype(np.float32)
            w.write(pickle.dumps(
                (x.tolist(), int(np.argmax(x @ W)), i)))
    return path, count_chunks(path)


def _start_trainer_worker(addr, out_file, hang_at=None):
    env = dict(os.environ, REPO=REPO, ADDR=addr, OUT_FILE=out_file)
    if hang_at is not None:
        env["HANG_AT"] = str(hang_at)
    return subprocess.Popen(
        [sys.executable, "-c", TRAINER_WORKER_SRC], env=env, cwd=REPO,
        stderr=subprocess.PIPE, text=True,
    )


def _acked_chunks(*files):
    out = []
    for f in files:
        if os.path.exists(f):
            out += [json.loads(l)["acked_chunk"]
                    for l in open(f).read().splitlines()
                    if "acked_chunk" in l]
    return out


def test_sigkill_trainer_mid_pass_survivor_finishes(tmp_path):
    """Trainer A (real SGD loop) is SIGKILLed holding a chunk lease;
    its lease expires, the chunk is re-served, and trainer B finishes
    the pass with every chunk acked exactly once — the Go master's
    requeue semantics (service.go:313-356) under an actual training
    load, not a synthetic task loop."""
    from conftest import start_master

    from paddle_tpu.data.master_client import MasterClient
    from paddle_tpu.testing_faults import kill_process

    path, n_chunks = _write_record_file(tmp_path)
    assert n_chunks >= 4
    # records per chunk ~5: A trains through chunks 0-1, hangs on the
    # first record of chunk 2 (record ids are sequential)
    hang_record = None
    master, port = start_master(lease="0.6")
    addr = f"127.0.0.1:{port}"
    out_a = str(tmp_path / "a.jsonl")
    out_b = str(tmp_path / "b.jsonl")
    wa = wb = None
    try:
        c = MasterClient(addr)
        c.add_chunk_tasks(path, n_chunks)
        # find the first record of chunk 2 by reading chunk 2 alone
        from paddle_tpu.native.recordio import RecordReader
        import pickle

        with RecordReader(path, start_chunk=2,
                          step_chunk=n_chunks) as rd:
            hang_record = pickle.loads(next(iter(rd)))[2]

        wa = _start_trainer_worker(addr, out_a, hang_at=hang_record)
        # A trains through chunks 0-1; acking chunk 1 and leasing
        # chunk 2 (whose first record hangs it) happen in the same
        # reader pull, so "chunk 1 acked" == "A is parked on its lease"
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            if sorted(_acked_chunks(out_a)) == [0, 1]:
                break
            time.sleep(0.1)
        else:
            pytest.fail(f"worker A never reached the hang chunk: "
                        f"{c.counts}, acked={_acked_chunks(out_a)}")
        time.sleep(0.3)  # let the lease registration settle

        wb = _start_trainer_worker(addr, out_b)
        kill_process(wa)  # SIGKILL mid-pass, lease still held

        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if c.pass_finished():
                break
            time.sleep(0.2)
        assert c.pass_finished(), c.counts

        _, err = wb.communicate(timeout=60)
        assert wb.returncode == 0, f"survivor failed:\n{err[-3000:]}"

        acked = _acked_chunks(out_a, out_b)
        assert sorted(acked) == list(range(n_chunks)), (
            f"chunks acked {sorted(acked)} != exactly once each"
        )
        # the torn lease really was re-served to the survivor
        assert 2 in _acked_chunks(out_b)
        counts = c.counts
        assert counts["done"] == n_chunks and counts["discarded"] == 0
        # the survivor truly trained (losses recorded), not just acked
        losses = [json.loads(l)["loss"]
                  for l in open(out_b).read().splitlines()
                  if "loss" in l]
        assert len(losses) >= 2
    finally:
        for p in (wa, wb):
            if p is not None and p.poll() is None:
                p.kill()
                p.wait()
        MasterClient(addr, retry_seconds=1).shutdown()
        master.wait(timeout=10)


# =====================================================================
# (b) async sharded resume reproduces the synchronous-resume loss curve
# =====================================================================


def _tiny_conf():
    from paddle_tpu import dsl

    with dsl.model() as g:
        x = dsl.data("x", (6,))
        y = dsl.data("y", (1,), is_ids=True)
        h = dsl.fc(x, size=8, act="tanh")
        out = dsl.fc(h, size=3, name="output")
        dsl.classification_cost(out, y)
    return g.conf


def _fixed_batches(n=64, dim=6, classes=3):
    rng = np.random.default_rng(5)
    W = rng.standard_normal((dim, classes))
    xs = rng.standard_normal((n, dim)).astype(np.float32)
    ys = np.argmax(xs @ W, axis=1).astype(np.int64)
    data = [(xs[i], int(ys[i])) for i in range(n)]

    def reader():
        yield from data

    return reader


def _feeder():
    from paddle_tpu.data.feeder import (
        DataFeeder,
        dense_vector,
        integer_value,
    )

    return DataFeeder({"x": 0, "y": 1},
                      {"x": dense_vector(6), "y": integer_value(3)})


def _train_save_resume_curve(save_dir, mode):
    """Train 2 passes saving in `mode`, restart a FRESH trainer from
    the checkpoint, train 2 more passes, return the post-resume
    per-batch loss curve."""
    from paddle_tpu.core.config import OptimizationConf
    from paddle_tpu.data import reader as rd
    from paddle_tpu.trainer import EndIteration, SGD

    conf = _tiny_conf()
    opt = OptimizationConf(learning_method="adam", learning_rate=0.05)
    feeder = _feeder()
    batches = rd.batched(_fixed_batches(), 8)

    t1 = SGD(conf, opt, seed=11)
    t1.train(reader=batches, feeder=feeder, num_passes=2,
             save_dir=save_dir, checkpoint_mode=mode)

    t2 = SGD(conf, opt, seed=11)
    start = t2.resume(save_dir)
    assert start == 2
    losses = []

    def handler(e):
        if isinstance(e, EndIteration):
            losses.append(e.cost)

    t2.train(reader=batches, feeder=feeder, num_passes=4,
             start_pass=start, event_handler=handler,
             checkpoint_mode=mode)
    return losses


def test_async_resume_matches_sync_resume_loss_curve(tmp_path):
    """Async-vs-sync resume curve equality — runs with the PERSISTENT
    XLA COMPILATION CACHE DISABLED, which is the fix for the ~15%
    flake this test carried since r6/PR7 (ROADMAP 5c).

    Root cause (PR11 investigation, reproduced 7/20 trials with the
    cache on and min_compile_time_secs=0, 0/20 with it off): on this
    jax/XLA CPU runtime, DESERIALIZING an executable from the
    persistent compilation cache sometimes yields a corrupted program
    — the same defect family as the heap corruption the conftest's
    fresh-per-session cache dir works around. A resumed trainer is
    exactly the consumer that recompiles an identical train step
    in-process (fresh SGD -> fresh jit closure -> in-memory cache
    miss -> persistent-cache DESERIALIZE), and the corrupt program
    computes a deterministic wrong loss (1.6864 on the first resumed
    batch in this config; the historical 1.26577 at batch 2) or
    outright NaNs — flight-recorder bundles from divergent runs show
    `watchdog skip, loss=nan` on the first post-resume batches while
    the restored params are bit-identical and the data unmutated.
    Which ARM got the corrupt program varied trial-to-trial (the
    min-compile-time gate is measured wall time, hence the
    nondeterministic ~15%), so retrying could never fix it: this test
    pins bit-exact numerics between two in-process trainers, and the
    cache breaks bit-exactness at the executable level. Disabling the
    cache for this test removes the environmental corruption while
    every other test keeps the compile-speed win."""
    import jax

    prev_cache = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    try:
        sync = _train_save_resume_curve(str(tmp_path / "sync"), "sync")
        async_ = _train_save_resume_curve(
            str(tmp_path / "async"), "async"
        )
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_cache)
    assert len(sync) == len(async_) == 16  # 2 passes x 8 batches
    np.testing.assert_allclose(async_, sync, rtol=0, atol=1e-6)


def test_async_save_overlaps_and_loads_back(tmp_path):
    """The async writer commits every pass (manifest-complete) and the
    trainer-facing load returns bit-identical params to what was
    saved."""
    import jax

    from paddle_tpu.core.config import OptimizationConf
    from paddle_tpu.data import reader as rd
    from paddle_tpu.trainer import SGD
    from paddle_tpu.trainer import async_checkpoint as actp

    save_dir = str(tmp_path / "ckpt")
    t = SGD(_tiny_conf(),
            OptimizationConf(learning_method="sgd", learning_rate=0.1),
            seed=1)
    t.train(reader=rd.batched(_fixed_batches(), 8), feeder=_feeder(),
            num_passes=3, save_dir=save_dir, checkpoint_mode="async")
    assert actp.list_passes(save_dir) == [0, 1, 2]
    for p in actp.list_passes(save_dir):
        ok, reason = actp.verify_pass(save_dir, p)
        assert ok, reason
    tree, meta = actp.load_pass(save_dir)
    assert meta["pass_id"] == 2
    want = jax.device_get(t.params)
    for name, arr in tree["params"].items():
        np.testing.assert_array_equal(arr, want[name])


# =====================================================================
# (c) torn/partial checkpoints are rejected; loader falls back
# =====================================================================


def test_torn_shard_falls_back_to_previous_pass(tmp_path):
    from paddle_tpu.core.config import OptimizationConf
    from paddle_tpu.data import reader as rd
    from paddle_tpu.testing_faults import corrupt_file, truncate_file
    from paddle_tpu.trainer import SGD
    from paddle_tpu.trainer import async_checkpoint as actp

    save_dir = str(tmp_path / "ckpt")
    t = SGD(_tiny_conf(),
            OptimizationConf(learning_method="sgd", learning_rate=0.1),
            seed=2)
    t.train(reader=rd.batched(_fixed_batches(), 8), feeder=_feeder(),
            num_passes=3, save_dir=save_dir, checkpoint_mode="async")

    # SIGKILL-mid-write: the newest shard is torn (truncated)
    shard2 = os.path.join(save_dir, "pass-00002", "shard-p0.npz")
    truncate_file(shard2, keep_fraction=0.4)
    ok, reason = actp.verify_pass(save_dir, 2)
    assert not ok and "truncated" in reason
    assert actp.latest_complete_pass(save_dir) == 1

    t2 = SGD(_tiny_conf(),
             OptimizationConf(learning_method="sgd", learning_rate=0.1),
             seed=2)
    assert t2.resume(save_dir) == 2  # pass 1 + 1, NOT the torn pass 2

    # silent same-size corruption on the next-newest: checksum catches
    shard1 = os.path.join(save_dir, "pass-00001", "shard-p0.npz")
    corrupt_file(shard1)
    ok, reason = actp.verify_pass(save_dir, 1)
    assert not ok and "checksum" in reason
    assert actp.latest_complete_pass(save_dir) == 0
    # a missing manifest is an incomplete pass, not a crash
    os.remove(os.path.join(save_dir, "pass-00000", "manifest.json"))
    with pytest.raises(FileNotFoundError):
        actp.load_pass(save_dir)


def test_sync_save_pass_is_crash_safe(tmp_path):
    """A SIGKILL mid-save leaves only a `pass-%05d.tmp/` staging dir,
    which the loader must ignore; a re-run save atomically replaces
    it."""
    from paddle_tpu.trainer import checkpoint as ckpt

    save_dir = str(tmp_path / "ckpt")
    params = {"w": np.arange(6, dtype=np.float32)}
    ckpt.save_pass(save_dir, 0, params, meta={"global_step": 10})

    # simulated torn save of pass 1: staging dir, never renamed
    staging = os.path.join(save_dir, "pass-00001.tmp")
    os.makedirs(staging)
    with open(os.path.join(staging, "params.npz"), "wb") as f:
        f.write(b"\x00" * 17)  # garbage a crash could leave

    assert ckpt.list_sync_passes(save_dir) == [0]
    p, _, _, meta = ckpt.load_pass(save_dir)  # latest == 0, not 1
    assert meta["pass_id"] == 0 and meta["global_step"] == 10
    np.testing.assert_array_equal(p["w"], params["w"])

    # completing pass 1 sweeps its stale staging and lands atomically
    ckpt.save_pass(save_dir, 1, params, meta={"global_step": 20})
    assert ckpt.list_sync_passes(save_dir) == [0, 1]
    assert not os.path.exists(staging)

    # re-save swap crash window: the old complete pass is parked at
    # `.old` while the new one renames in; a crash BETWEEN the two
    # renames must still leave pass 1 loadable via the .old fallback
    d1 = os.path.join(save_dir, "pass-00001")
    os.replace(d1, d1 + ".old")  # exactly the mid-swap on-disk state
    assert ckpt.list_sync_passes(save_dir) == [0, 1]
    p, _, _, meta = ckpt.load_pass(save_dir, 1)
    assert meta["global_step"] == 20
    np.testing.assert_array_equal(p["w"], params["w"])
    # and a subsequent re-save of pass 1 heals the layout
    ckpt.save_pass(save_dir, 1, params, meta={"global_step": 30})
    assert os.path.isdir(d1) and not os.path.exists(d1 + ".old")
    assert ckpt.load_pass(save_dir, 1)[3]["global_step"] == 30


def test_async_write_failure_surfaces_on_wait(tmp_path):
    """Background write errors must not vanish in the daemon thread:
    wait() (and the next save()) re-raise as AsyncCheckpointError."""
    from paddle_tpu.trainer import async_checkpoint as actp

    blocker = tmp_path / "not_a_dir"
    blocker.write_text("file where the save dir should be")
    ckpt = actp.AsyncCheckpointer(str(blocker / "sub"))
    ckpt.save(0, {"w": np.ones(4, np.float32)})
    with pytest.raises(actp.AsyncCheckpointError):
        ckpt.wait()
    # surfacing CLEARS the latch: the writer stays usable (a transient
    # fault must not poison every later save on this instance) ...
    assert ckpt.last_error is None
    ckpt.save(1, {"w": np.ones(4, np.float32)})  # no stale re-raise
    # ... and a persistent fault re-surfaces on the next drain
    with pytest.raises(actp.AsyncCheckpointError):
        ckpt.wait()


# =====================================================================
# per-process shards: manifest completeness without jax.distributed
# (the CPU backend cannot run true multiprocess computations, so the
# shard protocol is driven through its explicit process hooks)
# =====================================================================


def test_multi_shard_manifest_completeness_and_merge(tmp_path):
    from paddle_tpu.trainer import async_checkpoint as actp

    d = str(tmp_path / "ckpt")
    table = np.arange(32, dtype=np.float32).reshape(8, 4)
    rep = np.full((3,), 7.0, np.float32)
    # process 1 commits first (manifest not yet written): incomplete
    actp.write_shard(
        d, 0,
        {"params/table##1": table[4:], "params/w##1": rep},
        num_shards=2, process_index=1,
    )
    assert actp.list_passes(d) == []  # no manifest yet -> not a pass
    assert actp.latest_complete_pass(d) == -1

    # process 0 commits + manifest: now complete
    actp.write_shard(
        d, 0,
        {"params/table##0": table[:4], "params/w##0": rep},
        meta={"global_step": 5}, num_shards=2, process_index=0,
    )
    ok, reason = actp.verify_pass(d, 0)
    assert ok, reason

    tree, meta = actp.load_pass(d)
    assert meta == {"pass_id": 0, "global_step": 5}
    # row-sharded table reassembles in device order; replicated w dedups
    np.testing.assert_array_equal(tree["params"]["table"], table)
    np.testing.assert_array_equal(tree["params"]["w"], rep)

    # a manifest claiming 3 shards with only 2 on disk is incomplete
    actp.write_shard(
        d, 1, {"params/w##0": rep}, num_shards=3, process_index=0,
    )
    ok, reason = actp.verify_pass(d, 1)
    assert not ok and "shard 1" in reason
    assert actp.latest_complete_pass(d) == 0


def test_non_axis0_sharding_reassembles_exactly(tmp_path):
    """Arrays sharded on axis 1 (column-parallel) — or any layout —
    must reassemble bit-exactly from the recorded slice map; guessing
    axis-0 concatenation here would silently scramble the weights."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.core.mesh import DATA_AXIS, make_mesh
    from paddle_tpu.trainer import async_checkpoint as actp

    mesh = make_mesh({DATA_AXIS: 8})
    w = np.arange(16 * 32, dtype=np.float32).reshape(16, 32)
    col_sharded = jax.device_put(
        w, NamedSharding(mesh, P(None, DATA_AXIS))
    )
    rep = jax.device_put(
        np.full((5,), 3.0, np.float32), NamedSharding(mesh, P())
    )
    d = str(tmp_path / "ckpt")
    with actp.AsyncCheckpointer(d) as ckpt:
        ckpt.save(0, {"w_col": col_sharded, "b": rep})
        ckpt.wait()

    # replicas were deduplicated at snapshot time: one copy of b,
    # 8 column shards of w_col (+ the slice-map entry)
    with np.load(os.path.join(d, "pass-00000",
                              "shard-p0.npz")) as z:
        tags = [k.rsplit("##", 1)[1] for k in z.files
                if k.startswith("params/b")]
        assert tags == ["r0"]
        assert sum(k.startswith("params/w_col") for k in z.files) == 8
        assert actp.INDEX_KEY in z.files

    tree, _ = actp.load_pass(d)
    np.testing.assert_array_equal(tree["params"]["w_col"], w)
    np.testing.assert_array_equal(tree["params"]["b"],
                                  np.full((5,), 3.0, np.float32))

    # template-driven restore places the same bytes back sharded
    tmpl = {
        "params": {
            "w_col": jax.ShapeDtypeStruct(
                (16, 32), np.float32,
                sharding=NamedSharding(mesh, P(None, DATA_AXIS)),
            ),
            "b": jax.ShapeDtypeStruct(
                (5,), np.float32,
                sharding=NamedSharding(mesh, P()),
            ),
        }
    }
    tree2, _ = actp.load_pass(d, template=tmpl)
    np.testing.assert_array_equal(
        np.asarray(tree2["params"]["w_col"]), w
    )
    np.testing.assert_array_equal(
        np.asarray(tree2["params"]["b"]),
        np.full((5,), 3.0, np.float32),
    )


def test_rotation_keeps_newest_complete(tmp_path):
    from paddle_tpu.trainer import async_checkpoint as actp

    d = str(tmp_path / "ckpt")
    with actp.AsyncCheckpointer(d, keep_last=2) as ckpt:
        for p in range(5):
            ckpt.save(p, {"w": np.full((4,), p, np.float32)})
        ckpt.wait()
        assert actp.list_passes(d) == [3, 4]
        tree, meta = actp.load_pass(d)
        assert meta["pass_id"] == 4


# =====================================================================
# (d) master-client retry/backoff under injected connection faults
# =====================================================================


class TestMasterClientRetries:
    def test_retries_through_connection_resets(self, tmp_path):
        """RSTs on the proxy path are absorbed by bounded
        retry-with-jitter; the call lands once the path heals."""
        from conftest import start_master

        from paddle_tpu.data.master_client import MasterClient
        from paddle_tpu.testing_faults import FlakyProxy

        master, port = start_master(lease="30")
        try:
            with FlakyProxy(("127.0.0.1", port)) as proxy:
                c = MasterClient(f"127.0.0.1:{proxy.port}",
                                 retry_seconds=20)
                proxy.reset_next(2)
                t0 = time.monotonic()
                c.add_task(b"payload-0")
                elapsed = time.monotonic() - t0
                # 2 resets -> at most ~base*(1+2)+cap of backoff
                assert elapsed < 10
                # the healed path serves normally
                assert c.get_task() is not None
        finally:
            MasterClient(f"127.0.0.1:{port}",
                         retry_seconds=1).shutdown()
            master.wait(timeout=10)

    def test_timeout_raises_clear_exception(self):
        """A master that stays down yields MasterRetryTimeout naming
        address, elapsed and attempts — not a bare socket error."""
        from paddle_tpu.data.master_client import (
            MasterClient,
            MasterRetryTimeout,
        )
        from paddle_tpu.testing_faults import FlakyProxy

        # proxy to a dead target: every connection dies instantly
        with FlakyProxy(("127.0.0.1", 1)) as proxy:
            proxy.refuse_all()
            c = MasterClient(f"127.0.0.1:{proxy.port}",
                             retry_seconds=1.2)
            t0 = time.monotonic()
            with pytest.raises(MasterRetryTimeout) as ei:
                c.add_task(b"x")
            elapsed = time.monotonic() - t0
            msg = str(ei.value)
            assert "unreachable" in msg and "attempts" in msg
            assert 1.0 <= elapsed < 8
            # MasterRetryTimeout stays catchable as ConnectionError
            # for pre-existing callers
            assert isinstance(ei.value, ConnectionError)

    def test_session_survives_midsession_cut_and_delay(self, tmp_path):
        """PR-8 satellite: a full WORK SESSION (add tasks, lease, ack,
        finish the pass) against the networked master survives
        mid-session connection faults — in-flight RST via
        cut_existing(), an RST'd fresh connection, and added latency —
        with every task done exactly once. Before this test only
        single-call retry behavior was pinned; here the faults land
        BETWEEN calls of one session, where a sloppy client would
        cache a dead socket or double-ack a re-leased task."""
        from conftest import start_master

        from paddle_tpu.data.master_client import MasterClient
        from paddle_tpu.testing_faults import FlakyProxy

        master, port = start_master(lease="30")
        try:
            with FlakyProxy(("127.0.0.1", port)) as proxy:
                c = MasterClient(f"127.0.0.1:{proxy.port}",
                                 retry_seconds=20)
                for i in range(6):
                    c.add_task(f"task-{i}".encode())
                # lease two tasks, then cut every open connection:
                # the client's NEXT call must transparently reconnect
                t1 = c.get_task()
                t2 = c.get_task()
                assert t1 is not None and t2 is not None
                proxy.cut_existing()
                assert c.task_done(t1[0])  # reconnects under the hood
                # an RST that kills the RESPONSE of a delivered ack:
                # the client retries, the duplicate ack returns False
                # (lease already closed), and the task stays done
                # exactly once — the at-least-once contract
                proxy.reset_next(1)
                c.close()  # force the doomed fresh connection
                c.task_done(t2[0])  # must not raise; False on dup is ok
                # added latency: calls still land, just slower
                proxy.delay(0.2)
                done = {t1[1], t2[1]}
                while True:
                    t = c.get_task()
                    if t is None:
                        break
                    assert c.task_done(t[0])
                    done.add(t[1])
                proxy.heal()
                assert done == {f"task-{i}".encode() for i in range(6)}
                assert c.pass_finished()
                counts = c.counts
                assert counts["done"] >= 6 and counts["pending"] == 0
        finally:
            MasterClient(f"127.0.0.1:{port}", retry_seconds=1).shutdown()
            master.wait(timeout=10)

    def test_black_hole_master_trips_retry_deadline(self):
        """ISSUE 9 satellite: a master that ACCEPTS connections but
        never answers must not hang the client past its retry budget.
        Before the fix, master_client recv'd with settimeout(None) —
        this exact fault hung a trainer forever."""
        from paddle_tpu.data.master_client import (
            MasterClient,
            MasterRetryTimeout,
        )
        from paddle_tpu.testing_faults import FlakyProxy

        with FlakyProxy(("127.0.0.1", 1)) as proxy:
            proxy.black_hole()
            c = MasterClient(f"127.0.0.1:{proxy.port}",
                             retry_seconds=1.5, connect_timeout=0.5)
            t0 = time.monotonic()
            with pytest.raises(MasterRetryTimeout):
                c.add_task(b"x")
            elapsed = time.monotonic() - t0
            # the deadline fired (not the 2017 forever-hang), and
            # promptly: one full-budget recv attempt + bookkeeping
            assert 1.0 <= elapsed < 8

    def test_protocol_error_fails_fast(self):
        """A peer speaking garbage is NOT retried for retry_seconds:
        MasterProtocolError surfaces immediately."""
        import socket
        import struct
        import threading

        from paddle_tpu.data.master_client import (
            MasterClient,
            MasterProtocolError,
        )

        srv = socket.socket()
        srv.bind(("127.0.0.1", 0))
        srv.listen(1)
        port = srv.getsockname()[1]

        def garbage_server():
            conn, _ = srv.accept()
            conn.recv(65536)
            conn.sendall(struct.pack("<I", 4) + b"junk")  # len < 8
            conn.close()

        t = threading.Thread(target=garbage_server, daemon=True)
        t.start()
        try:
            c = MasterClient(f"127.0.0.1:{port}", retry_seconds=30)
            t0 = time.monotonic()
            with pytest.raises(MasterProtocolError, match="malformed"):
                c.add_task(b"x")
            assert time.monotonic() - t0 < 2  # no 30s retry loop
        finally:
            srv.close()


# =====================================================================
# (e) SIGTERM preemption is lossless (ISSUE 9 tentpole)
# =====================================================================


def _worker_records(out_file):
    # the one parser of the worker's records
    from paddle_tpu.testing_faults import read_worker_records

    return read_worker_records(out_file)


def test_sigterm_mid_pass_loses_zero_batches_and_curve_matches(
    tmp_path,
):
    """kill -TERM mid-pass: the worker finishes the in-flight batch,
    flushes a mid-pass checkpoint, exits EXIT_PREEMPTED; the respawn
    auto-resumes AT THE EXACT BATCH. Assertions: (1) exit code is the
    preemption contract, (2) every global step trains exactly once
    across both processes (zero lost, zero retrained), (3) the
    concatenated loss curve is IDENTICAL to an uninterrupted run —
    preemption is invisible in the training record."""
    import signal

    from paddle_tpu.testing_faults import start_preemptible_trainer
    from paddle_tpu.trainer import watchdog as wdg

    passes, batches = 3, 16
    # uninterrupted control run
    clean_out = str(tmp_path / "clean.jsonl")
    pc = start_preemptible_trainer(
        REPO, str(tmp_path / "clean_ckpt"), clean_out,
        NUM_PASSES=passes, BATCHES=batches,
    )
    assert pc.wait(timeout=300) == 0, pc.stderr.read()[-2000:]

    # preempted run
    save = str(tmp_path / "ckpt")
    out_file = str(tmp_path / "out.jsonl")
    p = start_preemptible_trainer(
        REPO, save, out_file, NUM_PASSES=passes, BATCHES=batches,
        BATCH_SLEEP=0.05,
    )
    deadline = time.monotonic() + 180
    while time.monotonic() < deadline:
        if sum("loss" in ln for ln in _worker_records(out_file)) >= (
            batches + 4
        ):
            break
        time.sleep(0.05)
    else:
        pytest.fail("worker never reached mid-pass-1")
    p.send_signal(signal.SIGTERM)
    rc = p.wait(timeout=120)
    assert rc == wdg.EXIT_PREEMPTED, (rc, p.stderr.read()[-2000:])
    recs = _worker_records(out_file)
    pre = [ln for ln in recs if "preempted" in ln]
    assert pre, "worker exited 75 without recording the flush"

    p2 = start_preemptible_trainer(
        REPO, save, out_file, NUM_PASSES=passes, BATCHES=batches,
    )
    assert p2.wait(timeout=300) == 0, p2.stderr.read()[-2000:]
    recs = _worker_records(out_file)
    resume = [ln for ln in recs if "resume" in ln]
    # resumed mid-pass at the exact batch the flush recorded
    assert resume and resume[0]["resume"] == pre[0]["preempted"]
    assert resume[0]["skip"] == pre[0]["bi"]

    by_step = {}
    for ln in recs:
        if "loss" in ln:
            by_step.setdefault(ln["step"], []).append(ln["loss"])
    # zero lost, zero retrained
    assert sorted(by_step) == list(range(passes * batches))
    assert all(len(v) == 1 for v in by_step.values())
    # the loss curve matches the uninterrupted run bit-for-bit: the
    # flushed checkpoint restored params/opt-state/step exactly
    clean = {ln["step"]: ln["loss"]
             for ln in _worker_records(clean_out) if "loss" in ln}
    np.testing.assert_allclose(
        [by_step[s][0] for s in sorted(by_step)],
        [clean[s] for s in sorted(clean)],
        rtol=0, atol=1e-6,
    )


def test_launch_respawns_preempted_rank(tmp_path):
    """launch() treats EXIT_PREEMPTED as "respawn me", not failure:
    a rank that preempts once and then succeeds yields job rc 0; the
    respawn budget still bounds a preemption crash-loop."""
    from paddle_tpu.launch import launch
    from paddle_tpu.trainer.watchdog import EXIT_PREEMPTED

    marker = tmp_path / "preempted_once"
    script = tmp_path / "worker.py"
    script.write_text(
        "import os, sys\n"
        f"m = {str(marker)!r}\n"
        "if not os.path.exists(m):\n"
        "    open(m, 'w').close()\n"
        f"    sys.exit({EXIT_PREEMPTED})\n"
        "sys.exit(0)\n"
    )
    rc = launch("localhost", [sys.executable, str(script)],
                nproc_per_host=1, coordinator_port=17311)
    assert rc == 0 and marker.exists()

    # a rank that preempts FOREVER exhausts max_respawns and fails
    loop = tmp_path / "loop.py"
    loop.write_text(f"import sys; sys.exit({EXIT_PREEMPTED})\n")
    rc = launch("localhost", [sys.executable, str(loop)],
                nproc_per_host=1, coordinator_port=17312,
                max_respawns=2)
    assert rc == EXIT_PREEMPTED


# =====================================================================
# (f) async checkpoint atexit flush (ISSUE 9 satellite)
# =====================================================================


def test_interpreter_exit_flushes_enqueued_pass(tmp_path):
    """A pass enqueued but not wait()ed must survive a NORMAL
    interpreter exit: the atexit hook drains the writer. (SIGKILL
    still loses it — that is the manifest/fallback protocol's job.)"""
    save = str(tmp_path / "ckpt")
    src = (
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import numpy as np\n"
        "from paddle_tpu.trainer import async_checkpoint as actp\n"
        f"cp = actp.AsyncCheckpointer({save!r})\n"
        "cp.save(0, {'w': np.arange(8, dtype=np.float32)},\n"
        "        meta={'global_step': 3})\n"
        "# no wait(), no close(): exit must still commit the pass\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", src], capture_output=True, text=True,
        cwd=REPO, timeout=120,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    from paddle_tpu.trainer import async_checkpoint as actp

    ok, reason = actp.verify_pass(save, 0)
    assert ok, reason
    tree, meta = actp.load_pass(save)
    assert meta == {"pass_id": 0, "global_step": 3}
    np.testing.assert_array_equal(
        tree["params"]["w"], np.arange(8, dtype=np.float32)
    )


# =====================================================================
# (g) data-pipeline robustness: corrupt records don't kill the pass
# =====================================================================


def test_proto_reader_skips_corrupt_records_within_budget(tmp_path):
    """Bit-flipped records in a ProtoDataProvider file are dropped
    with a counted warning up to the budget; budget 0 keeps the
    strict abort; a budget-exceeding rot still fails loudly."""
    from paddle_tpu.data import proto_provider as pp
    from paddle_tpu.testing_faults import corrupt_file

    path = str(tmp_path / "data.bin")
    defs = [(pp.VECTOR_DENSE, 4), (pp.INDEX, 3)]
    samples = [
        (np.arange(4, dtype=np.float32) + i, i % 3) for i in range(60)
    ]
    pp.write_proto_data(path, defs, samples)
    assert len(pp.read_proto_data_raw(path)[1]) == 60

    corrupt_file(path, offset=os.path.getsize(path) // 2, nbytes=6)
    # strict mode (default): the pass aborts
    with pytest.raises(ValueError):
        pp.read_proto_data_raw(path)
    # bounded skip: the healthy head (and any recoverable tail)
    # survives; at least one record was dropped
    _, rows, _ = pp.read_proto_data_raw(path, skip_bad_records=8)
    assert 20 <= len(rows) < 60
    # the reader-combinator path carries the budget through
    got = list(pp.proto_reader(path, skip_bad_records=8)())
    assert len(got) == len(rows)
    # budget too small for the rot: loud failure, not silent loss
    with pytest.raises(ValueError, match="budget"):
        pp.read_proto_data_raw(path, skip_bad_records=0)


def test_provider_skips_faulty_files_within_budget(tmp_path):
    """@provider(skip_faulty_files=N): a file whose process() raises
    is skipped with a counted warning; the budget bounds it; strict
    default still aborts."""
    from paddle_tpu.data.feeder import dense_vector
    from paddle_tpu.data.provider import provider
    from paddle_tpu.testing_faults import truncate_file

    good = str(tmp_path / "good.npy")
    bad = str(tmp_path / "bad.npy")
    np.save(good, np.ones((5, 2), np.float32))
    np.save(bad, np.ones((5, 2), np.float32))
    truncate_file(bad, keep_fraction=0.3)  # torn write at crash

    def make(budget):
        @provider(input_types=[dense_vector(2)], should_shuffle=False,
                  skip_faulty_files=budget)
        def proc(settings, filename):
            for row in np.load(filename):  # truncated file raises
                yield (row,)
        return proc

    tolerant = make(1)
    out = list(tolerant([good, bad, good])())
    assert len(out) == 10  # both good files served
    assert tolerant.faulty_files_skipped == 1

    strict = make(0)
    with pytest.raises(Exception):
        list(strict([good, bad, good])())
