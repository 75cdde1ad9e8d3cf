"""Multi-chip DP-scaling benchmark (VERDICT r4 item 3).

Mirrors the reference's published 4-GPU matrix — AlexNet/GoogleNet at
total-batch 128*N / 256*N and the 4-GPU LSTM text-classification rows
at fixed total-batch 256/512 (`/root/reference/benchmark/README.md:
74-93,152-160`; the MultiGradientMachine per-device thread pool it
measured: `gserver/gradientmachines/MultiGradientMachine.h:85-168`).
Here the equivalent is ONE compiled program: the batch is sharded over
the mesh's data axis and XLA emits the gradient allreduce over ICI
(parallel/dp.py::TrainStep).

Where it runs:
- a real multi-chip slice (`jax.devices()` >= 2 TPU chips): real
  throughput rows, `vs_baseline` against the 4xK40m table;
- started on a CPU (`JAX_PLATFORMS=cpu`, the tests): re-execs itself
  onto a forced 8-virtual-device host-CPU mesh — a correctness/shape
  smoke with tiny per-device batches, every row marked
  `"synthetic": true` and no throughput claim;
- one TPU chip: refuses. It never leaves a chip for the CPU.

Invocation: `python bench.py --multichip` or `python bench_multichip.py
[PATTERN]`. On a pod slice, run it under the multi-host launcher the
same way as training (`python -m paddle_tpu.launch --hosts ... --
python bench_multichip.py`); each host sees the global mesh via
`jax.distributed` (paddle_tpu/core/mesh.py::distributed_init).

Each row also measures a ONE-device arm at the per-device batch and
reports `speedup` = ms_1dev * N / ms_Ndev — the reference's own
speedup formula (benchmark/README.md:79-84: (334*4)/347 = 3.85).
"""

import json
import os
import sys
import time

import numpy as np

# 4xK40m ms/batch, keyed (model, total_batch) — BASELINE.md rows 22-25,
# 29-30; benchmark/README.md:74-93 (images), :152-160 (lstm)
MC_BASELINES_MS = {
    ("alexnet", 512): 347.0,
    ("alexnet", 1024): 622.0,
    ("googlenet", 512): 1178.0,
    ("googlenet", 1024): 2367.0,
    ("lstm_h256", 256): 90.0,
    ("lstm_h256", 512): 118.0,
    ("lstm_h512", 256): 189.0,
    ("lstm_h512", 512): 268.0,
}
BASELINE_DEVICES = 4


def _ensure_devices(pattern):
    """Return (n_devices, synthetic). Started on ONE CPU device,
    re-exec under a forced 8-virtual-device host-CPU mesh so the
    sharded program still compiles and runs — the shape/correctness
    smoke. Started on one TPU chip, fail: rows from a CPU mesh would
    pass for chip rows. `execve` replaces this process, so no parent
    that has touched JAX is left behind. The re-exec command is
    rebuilt from the caller's PATTERN, not raw sys.argv — flags the
    caller already consumed (bench.py's --multichip) must not leak
    through as a filter that silently empties the sweep."""
    import jax

    devs = jax.devices()
    if len(devs) >= 2:
        return len(devs), devs[0].platform != "tpu"
    if devs[0].platform == "tpu":
        raise SystemExit(
            f"bench_multichip: found one {devs[0].device_kind} chip; "
            "the multi-chip rows need at least two, and this program "
            "does not fall back to a CPU mesh from a chip"
        )
    if os.environ.get("_BENCH_MC_REEXEC"):
        raise RuntimeError("cpu-mesh fallback still sees <2 devices")
    env = dict(os.environ)
    env["_BENCH_MC_REEXEC"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    xf = env.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in xf:
        env["XLA_FLAGS"] = (
            xf + " --xla_force_host_platform_device_count=8"
        ).strip()
    sys.stdout.flush()
    os.execve(
        sys.executable,
        [sys.executable, os.path.abspath(__file__)]
        + ([pattern] if pattern else []),
        env,
    )


from bench import _setup  # one source of truth for AMP/PRNG/cache setup
from bench import emit  # stamps the device; BENCH_FULL_RECORD appends


def _mesh_arm(conf, feed, opt_conf, mesh, iters):
    """Build one (possibly mesh-sharded) training program; returns
    (warmup_fn, window_fn) with state carried across calls, same
    contract as bench.py::_build_arm."""
    import jax

    from paddle_tpu.network import Network
    from paddle_tpu.optimizers import create_optimizer
    from paddle_tpu.parallel.dp import TrainStep, shard_batch

    net = Network(conf)
    params = net.init_params(jax.random.key(0))
    opt = create_optimizer(opt_conf, net.param_confs)
    step = TrainStep(net, opt, mesh=mesh, donate=False)
    st = {
        "params": params,
        "opt_state": opt.init_state(params),
        "state": net.init_state(),
        "i": 0,
    }
    if mesh is not None:
        st["params"], st["opt_state"], st["state"] = step.place(
            st["params"], st["opt_state"], st["state"]
        )
        feed = shard_batch(feed, mesh)
    else:
        feed = jax.device_put(feed)
    key = jax.random.key(1)

    # dispatch-vs-block split for the row's attribution triple (the
    # same convention as bench.py::_build_arm: submissions are host
    # work, the wait for the last loss is the device block; the feed is
    # pre-staged so data_wait is truly 0)
    timeline = {"data_s": 0.0, "dispatch_s": 0.0, "device_s": 0.0}

    def _run(n):
        t0 = time.perf_counter()
        for _ in range(n):
            (
                st["params"],
                st["opt_state"],
                st["state"],
                loss,
                _o,
            ) = step(
                st["params"], st["opt_state"], st["state"], feed,
                st["i"], key,
            )
            st["i"] += 1
        t1 = time.perf_counter()
        loss.block_until_ready()
        timeline["dispatch_s"] += t1 - t0
        timeline["device_s"] += time.perf_counter() - t1
        return float(loss)

    def warmup_fn(n):
        _run(n)
        # drop the compile-laden warmup from the attribution fields
        timeline["dispatch_s"] = timeline["device_s"] = 0.0

    def window_fn():
        t0 = time.perf_counter()
        _run(iters)
        return (time.perf_counter() - t0) / iters * 1e3

    window_fn.timeline = timeline
    return warmup_fn, window_fn


def _image_conf_feed(model, bs):
    from paddle_tpu import models
    from paddle_tpu.core.arg import id_arg, non_seq

    factory = {"alexnet": models.alexnet, "googlenet": models.googlenet}
    conf = factory[model](image_shape=(224, 224, 3), num_classes=1000)
    rng = np.random.default_rng(0)
    feed = {
        "image": non_seq(
            rng.standard_normal((bs, 224, 224, 3)).astype(np.float32)
        ),
        "label": id_arg(rng.integers(0, 1000, bs).astype(np.int32)),
    }
    return conf, feed


def _lstm_conf_feed(hidden, bs, t=100):
    from paddle_tpu.core.arg import id_arg
    from paddle_tpu.models import stacked_lstm_classifier

    conf = stacked_lstm_classifier(
        vocab_size=30000, emb_dim=128, hidden=hidden, num_layers=2,
        num_classes=2,
    )
    rng = np.random.default_rng(0)
    feed = {
        "words": id_arg(
            rng.integers(0, 30000, (bs, t)).astype(np.int32),
            np.full((bs,), t, np.int32),
        ),
        "label": id_arg(rng.integers(0, 2, bs).astype(np.int32)),
    }
    return conf, feed


def _bench_row(model, total_bs, n_dev, synthetic):
    """One DP row: N-device arm at total_bs (sharded), plus — on real
    hardware — a 1-device arm at total_bs/N for the reference speedup
    formula. Synthetic (CPU-mesh) rows shrink the batch to a shape
    smoke and skip the 1-device arm."""
    from paddle_tpu.core.config import OptimizationConf
    from paddle_tpu.core.mesh import DATA_AXIS, make_mesh

    if synthetic:
        run_bs, iters, warmup, windows = 2 * n_dev, 2, 2, 1
    else:
        run_bs, iters, warmup, windows = total_bs, 10, 15, 3

    if model.startswith("lstm"):
        hidden = int(model.split("_h")[1])
        # the smoke checks sharding/shape plumbing, not throughput —
        # a short sequence keeps the one-core CI mesh fast
        conf, feed = _lstm_conf_feed(hidden, run_bs,
                                     t=16 if synthetic else 100)
        opt = OptimizationConf(learning_method="adam", learning_rate=2e-3)
    else:
        conf, feed = _image_conf_feed(model, run_bs)
        opt = OptimizationConf(
            learning_method="momentum", learning_rate=0.001, momentum=0.9
        )

    mesh = make_mesh({DATA_AXIS: n_dev})
    w, f = _mesh_arm(conf, feed, opt, mesh, iters)
    w(warmup)
    ms = min(f() for _ in range(windows))
    out = {
        "value": round(ms, 3),
        "unit": "ms/batch",
        "devices": n_dev,
        "total_batch": run_bs,
        "per_device_batch": run_bs // n_dev,
    }
    if synthetic:
        out["synthetic"] = True
        out["note"] = (
            "host-CPU virtual mesh shape smoke - no throughput claim"
        )
        return out

    base = MC_BASELINES_MS.get((model, total_bs))
    if base is not None:
        out["vs_baseline"] = round(base / ms, 2)
        out["baseline_ms"] = base
        out["baseline_devices"] = BASELINE_DEVICES
    # reference speedup formula: time_1dev(per_dev_bs) * N / time_Ndev
    if model.startswith("lstm"):
        conf1, feed1 = _lstm_conf_feed(
            int(model.split("_h")[1]), run_bs // n_dev
        )
    else:
        conf1, feed1 = _image_conf_feed(model, run_bs // n_dev)
    w1, f1 = _mesh_arm(conf1, feed1, opt, None, iters)
    w1(warmup)
    ms1 = min(f1() for _ in range(windows))
    out["ms_1dev_per_dev_batch"] = round(ms1, 3)
    out["speedup"] = round(ms1 * n_dev / ms, 2)
    out["scaling_efficiency"] = round(ms1 * n_dev / ms / n_dev, 3)
    return out


def _bench_longctx_sharded(mode, t, n_dev, synthetic, bs=1):
    """The T>=32k long-context rows (ISSUE 12 tentpole: leave the
    reference's 2017 world): the SAME longctx model as bench.py's
    single-chip rows (bench.longctx_conf), but with the time dimension
    sharded over the mesh `seq` axis — `mode` "ring" (K/V blocks
    rotate over ICI, online softmax across AND inside ring steps:
    score tiles capped at RING_BLOCK_K) or "ulysses" (all-to-all
    seq->heads reshard with FLASH local attention, attn_impl="flash").
    Dense single-chip attention cannot play at these shapes at all —
    at T=32k the [B,H,T,T] scores alone mean ~69 GB of HBM traffic
    per layer per FORWARD (4 round trips x 8 heads x T^2 x 2 bytes;
    `attn_hbm_bytes_dense_equiv` states the fwd+bwd figure on the
    row); the flash shardings stream O(T) score bytes per chip.

    Real slice: measures tokens/s at the full T with the standard
    data_wait/host/device attribution triple. Single-device hosts
    re-exec onto the 8-virtual-device CPU mesh (synthetic=True): the
    row shrinks to a shape smoke (scaled-down T, same code path
    end-to-end — mesh, shard_map collectives, scan-of-blocks, bwd) so
    the mode cannot rot in CI; no throughput claim."""
    import jax

    from paddle_tpu.core.config import OptimizationConf
    from paddle_tpu.core.mesh import (
        DATA_AXIS, SEQ_AXIS, make_mesh, set_mesh,
    )
    from paddle_tpu.parallel.ring import attention_hbm_bytes

    from bench import (
        TPU_PEAK_FLOPS,
        _longctx_flops_fwd,
        longctx_conf,
        longctx_feed,
    )

    heads_adjusted = False
    if synthetic:
        # shape smoke: T scaled down but still sharded (T % n_dev == 0
        # and heads % n_dev == 0 for the ulysses head split)
        t_run, d, heads, layers, classes = 32 * n_dev, 64, n_dev, 1, 64
        iters, warmup, windows = 2, 2, 1
    else:
        t_run, d, heads, layers, classes = t, 512, 8, 2, 512
        iters, warmup, windows = 5, 5, 3
        if mode == "ulysses" and heads % n_dev:
            # the ulysses head split must divide the seq axis; record
            # the substitution ON the row — a 16-head arm is not the
            # 8-head model the ring row measures
            if d % n_dev:
                raise RuntimeError(
                    f"ulysses needs heads divisible by the seq axis "
                    f"({n_dev}) and d={d} % {n_dev} != 0 — pick a "
                    f"mesh whose seq axis divides {d}"
                )
            heads = n_dev
            heads_adjusted = True
    conf = longctx_conf(
        t_run, d, heads, layers, classes,
        attn_impl="flash", seq_parallel=mode,
    )
    feed = longctx_feed(bs, t_run, classes)
    mesh = make_mesh({DATA_AXIS: 1, SEQ_AXIS: n_dev})
    set_mesh(mesh)
    opt = OptimizationConf(learning_method="adam", learning_rate=1e-3)
    try:
        w, f = _mesh_arm(conf, feed, opt, mesh, iters)
        w(warmup)
        ms = min(f() for _ in range(windows))
    finally:
        set_mesh(make_mesh())  # later rows expect the default mesh
    toks = bs * t_run / (ms / 1e3)
    fwd = _longctx_flops_fwd(bs, t_run, d, heads, layers, classes)
    hd = d // heads
    from bench import _timeline_fields

    out = {
        **_timeline_fields(f.timeline),
        "value": round(toks, 1),
        "unit": "tokens/s (%s-sharded flash attention, T=%d)"
                % (mode, t_run),
        "ms_per_step": round(ms, 2),
        "analytic_mfu_per_chip": round(
            3 * fwd * (1e3 / ms) / TPU_PEAK_FLOPS / n_dev, 4
        ),
        "devices": n_dev,
        "seq_len": t_run,
        "seq_parallel": mode,
        "attn_impl": "flash",
        "heads": heads,
        "batch": bs,
        # what the 2017-semantics dense path WOULD stream through HBM
        # in attention-score bytes at this shape — the reason these
        # rows exist only as flash shardings
        "attn_hbm_bytes_dense_equiv": layers * attention_hbm_bytes(
            bs, t_run, t_run, heads, hd, "dense"
        ),
        "attn_hbm_bytes_flash": layers * attention_hbm_bytes(
            bs, t_run, t_run, heads, hd, "flash"
        ),
    }
    if heads_adjusted:
        out["heads_adjusted"] = True  # NOT the ring rows' 8-head model
    if synthetic:
        out["synthetic"] = True
        out["note"] = (
            "host-CPU virtual mesh shape smoke at scaled-down T - "
            "no throughput claim"
        )
    return out


def _bench_checkpoint_overhead(n_dev, synthetic):
    """Per-step cost of checkpointing at a fixed cadence, sync vs
    async (ROADMAP item 4: pod-scale snapshots must not stall
    training). Three arms over the same mesh-sharded program:

      base   — no saves (the floor)
      sync   — blocking `checkpoint.save_pass` every `cadence` steps
               (device_get + serialize + write on the training thread)
      async  — `AsyncCheckpointer.save` at the same cadence (only the
               host snapshot blocks; serialize + atomic write overlap
               the next steps)

    Headline `value` = mean training-thread stall per async save;
    `sync_save_ms` is what the same save costs when synchronous. The
    CPU-mesh smoke asserts async stall < sync save — the contract that
    makes async mode worth shipping."""
    import shutil
    import tempfile

    import jax

    from paddle_tpu.core.config import OptimizationConf
    from paddle_tpu.core.mesh import DATA_AXIS, make_mesh
    from paddle_tpu.network import Network
    from paddle_tpu.optimizers import create_optimizer
    from paddle_tpu.parallel.dp import TrainStep, shard_batch
    from paddle_tpu.trainer import checkpoint as ckpt
    from paddle_tpu.trainer import async_checkpoint as actp

    if synthetic:
        bs, t, steps, cadence = 2 * n_dev, 16, 8, 2
    else:
        bs, t, steps, cadence = 8 * n_dev, 64, 30, 5
    # the 30k-vocab embedding makes the checkpoint tens of MB — a save
    # whose serialize+write cost is visible against the step time
    conf, feed = _lstm_conf_feed(256, bs, t=t)
    opt_conf = OptimizationConf(learning_method="adam",
                                learning_rate=2e-3)
    mesh = make_mesh({DATA_AXIS: n_dev})

    net = Network(conf)
    params = net.init_params(jax.random.key(0))
    opt = create_optimizer(opt_conf, net.param_confs)
    step = TrainStep(net, opt, mesh=mesh, donate=False)
    st = {
        "params": params,
        "opt_state": opt.init_state(params),
        "state": net.init_state(),
        "i": 0,
    }
    st["params"], st["opt_state"], st["state"] = step.place(
        st["params"], st["opt_state"], st["state"]
    )
    feed = shard_batch(feed, mesh)
    key = jax.random.key(1)

    def one_step():
        (
            st["params"], st["opt_state"], st["state"], loss, _o,
        ) = step(
            st["params"], st["opt_state"], st["state"], feed,
            st["i"], key,
        )
        st["i"] += 1
        return float(loss)  # scalar fetch forces execution

    one_step()
    one_step()  # warm both the program and the dispatch path
    ckpt_bytes = sum(
        a.nbytes for a in actp.snapshot_shards(
            {"params": st["params"], "opt_state": st["opt_state"]}
        ).values()
    )

    def run_arm(save_fn):
        """Returns (ms_per_step over the loop, mean ms per save)."""
        stalls = []
        t0 = time.perf_counter()
        for i in range(steps):
            one_step()
            if save_fn is not None and (i + 1) % cadence == 0:
                s0 = time.perf_counter()
                save_fn((i + 1) // cadence)
                stalls.append(time.perf_counter() - s0)
        total = time.perf_counter() - t0
        stall_ms = (
            sum(stalls) / len(stalls) * 1e3 if stalls else 0.0
        )
        return total / steps * 1e3, stall_ms

    base_ms, _ = run_arm(None)

    sync_dir = tempfile.mkdtemp(prefix="bench_ckpt_sync_")
    async_dir = tempfile.mkdtemp(prefix="bench_ckpt_async_")
    try:
        def sync_save(pass_id):
            ckpt.save_pass(
                sync_dir, pass_id,
                jax.device_get(st["params"]),
                jax.device_get(st["opt_state"]),
                jax.device_get(st["state"]),
                meta={"global_step": st["i"]},
            )

        sync_ms, sync_save_ms = run_arm(sync_save)

        writer = actp.AsyncCheckpointer(async_dir, keep_last=2)

        def async_save(pass_id):
            writer.save(
                pass_id, st["params"], st["opt_state"], st["state"],
                meta={"global_step": st["i"]},
            )

        async_ms, async_stall_ms = run_arm(async_save)
        d0 = time.perf_counter()
        writer.close()  # drain; surfaces any background write error
        drain_ms = (time.perf_counter() - d0) * 1e3
        # the drained checkpoints really committed (manifest-complete,
        # checksums verified) — reported on the row so the smoke can
        # assert it, raised here so a silent writer can't score a row
        committed = [
            p for p in actp.list_passes(async_dir)
            if actp.verify_pass(async_dir, p)[0]
        ]
        if not committed:
            raise RuntimeError(
                "async writer committed no complete pass"
            )
    finally:
        shutil.rmtree(sync_dir, ignore_errors=True)
        shutil.rmtree(async_dir, ignore_errors=True)

    out = {
        "value": round(async_stall_ms, 3),
        "unit": "ms training-thread stall per async save",
        "sync_save_ms": round(sync_save_ms, 3),
        "async_stall_ms": round(async_stall_ms, 3),
        "stall_vs_sync": round(
            async_stall_ms / sync_save_ms, 3
        ) if sync_save_ms else None,
        "base_ms_per_step": round(base_ms, 3),
        "sync_ms_per_step": round(sync_ms, 3),
        "async_ms_per_step": round(async_ms, 3),
        "async_drain_ms": round(drain_ms, 3),
        "async_committed_passes": len(committed),
        "save_cadence_steps": cadence,
        "steps": steps,
        "checkpoint_mb": round(ckpt_bytes / 1e6, 1),
        "devices": n_dev,
        "total_batch": bs,
    }
    if synthetic:
        out["synthetic"] = True
        out["note"] = (
            "host-CPU virtual mesh smoke - stall RATIO is the claim, "
            "absolute times are not"
        )
    return out


def _bench_preempt_recovery(n_dev, synthetic):
    """Permanent recovery row (ISSUE 9): elasticity measured like
    throughput. Two arms, both against the REAL trainer:

      sigterm — a preemptible SGD worker subprocess is SIGTERMed
                mid-pass; it finishes the in-flight batch, flushes a
                mid-pass async checkpoint, exits EXIT_PREEMPTED (75);
                a respawn auto-resumes. Measured: flush latency
                (SIGTERM->exit), time-to-recover (respawn->first newly
                trained batch, jit compile included — that IS the
                recovery cost), and batches lost/retrained across the
                whole run (both must be 0: the global-step record must
                cover every batch exactly once).
      nan     — an in-process trainer hits one poisoned batch with
                skip_budget=0, forcing the rollback rung. Measured:
                detection latency in batches (contract: 1), rollback
                wall time, and batches of progress the rollback
                discarded (bounded by the checkpoint cadence).

    CPU smoke: timings are machine-relative; the loss-zero claims are
    exact. `value` (headline) = time_to_recover seconds."""
    import shutil
    import signal
    import tempfile

    from paddle_tpu.testing_faults import (
        read_metrics_records,
        read_worker_records,
        start_preemptible_trainer,
    )
    from paddle_tpu.trainer import watchdog as wdg

    repo = os.path.dirname(os.path.abspath(__file__))
    work = tempfile.mkdtemp(prefix="bench_preempt_")
    save = os.path.join(work, "ckpt")
    out_file = os.path.join(work, "out.jsonl")
    num_passes, batches = 3, 16
    total_steps = num_passes * batches

    def _lines():
        return read_worker_records(out_file)

    try:
        # ---- arm 1: SIGTERM mid-pass ----
        p = start_preemptible_trainer(
            repo, save, out_file, NUM_PASSES=num_passes,
            BATCHES=batches, BATCH_SLEEP=0.05,
        )
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            if sum("loss" in ln for ln in _lines()) >= batches + 4:
                break
            time.sleep(0.05)
        p.send_signal(signal.SIGTERM)
        t0 = time.monotonic()
        rc = p.wait(timeout=120)
        flush_s = time.monotonic() - t0
        if rc != wdg.EXIT_PREEMPTED:
            raise RuntimeError(
                f"worker exited {rc}, want {wdg.EXIT_PREEMPTED}: "
                f"{p.stderr.read()[-500:]}"
            )
        steps_before = {ln["step"] for ln in _lines() if "loss" in ln}

        t1 = time.monotonic()
        p2 = start_preemptible_trainer(
            repo, save, out_file, NUM_PASSES=num_passes,
            BATCHES=batches,
        )
        recover_s = None
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            new = {ln["step"] for ln in _lines()
                   if "loss" in ln} - steps_before
            if new:
                recover_s = time.monotonic() - t1
                break
            time.sleep(0.05)
        rc2 = p2.wait(timeout=300)
        if rc2 != 0 or recover_s is None:
            raise RuntimeError(
                f"resume failed rc={rc2}: {p2.stderr.read()[-500:]}"
            )
        steps = [ln["step"] for ln in _lines() if "loss" in ln]
        lost = total_steps - len(set(steps))
        retrained = len(steps) - len(set(steps))

        # ---- arm 2: injected NaN -> rollback ----
        shutil.rmtree(work, ignore_errors=True)
        os.makedirs(work, exist_ok=True)
        nan_at = 2 * batches + 4  # mid-pass 2: passes 0-1 checkpointed
        metrics_file = os.path.join(work, "metrics.jsonl")
        p3 = start_preemptible_trainer(
            repo, save, out_file, NUM_PASSES=num_passes,
            BATCHES=batches, NAN_AT=nan_at, SKIP_BUDGET=0,
            GOOD_BATCHES=2, METRICS_FILE=metrics_file,
        )
        t2 = time.monotonic()
        rc3 = p3.wait(timeout=600)
        nan_wall_s = time.monotonic() - t2
        if rc3 != 0:
            raise RuntimeError(
                f"nan arm exited {rc3}: {p3.stderr.read()[-500:]}"
            )
        report = next(ln["report"] for ln in _lines()
                      if "report" in ln)
        # the watchdog's structured series on the obs METRICS stream
        # (ISSUE 10) is the measurement source now — the report stays
        # as a cross-check that stream and report cannot disagree
        wd_events = read_metrics_records(metrics_file, kind="watchdog")
        skips = [e for e in wd_events if e["event"] == "skip"]
        rollbacks = [e for e in wd_events if e["event"] == "rollback"]
        if not rollbacks:
            raise RuntimeError(
                f"no rollback on metrics stream: {wd_events}"
            )
        if len(rollbacks) != report["rollbacks"]:
            raise RuntimeError(
                f"metrics stream ({len(rollbacks)} rollbacks) "
                f"disagrees with report ({report['rollbacks']})"
            )
        # detection latency, MEASURED from the event stream: the skip
        # event's global_step minus the injected batch's step, plus 1
        # (the contract is "within 1 batch" — fires ON the poisoned
        # batch). A lagging verdict would read 2+ here, not stay 1.
        detect_batches = (
            skips[0]["global_step"] - nan_at + 1 if skips else -1
        )
        # progress discarded = steps from the restored checkpoint to
        # the fault (they retrain after rollback)
        batches_lost_nan = nan_at - rollbacks[0]["global_step"]
        # per-pass step-timeline records from the same stream give
        # this row the attribution triple every permanent row carries
        timelines = read_metrics_records(metrics_file, kind="timeline")
        tl = timelines[-1] if timelines else {}
    finally:
        shutil.rmtree(work, ignore_errors=True)

    out = {
        "value": round(recover_s, 3),
        "unit": "s to first trained batch after preemption respawn",
        "sigterm_flush_s": round(flush_s, 3),
        "sigterm_batches_lost": lost,
        "sigterm_batches_retrained": retrained,
        "sigterm_exit_code": rc,
        "nan_detect_batches": detect_batches,
        "nan_rollbacks": report["rollbacks"],
        "nan_batches_lost": batches_lost_nan,
        "nan_run_wall_s": round(nan_wall_s, 3),
        "devices": n_dev,
        "passes": num_passes,
        "batches_per_pass": batches,
        "data_wait_frac": tl.get("data_wait_frac", 0.0),
        "host_overhead_frac": tl.get("host_overhead_frac", 0.0),
        "device_frac": tl.get("device_frac", 0.0),
    }
    if synthetic:
        out["synthetic"] = True
        out["note"] = (
            "CPU smoke - loss-zero/exactly-once claims are exact, "
            "absolute times are not"
        )
    return out


def _bench_ctr_bigvocab(n_dev, synthetic):
    """Permanent elastic sparse-CTR row (ISSUE 20): the sharded
    embedding tier's robustness story, measured like throughput.
    Three phases against the REAL stack:

      kill    — the sharded-CTR worker subprocess (per-shard hot
                caches over an n_dev CPU mesh, async sharded-table
                generations) is SIGKILLed mid-epoch with a
                generation in flight; a respawn recovers from the
                per-shard manifests. Measured: kill_recover_s
                (respawn exec -> first NEWLY acknowledged batch) and
                the commit-acknowledged ledger's exactly-once
                verdict: batches_lost / batches_retrained, both
                required to be 0.
      scale   — rows_total / rows_touched_frac from the finished
                worker: the 2**30-row logical table where only the
                hot set ever materialized (V-independence priced).
      swap    — one ctr replica serves the worker's committed
                generations through a FleetRouter while a request
                stream runs; a rollout() hot-swaps to the newest
                generation mid-stream. Measured:
                swap_downtime_requests_lost (required 0) and the
                swap latency.

    CPU smoke: timings are machine-relative; the zero claims are
    exact. `value` (headline) = kill_recover_s."""
    import shutil
    import tempfile

    from paddle_tpu.serving.fleet import FleetConfig, FleetRouter
    from paddle_tpu.testing_faults import (
        kill_process,
        read_worker_records,
        start_serving_replica,
        start_sharded_ctr_trainer,
    )

    repo = os.path.dirname(os.path.abspath(__file__))
    work = tempfile.mkdtemp(prefix="bench_ctr_bigvocab_")
    save = os.path.join(work, "gens")
    os.makedirs(save)
    out_file = os.path.join(work, "ledger.jsonl")
    rows_total = 1 << 30
    if synthetic:
        batches, capacity, num_slots, hot = 16, 64, 48, 96
    else:
        batches, capacity, num_slots, hot = 48, 4096, 1024, 4096
    env = dict(SHARDS=n_dev, ROWS_TOTAL=rows_total, BATCHES=batches,
               CAPACITY=capacity, NUM_SLOTS=num_slots, HOT=hot,
               BATCH=8, FEATS=4, BATCH_SLEEP=0.05)

    def _trained():
        return [ln["trained"] for ln in read_worker_records(out_file)
                if "trained" in ln]

    router = None
    replica = None
    try:
        # ---- phase 1: SIGKILL mid-epoch, manifest recovery ----
        p = start_sharded_ctr_trainer(repo, save, out_file, **env)
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            if len(_trained()) >= 3:
                break
            if p.poll() is not None:
                raise RuntimeError(
                    "worker died early: " + p.stderr.read()[-500:]
                )
            time.sleep(0.05)
        kill_process(p)  # SIGKILL: no flush, the generation in
        acked_before = set(_trained())  # flight stays torn on disk
        t1 = time.monotonic()
        p2 = start_sharded_ctr_trainer(repo, save, out_file, **env)
        kill_recover_s = None
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            if set(_trained()) - acked_before:
                kill_recover_s = time.monotonic() - t1
                break
            time.sleep(0.05)
        rc = p2.wait(timeout=300)
        if rc != 0 or kill_recover_s is None:
            raise RuntimeError(
                f"resume failed rc={rc}: {p2.stderr.read()[-500:]}"
            )
        trained = _trained()
        lost = len(set(range(batches)) - set(trained))
        retrained = len(trained) - len(set(trained))
        done = [ln for ln in read_worker_records(out_file)
                if ln.get("done")][-1]
        touched_frac = done["rows_materialized"] / done["rows_total"]

        # ---- phase 2: serve the generations, hot-swap mid-stream --
        proc, port = start_serving_replica(
            repo, REPLICA_MODE="ctr", MODEL_NAME="ctr",
            MODEL_TAG="pre-swap", MODEL_DIR=save)
        replica = proc
        if not port:
            raise RuntimeError(
                f"ctr replica refused: {proc.boot_line}"
            )
        router = FleetRouter({"r0": f"127.0.0.1:{port}"},
                             FleetConfig(monitor=False))
        ids = [1, 2, 3, 4]
        swap_lost = served = 0
        swap_s = None
        n_requests = 60 if synthetic else 400
        for i in range(n_requests):
            if i == n_requests // 2:
                t2 = time.monotonic()
                router.rollout("ctr", tag="post-swap")
                swap_s = time.monotonic() - t2
            resp = router.call("ctr", ids, deadline_ms=10_000)
            served += 1
            if not resp.get("ok"):
                swap_lost += 1
        final = router.call("ctr", ids, deadline_ms=10_000)
        if final.get("tag") != "post-swap":
            raise RuntimeError(f"swap did not land: {final}")
    finally:
        if router is not None:
            router.close()
        if replica is not None:
            kill_process(replica)
        shutil.rmtree(work, ignore_errors=True)

    out = {
        "value": round(kill_recover_s, 3),
        "unit": "s from respawn to first newly acknowledged batch",
        "rows_total": rows_total,
        "rows_touched_frac": touched_frac,
        "kill_recover_s": round(kill_recover_s, 3),
        "batches_lost": lost,
        "batches_retrained": retrained,
        "swap_downtime_requests_lost": swap_lost,
        "swap_s": round(swap_s, 3),
        "swap_requests_served": served,
        "batches": batches,
        "hot_capacity_per_shard": capacity,
        "devices": n_dev,
    }
    if synthetic:
        out["synthetic"] = True
        out["note"] = (
            "CPU smoke - exactly-once/zero-loss claims are exact, "
            "absolute times are not"
        )
    return out


def build_rows(n_dev):
    rows = []
    for model in ("alexnet", "googlenet"):
        for per_dev in (128, 256):
            total = per_dev * n_dev
            rows.append((f"mc_{model}_tbs{total}_dp{n_dev}",
                         model, total))
    # reference lstm rows keep TOTAL batch fixed at 256/512
    for hidden in (256, 512):
        for total in (256, 512):
            rows.append(
                (f"mc_lstm_h{hidden}_tbs{total}_dp{n_dev}",
                 f"lstm_h{hidden}", total)
            )
    return rows


def mc_main(argv):
    pattern = argv[1] if len(argv) > 1 else ""
    n_dev, synthetic = _ensure_devices(pattern)  # may re-exec
    _setup()
    budget_s = float(os.environ.get("BENCH_BUDGET_S", "2400"))
    t_start = time.monotonic()
    emit({  # emit stamps platform, device_kind and device_count
        "metric": "mc_config",
        "devices": n_dev,
        "synthetic": synthetic,
    })
    failures = 0
    rows = [
        (name, lambda m=model, t=total: _bench_row(m, t, n_dev,
                                                   synthetic))
        for name, model, total in build_rows(n_dev)
    ]
    # permanent long-context rows (ISSUE 12 / ROADMAP 1): ring- and
    # Ulysses-sharded flash attention at T >= 32k — the sequence
    # lengths the 2017 reference (and our own dense path) cannot
    # reach; tools/check_bench_record.py pins the row names so the
    # matrix cannot silently drop them
    rows.append((
        f"mc_longctx_ring_t32768_sp{n_dev}",
        lambda: _bench_longctx_sharded("ring", 32768, n_dev,
                                       synthetic),
    ))
    rows.append((
        f"mc_longctx_ulysses_t32768_sp{n_dev}",
        lambda: _bench_longctx_sharded("ulysses", 32768, n_dev,
                                       synthetic),
    ))
    rows.append((
        f"mc_longctx_ring_t131072_sp{n_dev}",
        lambda: _bench_longctx_sharded("ring", 131072, n_dev,
                                       synthetic),
    ))
    # permanent elasticity rows (ROADMAP item 4 / ISSUE 9): checkpoint
    # stalls and preemption recovery are tracked like MFU, not assumed
    # away
    rows.append((
        f"mc_checkpoint_overhead_dp{n_dev}",
        lambda: _bench_checkpoint_overhead(n_dev, synthetic),
    ))
    rows.append((
        f"mc_preempt_recovery_dp{n_dev}",
        lambda: _bench_preempt_recovery(n_dev, synthetic),
    ))
    # permanent elastic sparse-CTR row (ISSUE 20): SIGKILL the
    # sharded-table worker mid-epoch, recover from per-shard
    # manifests, hot-swap the serving model mid-stream — the
    # exactly-once ledger and zero-downtime swap are enforced
    # field-by-field by tools/check_bench_record.py
    rows.append((
        f"ctr_bigvocab_dp{n_dev}",
        lambda: _bench_ctr_bigvocab(n_dev, synthetic),
    ))
    for name, fn in rows:
        if pattern and pattern not in name:
            continue
        elapsed = time.monotonic() - t_start
        if elapsed > budget_s:
            emit({
                "metric": name, "skipped": "budget",
                "elapsed_s": round(elapsed, 1),
            })
            continue
        line = {"metric": name}
        try:
            line.update(fn())
        except Exception as e:
            failures += 1
            line["error"] = f"{type(e).__name__}: {e}"[:300]
            line["value"] = None
        emit(line)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(mc_main(sys.argv))
