"""Global process flags.

Mirrors the reference's 27 gflags (paddle/utils/Flags.cpp:18-82) in
capability: a typed global key/value store consulted by the trainer,
data pipeline and parallel runtime. TPU-specific flags replace
GPU-specific ones (use_gpu -> platform, trainer_count -> mesh shape).
"""

from __future__ import annotations

from typing import Any

_DEFAULTS: dict[str, Any] = {
    # device / mesh
    "platform": None,  # None = jax default; "cpu" forces host backend
    "mesh_shape": None,  # e.g. {"data": 8} — default: all devices on "data"
    # training loop
    "log_period": 100,
    "show_parameter_stats_period": 0,
    "test_period": 0,
    "seed": 0,  # 0 = nondeterministic seed from OS entropy
    # FP-exception trap (reference enables feenableexcept at trainer
    # start, trainer/TrainerMain.cpp:49): aborts on NaN-producing ops
    "trap_fp": False,
    # training watchdog (trainer/watchdog.py): on-device non-finite
    # skip + EWMA spike ladder + checkpoint rollback + SIGTERM-safe
    # preemption. False disables (raw 2017 semantics: a NaN batch
    # poisons the params silently).
    "watchdog": True,
    # PRNG implementation: None = jax default (threefry). "rbg" is
    # substantially faster on TPU for dropout-heavy models (~27% whole
    # -step on AlexNet) at the cost of weaker shard-stability guarantees
    "prng_impl": None,
    "save_dir": None,
    "saving_period": 1,
    "save_only_one": False,
    # "sync": training stalls for the whole serialize+write;
    # "async": only the device->host snapshot blocks, serialization and
    # the atomic-rename shard write overlap the next pass
    # (trainer/async_checkpoint.py)
    "checkpoint_mode": "sync",
    "start_pass": 0,
    # multi-step pipelining (ROADMAP 5d): >1 runs N consecutive train
    # steps as one jitted scan-of-steps dispatch (SGD steps_per_dispatch
    # default; the bench-trick promoted to a trainer option). Short-step
    # models stop paying the ~2-10 ms per-program dispatch floor per
    # batch; events/evaluators/watchdog still see every batch.
    "steps_per_dispatch": 1,
    # recompile guard (analysis/recompile_guard.py, ISSUE 13): after
    # the first pass (warmup — every expected shape incl. the ragged
    # reader tail has traced once) the trainer arms the TrainStep's
    # jit-cache-miss tracker. "off" = never arm; "record" = count
    # steady-state retraces (recompile_guard.violations metric +
    # SGD.recompile_violations()) without failing; "strict" = raise
    # RecompileError from inside the retrace — the bench/CI mode.
    "recompile_guard": "off",
    # per-step timeline attribution (obs/timeline.py): fence the
    # device with block_until_ready every N steps (span `train.fence`)
    # so device_step is measured end-to-end while steady-state
    # dispatch stays async. 0 = never fence (fetches the loop makes
    # anyway still count). It samples nothing else: the trainer spans
    # every step.
    "timeline_sample_period": 16,
    # distributed tracing (obs/tracing.py): serving traces every
    # request that arrives WITH a carrier, plus every Nth anonymous
    # request when trace_serve_period > 0 (0 = carrier-bearing only)
    "trace_serve_period": 0,
    # flight recorder (obs/flight_recorder.py): ring size, dump rate
    # limit, dump-dir bound, and the guarded jax-profiler capture hook
    "flight_ring_capacity": 4096,
    "flight_min_dump_interval_s": 60.0,
    "flight_max_bundles": 8,
    "flight_profiler_capture": False,
    # anomaly thresholds that trip a flight-recorder dump on the
    # serving path: admitted-p99 SLO (ms over a 128-request sliding
    # window; 0 disables) and shed-rate spike (shed fraction over a
    # serve_shed_window_s window, needing >= 20 decisions)
    "serve_p99_slo_ms": 0,
    "serve_shed_rate_threshold": 0.5,
    "serve_shed_window_s": 5.0,
    # fleet SLO burn-rate monitor (obs/aggregate.py wired into
    # serving/fleet.py, ISSUE 17): availability target, the
    # fast/slow multi-window burn-rate pairs (Google-SRE style: an
    # alert needs the budget burning in BOTH the short window and its
    # long companion), and the incident-bundle dump discipline (same
    # rate-limit + bounded-dir contract as the flight recorder)
    "fleet_availability_target": 0.999,
    "fleet_burn_fast_window_s": 60.0,
    "fleet_burn_fast_threshold": 14.4,
    "fleet_burn_slow_window_s": 300.0,
    "fleet_burn_slow_threshold": 6.0,
    "fleet_burn_min_decisions": 20,
    "fleet_incident_min_interval_s": 60.0,
    "fleet_incident_max_bundles": 8,
    # kernels: None = auto (fused Pallas cells on TPU, lax.scan elsewhere)
    "use_pallas_rnn": None,
    # precision policy: params in float32, matmuls in bfloat16 by default
    "default_dtype": "float32",
    "matmul_precision": "default",
    # generation
    "beam_size": 1,
    # distributed control plane
    "coordinator_address": None,
    "process_id": 0,
    "num_processes": 1,
}

_flags: dict[str, Any] = dict(_DEFAULTS)


def get_flag(name: str) -> Any:
    if name not in _flags:
        raise KeyError(f"unknown flag {name!r}")
    return _flags[name]


def set_flag(name: str, value: Any) -> None:
    _flags[name] = value


def reset_flags() -> None:
    _flags.clear()
    _flags.update(_DEFAULTS)
