"""Test env: force an 8-device CPU mesh so distributed paths are testable
without TPU hardware — the analogue of the reference's GPU-stub CPU-only
test mode (paddle/cuda/include/stub/*.h); see SURVEY.md §4."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# One persistent XLA cache for the session, at the fixed place every
# entry point uses (core/compile_cache.py). It is exported so that the
# subprocesses tests spawn (the CLI, distributed workers, the
# inline replica/trainer sources of testing_faults, which never call
# the helper) read and write the same directory.
from paddle_tpu.core import compile_cache  # noqa: E402

os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", compile_cache.enable())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)


def pytest_sessionfinish(session, exitstatus):
    """Lock-order gate (ISSUE 13): when this session ran with
    PADDLE_LOCK_CHECK=1 (tests/run_suite.sh sets it on the faults
    shard), the known locks (obs registry/event stream, serving
    admission queue, async checkpointer, flight-recorder ring) were
    created instrumented — any lock-order inversion observed across
    the whole session fails the shard even if every test passed."""
    from paddle_tpu.analysis import lock_order

    if not lock_order.enabled():
        return
    bad = lock_order.violations()
    if bad:
        rep = session.config.pluginmanager.get_plugin(
            "terminalreporter"
        )
        for v in bad:
            msg = f"LOCK-ORDER VIOLATION: {v['detail']}"
            if rep is not None:
                rep.write_line(msg, red=True)
                for edge, stack in v["stacks"].items():
                    rep.write_line(f"  first {edge} at:\n{stack}")
            else:
                print(msg)
        session.exitstatus = 3


def start_master(lease="0.6", snapshot=None, extra=()):
    """Spawn the networked elastic master on a free port; returns
    (proc, port). Shared by test_master_server.py and the dataset
    elastic-flow test."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cmd = [
        sys.executable, "-m", "paddle_tpu.data.master_serve",
        "--port", "0", "--lease-seconds", str(lease), *extra,
    ]
    if snapshot:
        cmd += ["--snapshot", snapshot, "--snapshot-every", "0.2"]
    proc = subprocess.Popen(
        cmd, stdout=subprocess.PIPE, text=True, cwd=repo
    )
    line = proc.stdout.readline().strip()
    assert line.startswith("LISTENING"), line
    return proc, int(line.split()[1])
