"""Two-PROCESS jax.distributed smoke test (CPU backend).

Exercises the multi-host control plane end-to-end: core/mesh.py
`distributed_init` bootstrap, a global mesh spanning both processes,
cross-process collectives inside jit, and sharded checkpoint
save/restart/resume via trainer/checkpoint.py save_sharded/load_sharded
— the Go pserver's checkpoint/recover capability
(go/pserver/service.go:76-126) without etcd.
"""

import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = """
import os, sys
sys.path.insert(0, os.environ["REPO"])
import jax
jax.config.update("jax_platforms", "cpu")

from paddle_tpu.core.mesh import DATA_AXIS, distributed_init, make_mesh

pid = int(os.environ["PROC_ID"])
phase = int(os.environ["PHASE"])
ckpt_dir = os.environ["CKPT_DIR"]

distributed_init(
    coordinator_address=os.environ["COORD"], num_processes=2,
    process_id=pid,
)
assert jax.process_count() == 2
assert len(jax.devices()) == 8  # 4 local x 2 processes
assert len(jax.local_devices()) == 4

import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.trainer import checkpoint as ckpt

mesh = make_mesh({DATA_AXIS: 8})
sharding = NamedSharding(mesh, P(DATA_AXIS, None))
V, D = 64, 4

if phase == 1:
    init = (
        jnp.arange(V * D, dtype=jnp.float32).reshape(V, D) / (V * D)
    )
    table = jax.device_put(init, sharding)
    steps = 3
else:
    tmpl = jax.ShapeDtypeStruct((V, D), jnp.float32, sharding=sharding)
    state = ckpt.load_sharded(ckpt_dir, {"table": tmpl})
    table = state["table"]
    steps = 2

@jax.jit
def step(t):
    # grad of sum(t^2)/2 is t -> decay; the global sum is a
    # cross-process all-reduce inserted by GSPMD
    t = t - 0.1 * t
    return t, jnp.sum(t)

for _ in range(steps):
    table, total = step(table)

if phase == 1:
    ckpt.save_sharded(ckpt_dir, {"table": table})

print(f"TOTAL {float(total):.8f}", flush=True)
"""


def _run_phase(phase, port, ckpt_dir):
    # the two workers are CPU processes by design (JAX_PLATFORMS below):
    # the pytest parent has touched JAX and would hold a chip
    procs = []
    for pid in range(2):
        env = dict(
            os.environ,
            REPO=REPO,
            PROC_ID=str(pid),
            PHASE=str(phase),
            COORD=f"127.0.0.1:{port}",
            CKPT_DIR=ckpt_dir,
            XLA_FLAGS="--xla_force_host_platform_device_count=4",
            JAX_PLATFORMS="cpu",
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", WORKER],
                env=env, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True, cwd=REPO,
            )
        )
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=180)
            assert p.returncode == 0, f"worker failed:\n{err[-3000:]}"
            outs.append(out)
    finally:
        # a failed/hung worker must not leak its sibling, which would
        # otherwise block forever on the 2-process rendezvous
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    return outs


def test_two_process_mesh_and_sharded_checkpoint(tmp_path):
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    ckpt_dir = str(tmp_path / "ckpt")

    # phase 1: bootstrap 2 processes, 3 steps, save sharded state
    outs1 = _run_phase(1, port, ckpt_dir)
    # each process wrote its own shard file
    files = sorted(os.listdir(ckpt_dir))
    assert files == ["ckpt.p0.npz", "ckpt.p1.npz"], files

    # phase 2 = RESTART: fresh processes restore + 2 more steps
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port2 = s.getsockname()[1]
    outs2 = _run_phase(2, port2, ckpt_dir)

    # oracle: 5 total decay steps of the deterministic table
    V, D = 64, 4
    init = np.arange(V * D, dtype=np.float32).reshape(V, D) / (V * D)
    want = float(np.sum(init * 0.9**5))

    def total(out):
        (line,) = [
            ln for ln in out.splitlines() if ln.startswith("TOTAL ")
        ]
        return line

    for out in outs2:
        got = float(total(out).split()[-1])
        assert abs(got - want) < 1e-4, (got, want)
    # both processes agree (the all-reduce really was global)
    assert total(outs2[0]) == total(outs2[1])
    # and phase-1 totals match the 3-step oracle
    want1 = float(np.sum(init * 0.9**3))
    for out in outs1:
        assert abs(float(total(out).split()[-1]) - want1) < 1e-4
