"""Multi-host job launcher — `python -m paddle_tpu launch`.

Reference: paddle/scripts/cluster_train/paddle.py:24-157 — the fabric/
ssh launcher that started pservers + trainers on every node of a
cluster with the right ports/trainer_id environment. The TPU-native
equivalent is much smaller because there are no pserver processes:
one process per host joins a `jax.distributed` rendezvous (the
coordinator is process 0) and the SAME jit-compiled program runs SPMD
across all hosts' chips — the launcher only has to start the processes
with the right coordinator/world/rank environment.

    python -m paddle_tpu launch --hosts a,b,c -- \
        python -m paddle_tpu train --config cfg.py

Local smoke form (and the unit-tested path): --hosts localhost with
--nproc-per-host N starts N local processes. Remote hosts are reached
via plain `ssh` (the reference assumed the binaries/data are already
installed on every node — same contract, cluster_train/paddle.py
job_prepare docstring).

Environment protocol (read by `distributed_init_from_env`):
    PADDLE_COORDINATOR  host:port of process 0's coordinator
    PADDLE_NUM_PROCESSES / PADDLE_PROCESS_ID  world size / rank
"""

from __future__ import annotations

import os
import shlex
import subprocess
import sys
import threading

__all__ = ["launch", "distributed_init_from_env", "main"]


def distributed_init_from_env(env=os.environ) -> bool:
    """Join the rendezvous the launcher described in the environment.
    Returns True if distributed mode was initialized."""
    coord = env.get("PADDLE_COORDINATOR")
    if not coord:
        return False
    from paddle_tpu.core import flags as _flags
    from paddle_tpu.core.mesh import distributed_init

    n = int(env.get("PADDLE_NUM_PROCESSES", "1"))
    pid = int(env.get("PADDLE_PROCESS_ID", "0"))
    _flags.set_flag("coordinator_address", coord)
    _flags.set_flag("num_processes", n)
    _flags.set_flag("process_id", pid)
    distributed_init(
        coordinator_address=coord, num_processes=n, process_id=pid
    )
    return True


def _is_local(host: str) -> bool:
    return host in ("localhost", "127.0.0.1", "::1")


def _stream(proc, tag):
    """Prefix a worker's stdout lines (the launcher's merged log —
    cluster_train/paddle.py tailed per-node logs instead)."""

    def pump():
        for line in proc.stdout:
            sys.stdout.write(f"[{tag}] {line}")
            sys.stdout.flush()

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    return t


def launch(
    hosts,
    command,
    nproc_per_host: int = 1,
    coordinator_port: int = 7164,
    ssh_opts=(),
    extra_env=None,
    max_respawns: int = 3,
) -> int:
    """Start `command` on every host with the rendezvous environment;
    wait for all; kill the survivors if any process fails. Returns the
    first non-zero exit code (0 = all succeeded).

    A rank that exits with `EXIT_PREEMPTED` (75 — the trainer's
    SIGTERM contract, trainer/watchdog.py) is NOT a failure: it
    flushed a checkpoint and asked to be restarted, so the launcher
    respawns it in place (up to `max_respawns` times per rank) and the
    respawned trainer auto-resumes from the flushed checkpoint."""
    from paddle_tpu.trainer.watchdog import EXIT_PREEMPTED

    if isinstance(hosts, str):
        hosts = [h.strip() for h in hosts.split(",") if h.strip()]
    world = len(hosts) * nproc_per_host
    coord_host = hosts[0] if not _is_local(hosts[0]) else "127.0.0.1"
    coord = f"{coord_host}:{coordinator_port}"

    # This launcher never imports JAX: a parent that had touched it
    # would hold the host's chips, and the workers need them.
    def _spawn(host, rank):
        env_kv = {
            "PADDLE_COORDINATOR": coord,
            "PADDLE_NUM_PROCESSES": str(world),
            "PADDLE_PROCESS_ID": str(rank),
            **(extra_env or {}),
        }
        if _is_local(host):
            p = subprocess.Popen(
                command,
                env={**os.environ, **env_kv},
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        else:
            # the reference's fabric run() ≙ plain ssh; quoting via
            # shlex so the command survives the remote shell
            remote = "cd {wd} && {env} {cmd}".format(
                wd=shlex.quote(os.getcwd()),
                env=" ".join(
                    f"{k}={shlex.quote(v)}" for k, v in env_kv.items()
                ),
                cmd=" ".join(shlex.quote(c) for c in command),
            )
            p = subprocess.Popen(
                ["ssh", *ssh_opts, host, remote],
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        _stream(p, f"rank{rank}@{host}")
        return p

    slots = []  # rank -> (host,)
    procs = []
    rank = 0
    for host in hosts:
        for _ in range(nproc_per_host):
            slots.append(host)
            procs.append(_spawn(host, rank))
            rank += 1
    respawns = [0] * len(procs)

    rc = 0
    try:
        # Poll ALL ranks, not procs[0] first: a crash on a later rank
        # must be observed even while earlier ranks block forever in a
        # collective waiting for it.
        import time as _time

        live = list(range(len(procs)))
        while live:
            for r in list(live):
                code = procs[r].poll()
                if code is None:
                    continue
                if (code == EXIT_PREEMPTED
                        and respawns[r] < max_respawns):
                    # preemption, not failure: restart the rank; its
                    # trainer resumes from the flushed checkpoint
                    respawns[r] += 1
                    sys.stdout.write(
                        f"[launch] rank{r} preempted (exit "
                        f"{EXIT_PREEMPTED}); respawn "
                        f"{respawns[r]}/{max_respawns}\n"
                    )
                    sys.stdout.flush()
                    procs[r] = _spawn(slots[r], r)
                    continue
                live.remove(r)
                if code and not rc:
                    rc = code
                    # fail fast: a dead member blocks the collective
                    # for everyone else — bring the job down
                    for q in procs:
                        if q.poll() is None:
                            q.kill()
            if live:
                _time.sleep(0.05)
    finally:
        for q in procs:
            if q.poll() is None:
                q.kill()
                q.wait()
    return rc


def main(args) -> int:
    return launch(
        args.hosts,
        args.command,
        nproc_per_host=args.nproc_per_host,
        coordinator_port=args.port,
        ssh_opts=shlex.split(args.ssh_opts) if args.ssh_opts else (),
        max_respawns=getattr(args, "max_respawns", 3),
    )
