"""Device mesh bootstrap.

Replaces the reference's entire communication stack — the in-node ring of
MultiGradientMachine (gserver/gradientmachines/MultiGradientMachine.cpp:389),
the C++ pserver star topology (paddle/pserver/LightNetwork.h:40), and the Go
pserver (go/pserver) — with a single ``jax.sharding.Mesh`` whose collectives
XLA compiles onto ICI/DCN.

Canonical axis names:
  data  — data parallel (batch split, grads psum'd)
  model — tensor/model parallel (weight shards)
  seq   — sequence/context parallel (ring attention / all-to-all)
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh

DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"

_current_mesh: Optional[Mesh] = None


def make_mesh(shape: Optional[dict] = None, devices=None) -> Mesh:
    """Build a mesh. `shape` maps axis name -> size; a size of -1 takes
    every remaining device. Default: all devices on the `data` axis —
    the analogue of `trainer_count` data parallelism
    (reference: paddle/utils/Flags.cpp trainer_count)."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if not shape:
        shape = {DATA_AXIS: n}
    names = list(shape.keys())
    sizes = list(shape.values())
    if -1 in sizes:
        known = math.prod(s for s in sizes if s != -1)
        if n % known != 0:
            raise ValueError(
                f"mesh shape {dict(zip(names, sizes))}: -1 cannot take the "
                f"remaining devices ({known} does not divide {n})"
            )
        sizes[sizes.index(-1)] = n // known
    prod = math.prod(sizes)
    if prod > n:
        raise ValueError(f"mesh shape {dict(zip(names, sizes))} > {n} devices")
    if prod < n:
        import logging

        logging.getLogger(__name__).warning(
            "mesh shape %s uses %d of %d devices (prefix sub-mesh)",
            dict(zip(names, sizes)), prod, n,
        )
    dev_array = np.asarray(devices[:prod]).reshape(sizes)
    return Mesh(dev_array, axis_names=tuple(names))


def set_mesh(mesh: Mesh) -> None:
    global _current_mesh
    _current_mesh = mesh


def get_mesh() -> Mesh:
    global _current_mesh
    if _current_mesh is None:
        _current_mesh = make_mesh()
    return _current_mesh


def distributed_init(coordinator_address=None, num_processes=None, process_id=None):
    """Multi-host control-plane bootstrap (replaces etcd registration of
    go/pserver/etcd_client.go and the sockets of pserver/LightNetwork.h)."""
    if coordinator_address is not None:
        # the CPU backend runs cross-process programs only over gloo,
        # and the option is read when the backend is created; it has
        # no effect on a TPU
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
