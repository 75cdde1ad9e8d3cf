"""Kernels and array ops. This module itself holds the one rule every
Pallas call site in the tree follows for interpret mode."""

import jax


def pallas_interpret(requested=None) -> bool:
    """Interpret mode for a Pallas call site: on a TPU it is off, and
    asking for it there raises; everywhere else (the CPU tests) it is
    on unless the caller says otherwise. The device query is left
    unguarded on purpose — a backend that cannot start has to fail the
    caller, not choose a mode for it."""
    on_tpu = jax.default_backend() == "tpu"
    if requested is None:
        return not on_tpu
    if requested and on_tpu:
        raise ValueError(
            "Pallas interpret mode was requested on a TPU: the kernel "
            "would run as plain XLA ops and every number read off it "
            "would describe another program"
        )
    return bool(requested)
