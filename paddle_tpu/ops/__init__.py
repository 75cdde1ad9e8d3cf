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


def note_kept(*values) -> None:
    """Count `values` (anything with a shape and a dtype) into the counter
    `recompute.tagged_bytes`: what the code being traced has tagged
    (`jax.ad_checkpoint.checkpoint_name`) with a name a recompute group
    keeps (`network._KEEP`). `Network._run_group` reads the counter around
    a group's trace for its gauge `recompute.kept_bytes`. Counted by the
    op that tags, once a call (a `custom_vjp`'s forward rule is traced again
    when the call is differentiated, so its primal function counts), because
    asking jax costs a second trace of every group: the residuals of each
    group's linearisation took 4.8 s on the chip's host and gave 2.2 s back
    through jax's caches, 2.6 s on the 2.5 s the Kimi step takes to trace
    (PR 36). `tests/test_recompute_keep.py` holds the count to that reading."""
    from paddle_tpu import obs

    obs.get_registry().counter("recompute.tagged_bytes").inc(
        sum(v.size * v.dtype.itemsize for v in values))
