"""Where the persistent XLA compilation cache lives.

One rule for every entry point (chip_smoke.py, benchmarks/run.py,
`python -m paddle_tpu train|serve`, the capture tools, the tests): a
directory that stays put. A cache that moves — a temporary directory, a
path with a pid or a time in it — never hits, and a cold run on the
chip is mostly compilation.
"""

from __future__ import annotations

import contextlib
import os
import threading

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)

# `bypassed()` flips a process-global JAX option: blocks that overlap
# (an online-learning thread in `SparseUpdater`, a serving thread in
# `store_verified`) are counted, and the option goes back when the last
# one leaves
_bypass_lock = threading.Lock()
_bypass_depth = 0
_bypass_restore = None


def enable() -> str:
    """Turn the persistent cache on and return its directory. Where
    `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and nothing
    is configured here; otherwise it is `<checkout>/.jax_cache`."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


@contextlib.contextmanager
def bypassed():
    """Compile inside this block without the persistent cache, reads
    and writes both. For the few programs whose executable is itself
    serialized afterwards (`inference.store_verified`): on the CPU
    backend of JAX 0.9.0 an executable that came OUT of the persistent
    cache serializes into one that no longer loads ("Function
    broadcast_dot_fusion.N not found"), so such a program has to be
    compiled, not reloaded."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    global _bypass_depth, _bypass_restore
    with _bypass_lock:
        if _bypass_depth == 0:
            _bypass_restore = jax.config.jax_enable_compilation_cache
            jax.config.update("jax_enable_compilation_cache", False)
            compilation_cache.reset_cache()
        _bypass_depth += 1
    try:
        yield
    finally:
        with _bypass_lock:
            _bypass_depth -= 1
            if _bypass_depth == 0:
                jax.config.update("jax_enable_compilation_cache",
                                  _bypass_restore)
                compilation_cache.reset_cache()
