"""Where the persistent XLA compilation cache lives.

One rule for every entry point (chip_smoke.py, benchmarks/run.py,
`python -m paddle_tpu train|serve`, the capture tools, the tests): a
directory that stays put. A cache that moves — a temporary directory, a
path with a pid or a time in it — never hits, and a cold run on the
chip is mostly compilation.

And what compiling costs, from inside: `watch()` listens to the events
JAX's own monitoring sends when it traces a function, lowers it and
compiles it (or loads it from the cache above), and publishes them as
the `compile.*` counters of the process's registry and, where a stream
or a flight recorder is attached, as `compile.trace` / `compile.lower`
/ `compile.backend` spans under whatever span the thread is in.
"""

from __future__ import annotations

import collections
import contextlib
import os
import threading
import time

from paddle_tpu.obs import metrics as _metrics
from paddle_tpu.obs import tracing as _tracing

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
    is configured here; otherwise it is `<checkout>/.jax_cache`. The
    compile watch goes on with it."""
    watch()
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


# ---- the compile watch ----
#
# JAX times three stages of every function it compiles and says so on
# `jax.monitoring`, each with a `fun_name`: tracing it to a jaxpr,
# lowering the jaxpr to a module, and the backend's compile (which is a
# LOAD where the persistent cache holds the program: a hit is timed as
# the load it is). The stages nest: tracing `step` traces every jitted
# function `step` calls, each an event of its own inside the outer one,
# and a trace may compile and run a small program on the way. So a
# thread keeps a stack of the stages it is in; a stage that ends gives
# its own seconds (its duration less the stages inside it) to the stage
# at the bottom of the stack, the ROOT: the function somebody called.
# When a root ends, what it and everything it pulled in spent is
# published under ITS name: no second is counted twice, and the series
# are as many as the functions a program calls, not as the primitives
# they are made of.

_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_COUNTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
SPENT = ("trace_s", "lower_s", "backend_s", "cache_hits", "cache_misses")
_ROOT = SPENT + ("programs",)     # what a root's record holds
# JAX sends the trace event also where it only FOUND the jaxpr in a cache
# of its own: the eager fold of a step's rng key sends one of 10 us every
# step. Such a root is counted and not spanned: a ring of recent spans is
# for what took time.
SPAN_FLOOR_S = 1e-3

_watch_lock = threading.Lock()
_watching = False
# what the last roots spent: (thread, t1_ns, {SPENT key: value}), for
# `spent()`; a first dispatch is a handful of roots, a recompile three
_recent = collections.deque(maxlen=512)


class _Stack(threading.local):
    def __init__(self):
        self.open = []      # [seconds of the stages inside], innermost last
        self.root = None    # what the root and all inside it spent


_stack = _Stack()


def _fn(fun_name) -> str:
    """`step` of JAX's `step` (a trace) and `jit(step)` (its module)."""
    name = str(fun_name)
    if name.endswith(")") and "(" in name:
        return name[name.index("(") + 1:-1]
    return name


def _on_start(event, _value, **_):
    if event in _STAGES:
        if not _stack.open:
            _stack.root = dict.fromkeys(_ROOT, 0)
        _stack.open.append(0.0)


def _on_duration(event, secs, fun_name="", **_):
    stage = _STAGES.get(event)
    if stage is None:
        if event == _CACHE_LOAD:
            _metrics.get_registry().counter("compile.cache_load_s").inc(secs)
        return
    inside = _stack.open.pop() if _stack.open else 0.0
    root = _stack.root
    if root is None:    # begun before the watch: a root of its own
        root = dict.fromkeys(_ROOT, 0)
    root[stage + "_s"] += max(secs - inside, 0.0)
    if stage == "backend":
        root["programs"] += 1
    if _stack.open:
        _stack.open[-1] += secs
        return
    _stack.root = None
    now = time.monotonic_ns()
    fn = _fn(fun_name)
    reg = _metrics.get_registry()
    for key in ("trace_s", "lower_s", "backend_s", "programs"):
        if root[key]:
            reg.counter("compile." + key).inc(root[key], fn=fn)
    with _watch_lock:
        _recent.append((threading.get_ident(), now, root))
    if secs < SPAN_FLOOR_S or (reg.stream is None and reg.recorder is None):
        return
    trace_id, parent_id = _tracing.current() or (_tracing.new_trace_id(), "")
    _tracing.emit_span(
        "compile." + stage, trace_id, _tracing.new_span_id(), parent_id,
        dur_s=secs, t0_ns=now - int(secs * 1e9), t1_ns=now,
        labels={"fn": fn}, registry=reg)


def _on_event(event, **_):
    key = _CACHE_COUNTS.get(event)
    if key is None:
        return
    _metrics.get_registry().counter("compile." + key).inc()
    if _stack.root is not None:
        _stack.root[key] += 1


def watch() -> None:
    """Listen to JAX's compile events, once a process (`enable()` and
    every `TrainStep` call this; a second call does nothing). Counters,
    each root's seconds by `fn`, the root's name: `compile.trace_s`,
    `compile.lower_s`, `compile.backend_s` (a compile, or the load a
    cache hit is timed as) and `compile.programs` (programs compiled or
    loaded); `compile.cache_load_s`, `compile.cache_hits`,
    `compile.cache_misses`. A root's end is also a finished span
    `compile.trace` / `compile.lower` / `compile.backend` (label `fn`,
    its whole duration, from `SPAN_FLOOR_S` up) under the thread's
    current span, where a stream or a flight recorder is attached:
    under a trainer's first `train.dispatch` they show where its
    seconds went, and a recompile in steady state names itself in the
    recorder's ring. Events fire only when JAX traces or compiles: a
    cached dispatch sends none."""
    global _watching
    with _watch_lock:
        if _watching:
            return
        _watching = True
    import jax.monitoring as mon

    mon.register_scalar_listener(_on_start)
    mon.register_event_duration_secs_listener(_on_duration)
    mon.register_event_listener(_on_event)


def spent(t0_ns: int, t1_ns: int) -> dict:
    """What the roots that ended on THIS thread between two readings of
    the monotonic clock spent: seconds tracing, lowering and in the
    backend, cache hits and misses (`SPENT`'s keys). Zeros where
    nothing compiled, or where it was too long ago: the last 512 roots
    of the process are kept."""
    me = threading.get_ident()
    with _watch_lock:
        recent = list(_recent)
    out = dict.fromkeys(SPENT, 0)
    for thread, at, root in recent:
        if thread == me and t0_ns <= at <= t1_ns:
            for key in SPENT:
                out[key] += root[key]
    return out
