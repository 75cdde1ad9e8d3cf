"""Distributed tracing: spans, context, and cross-process carriers.

PR10's registry answers "how much, in aggregate"; this module answers
"where did THIS request / THIS RPC / THIS bad step spend its time" —
the reference's per-operation timer machinery (utils/Stat.h
REGISTER_TIMER around one operation) generalized to a causally-linked
span tree that survives process boundaries:

- A **span** is one named, timed operation: `trace_id` (shared by the
  whole causal chain), `span_id`, `parent_id`, a wall-clock start
  (`ts`), a duration (`dur_s`), its start and end on the process's
  monotonic nanosecond clock (`t0_ns`, `t1_ns`), free-form `labels`,
  and a `status` ("ok" or a failure reason). Finished spans are emitted
  as `kind="span"` events on the registry's JSONL EventStream (and into
  the flight-recorder ring when one is attached) — there is no second
  export pipe to keep alive.

- **`span(...)`** is the one way to time a piece of a hot loop: ONE
  pair of clock reads serves three readers. The caller gets the
  duration (`Span.dur_ns` after the block; the trainer adds it to its
  `StepTimeline`, so counters and spans cannot disagree); the event
  above is emitted when a stream or a recorder is attached; and the
  block runs inside a `jax.profiler.TraceAnnotation` of the same name
  (`StepTraceAnnotation` when `step_num` is given), so whenever a
  profiler session is on, the span lies in the `.xplane.pb` beside
  the device's operations, on the profiler's clock, its labels as the
  event's arguments. "Tracing on" is a profiler session or an attached
  sink, nothing else: with neither, a span is two clock reads and an
  inactive TraceMe. Spans nest through a thread-local context.

- Code that crosses threads or stamps spans post-hoc from timestamps
  it already measured (the serving scheduler) uses the explicit API:
  `new_trace_id()` / `new_span_id()` / `emit_span(...)`.

- The **carrier** is an explicit dict `{"trace_id": ..., "span_id":
  ...}` — small enough to ride any protocol that can carry two
  strings (the serving TCP JSON frame's `trace` field, an env var for
  spawned workers). `inject()` captures the current context into a
  carrier; `attach(carrier)` makes a remote parent the local context
  so this process's spans join the caller's trace.

The trainer spans every step (`train.step` and its children, see
`SGD.train`); serving samples (every carrier-bearing request plus
every `trace_serve_period`-th anonymous one). Ids of a trace begun in
this process come from a process counter; only spans that join a
carrier's trace draw random ids, because other processes add to it.

No jax import, at module scope or later (module scope is linted by
`ast_lint.check_jax_import_fence`): the profiler is taken from
`sys.modules` when the process has jax loaded already, so tracing
works in the TCP front end, the master client and data workers
without a device runtime.
"""

from __future__ import annotations

import binascii
import itertools
import os
import sys
import threading
import time
from typing import Optional

from paddle_tpu.obs import metrics as _metrics

# env var a parent process sets to make a child's spans join its
# trace (the spawned-worker analogue of the TCP `trace` field)
CARRIER_ENV = "PADDLE_TRACE_CARRIER"


def new_trace_id() -> str:
    """128-bit random hex — collision-safe across processes."""
    return binascii.hexlify(os.urandom(16)).decode()


def new_span_id() -> str:
    """64-bit random hex."""
    return binascii.hexlify(os.urandom(8)).decode()


_counter = itertools.count(1)
_process_tag = ""


def _retag() -> None:
    """What tells the traces this process begins from another's: the
    pid and the clock, taken again in a forked child."""
    global _process_tag
    _process_tag = "%08x%08x" % (os.getpid() & 0xFFFFFFFF,
                                 time.time_ns() >> 10 & 0xFFFFFFFF)


_retag()
os.register_at_fork(after_in_child=_retag)


def _counted_id() -> str:
    """64-bit hex from the process counter, for the ids of a trace
    begun here: nothing but this process numbers its spans."""
    return "%016x" % next(_counter)


class _Context(threading.local):
    def __init__(self):
        # [(trace_id, span_id, begun_here), ...]: begun_here is True
        # for a trace this process started without a carrier
        self.stack = []


_ctx = _Context()


def current() -> Optional[tuple]:
    """(trace_id, span_id) of the innermost active span/attachment in
    this thread, or None."""
    return _ctx.stack[-1][:2] if _ctx.stack else None


def context() -> Optional[tuple]:
    """This thread's innermost context as it is, for `attach` on
    another thread of THIS process (a worker's spans join the trace
    and keep its counted ids). Across processes, `inject` a carrier."""
    return _ctx.stack[-1] if _ctx.stack else None


def inject() -> Optional[dict]:
    """Current context as a carrier dict, or None outside any trace."""
    cur = current()
    if cur is None:
        return None
    return {"trace_id": cur[0], "span_id": cur[1]}


def extract(carrier) -> Optional[tuple]:
    """Parse a carrier dict into (trace_id, parent_span_id); None on
    anything malformed — a bad carrier degrades to an untraced
    operation, never an error on the serving path."""
    if not isinstance(carrier, dict):
        return None
    tid, sid = carrier.get("trace_id"), carrier.get("span_id")
    if not isinstance(tid, str) or not tid:
        return None
    if not isinstance(sid, str) or not sid:
        sid = ""
    return tid, sid


class attach:
    """Context manager: make `carrier` (or what `context()` gave on
    another thread of this process) the current context WITHOUT
    opening a span — spans created inside become children of the
    remote parent. A None/malformed carrier attaches nothing (the
    body still runs), unless `or_begin`: then, where the thread is in
    no trace either, a new trace begins here (ids from the process
    counter) and the spans inside are its roots."""

    def __init__(self, carrier, or_begin: bool = False):
        if isinstance(carrier, tuple):  # another thread's `context()`
            self._entry = carrier
            return
        parsed = extract(carrier)
        if parsed is not None:
            self._entry = parsed + (False,)
        elif or_begin and not _ctx.stack:
            self._entry = (_process_tag + _counted_id(), "", True)
        else:
            self._entry = None

    def __enter__(self):
        if self._entry is not None:
            _ctx.stack.append(self._entry)
        return self

    def __exit__(self, *exc):
        if self._entry is not None:
            _ctx.stack.pop()
        return False


def attach_from_env(or_begin: bool = False):
    """`attach` using the CARRIER_ENV env var (JSON carrier) — how a
    spawned worker joins the trace of the process that launched it."""
    import json

    raw = os.environ.get(CARRIER_ENV)
    carrier = None
    if raw:
        try:
            carrier = json.loads(raw)
        except ValueError:
            carrier = None
    return attach(carrier, or_begin=or_begin)


class Span:
    """One operation, in flight or finished. Created by `span(...)`
    (context-managed, thread-local nesting) or `start_span(...)`
    (manual; caller must call `finish()`). The clock is read at
    creation and at finish(); emission happens at finish()."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "labels",
                 "status", "t0_ns", "t1_ns", "_registry", "_finished")

    def __init__(self, name: str, trace_id: str, parent_id: str,
                 labels: Optional[dict] = None, registry=None):
        self.name = name
        self.trace_id = trace_id
        self.span_id = new_span_id()
        self.parent_id = parent_id or ""
        self.labels = labels if labels is not None else {}
        self.status = "ok"
        self._registry = registry
        self._finished = False
        self.t1_ns = None
        self.t0_ns = time.monotonic_ns()

    @property
    def dur_ns(self) -> int:
        """Nanoseconds from start to finish (so far, while open)."""
        end = self.t1_ns if self.t1_ns is not None else time.monotonic_ns()
        return end - self.t0_ns

    @property
    def dur_s(self) -> float:
        return self.dur_ns * 1e-9

    def set_label(self, key: str, value) -> None:
        self.labels[str(key)] = value

    def discard(self) -> None:
        """This turned out to be no operation (the reader's last call,
        which only found the pass over): emit nothing for it."""
        self._finished = True

    def finish(self, status: Optional[str] = None) -> None:
        if self.t1_ns is None:
            self.t1_ns = time.monotonic_ns()
        if self._finished:
            return
        self._finished = True
        if status is not None:
            self.status = status
        reg = self._registry or _metrics.get_registry()
        if reg.stream is None and reg.recorder is None:
            return
        emit_span(
            self.name, self.trace_id, self.span_id, self.parent_id,
            dur_s=self.dur_s, t0_ns=self.t0_ns, t1_ns=self.t1_ns,
            status=self.status, labels=self.labels, registry=reg,
        )


_profiler = None  # jax.profiler, once the process has it loaded


def _annotation(name: str, step_num, labels: dict):
    """The profiler's TraceMe for a span, or None in a process that
    has not loaded jax (where no profiler session can be on). Labels
    ride as the event's arguments; its name stays `name`."""
    global _profiler
    if _profiler is None:
        _profiler = getattr(sys.modules.get("jax"), "profiler", None)
        if _profiler is None:
            return None
    if step_num is None:
        return _profiler.TraceAnnotation(name, **labels)
    return _profiler.StepTraceAnnotation(name, step_num=step_num, **labels)


class span(Span):
    """`with span("master.get_task", op=2) as s:` — child of the
    current thread context (or the root of a brand-new trace), pushed
    while the body runs, finished on exit; an exception marks status
    "error" and propagates. After the block `s.dur_ns` / `s.dur_s` is
    what the one pair of clock reads measured. `step_num` makes it a
    step's root in the profiler's trace (StepTraceAnnotation)."""

    __slots__ = ("_step_num", "_annotation")

    def __init__(self, name: str, registry=None, step_num=None,
                 **labels):
        self.name = name
        self.labels = labels
        self.status = "ok"
        self._registry = registry
        self._finished = False
        self._step_num = step_num
        self.t1_ns = None

    def __enter__(self) -> Span:
        stack = _ctx.stack
        if stack:
            self.trace_id, self.parent_id, begun_here = stack[-1]
        else:
            self.trace_id, self.parent_id, begun_here = (
                _process_tag + _counted_id(), "", True)
        self.span_id = _counted_id() if begun_here else new_span_id()
        stack.append((self.trace_id, self.span_id, begun_here))
        self._annotation = _annotation(
            self.name, self._step_num, self.labels)
        if self._annotation is not None:
            self._annotation.__enter__()
        if self._step_num is not None:
            self.labels["step_num"] = self._step_num
        self.t0_ns = time.monotonic_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.t1_ns = time.monotonic_ns()
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        _ctx.stack.pop()
        if exc_type is not None and self.status == "ok":
            self.status = "error"
        self.finish()
        return False


def start_span(name: str, trace_id: Optional[str] = None,
               parent_id: Optional[str] = None, registry=None,
               **labels) -> Span:
    """Manual span: NOT pushed on the thread context (safe to finish
    from another thread). Defaults parent to the current context."""
    if trace_id is None:
        cur = current()
        if cur is not None:
            trace_id, parent_id = cur[0], parent_id or cur[1]
        else:
            trace_id = new_trace_id()
    return Span(name, trace_id, parent_id or "", labels,
                registry=registry)


def emit_span(name: str, trace_id: str, span_id: str, parent_id: str,
              dur_s: float, ts: Optional[float] = None,
              t0_mono: Optional[float] = None, status: str = "ok",
              labels: Optional[dict] = None, registry=None,
              t0_ns: Optional[int] = None,
              t1_ns: Optional[int] = None) -> None:
    """Emit one finished span record (post-hoc path: the caller
    already measured the interval). `ts` is the wall-clock START; when
    only a monotonic start is known (`t0_mono` seconds or `t0_ns`),
    the wall start is recovered via the current mono->wall offset
    (valid within one process — exactly where monotonic stamps come
    from). `t0_ns`/`t1_ns`, where given, are recorded as they are."""
    if t0_mono is None and t0_ns is not None:
        t0_mono = t0_ns * 1e-9
    if ts is None:
        if t0_mono is not None:
            ts = time.time() - (time.monotonic() - t0_mono)
        else:
            ts = time.time() - dur_s
    reg = registry or _metrics.get_registry()
    ends = {} if t0_ns is None else {"t0_ns": t0_ns, "t1_ns": t1_ns}
    reg.event(
        "span",
        name=name,
        trace_id=trace_id,
        span_id=span_id,
        parent_id=parent_id or "",
        ts=round(ts, 6),
        dur_s=round(dur_s, 9),
        status=status,
        labels=labels or {},
        **ends,
    )
