"""What the program's own spans and scopes say of a traced window.

`SGD.train` opens a span at every boundary of a step (`train.step` and its
children, paddle_tpu/obs/tracing.py); under a profiler session each lies in
the `.xplane.pb` as a host event of that name, on the clock of the device's
operations. Here the device's idle time is put down to those spans exactly:
an idle gap is split among the spans that overlap it, by overlap, and where
spans nest the innermost takes what it covers (trace_reduce.cover). The
`feed-ahead` worker's spans lie on a thread of their own and are timed
beside them. And the device's busy time is put down to the scope its
operations carry (`<type>:<name>` of a layer, `optimizer`, `watchdog`).

A trace of a program that has no such spans (the parent of the PR that
brought them) gives None, and the readers then report nothing.
"""

from __future__ import annotations

import functools
import os
import re

from benchmarks import harness, trace_reduce
from benchmarks.trace_reduce import (TOP_GAPS, UNCOVERED, clip, cover, gaps,
                                     total, union)

# Nothing here reads it since run.py hands the readers the trace's path;
# tests/test_benchmark_harness.py, which no `benchmark` PR may edit, patches it
TRACE_DIR = os.path.join(harness.ROOT, ".bench_trace")
WINDOW_SPAN = "bench_window"                             # run.py's
ROOT_SPAN = "train.step"
# the training thread's, innermost first: a span takes of a gap only what
# no span before it took
SPANS = ("train.h2d", "train.input_wait.feeder", "train.dispatch",
         "train.fetch", "train.fence", "train.handlers", "train.checkpoint",
         ROOT_SPAN)
# the `feed-ahead` worker's, on a thread of its own: timed, and no part of
# the cover, or the worker, which is always inside its feeder, would take
# every idle gap
WORKER_SPANS = ("feed_ahead.reader", "feed_ahead.feeder")


def split(device_ops, host_spans, window, order=SPANS):
    """The arithmetic, on plain data (trace_reduce.read's), in its unit of
    time. -> the window's length, the busy time of the fullest device, the
    time in the window of each span of `host_spans` (those of `order` and
    any beside them, such as the worker's), the idle time by span of
    `order`, and the longest idle gaps with what covers each."""
    lo, hi = window
    if not device_ops or hi <= lo:
        return None
    busy = {d: union(clip([(s, e) for _, s, e in ops], lo, hi))
            for d, ops in device_ops.items()}
    fullest = max(busy, key=lambda d: total(busy[d]))
    idle = gaps(busy[fullest], lo, hi)
    spans = {n: clip(iv, lo, hi) for n, iv in host_spans.items()}
    by_span = cover(idle, spans, order)
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:TOP_GAPS]
    return {
        "window": hi - lo,
        "busy": total(busy[fullest]),
        "span_time": {n: total(iv) for n, iv in spans.items()},
        "idle_by_span": {n: total(iv) for n, iv in by_span.items()},
        "gaps": [(e - s, {n: total(iv)
                          for n, iv in cover([(s, e)], spans, order).items()
                          if iv}) for s, e in longest],
    }


def _trace_of(run):
    """(path, mtime) of the traced run's file, which run.py hands over as
    `trace_file`, or None where the run has no trace."""
    path = run.get("trace_file") if run.get("trace") else None
    return (path, os.path.getmtime(path)) if path else None


@functools.lru_cache(maxsize=1)
def _read(path, mtime):
    """One pass over the file serves both reductions."""
    return trace_reduce.read(path, SPANS + WORKER_SPANS, WINDOW_SPAN)


@functools.lru_cache(maxsize=1)
def _split_file(path, mtime):
    device_ops, host_spans, window = _read(path, mtime)
    if not host_spans.get(ROOT_SPAN) or window is None:
        return None
    r = split(device_ops, host_spans, window)
    if r is None:
        return None
    ns = 1e-9
    out = {"window_s": r["window"] * ns, "busy_s": r["busy"] * ns,
           "span_s": {n: t * ns for n, t in r["span_time"].items()},
           "idle_s": {n: t * ns for n, t in r["idle_by_span"].items()},
           "gaps": [[g * ns, {n: t * ns for n, t in c.items()}]
                    for g, c in r["gaps"]]}
    harness.say(program_spans=out)
    return out


def of_run(run):
    """`split` of the traced run's file, in seconds, or None: no trace, or
    a program without the spans. Said once on an earlier line of the log."""
    found = _trace_of(run)
    return _split_file(*found) if found else None


# ---- the device's busy time by scope ----

_LAYER = re.compile(r"[A-Za-z0-9_\-]+:[^/()]+")
STEP_SCOPES = ("optimizer", "watchdog")


def scope_of(op_name: str) -> str:
    """The program's scope in an operation's `op_name` (the path of
    jax.named_scope names its metadata carries): `batch_norm:bn2a` from
    `jit(step)/transpose(jvp(batch_norm:bn2a))/mul`, `optimizer`,
    `watchdog`; the outermost where several nest; `` where none."""
    for part in op_name.split("/"):
        if part in STEP_SCOPES:
            return part
        m = _LAYER.search(part)
        if m:
            return m.group(0)
    return ""


def in_classes(scope: str, classes) -> bool:
    """`batch_norm:` takes every layer of that type, `optimizer` itself."""
    return any(scope.startswith(c) if c.endswith(":") else scope == c
               for c in classes)


# An operation's scope is the `tf_op` stat of its XEventMetadata. That is
# where the v5e's trace carries it, and nowhere `ProfileData` shows: an
# event there has its name (the HLO line) and its own three stats. So the
# metadata is read off the file's bytes: the few fields of
# tsl/profiler/protobuf/xplane.proto that lead to it, by field number.
_PLANES, _PLANE_NAME, _EVENT_METADATA, _STAT_METADATA = 1, 2, 4, 5
_MAP_VALUE = 2
_META_NAME, _META_STATS = 2, 5
_STAT_ID, _STAT_STR, _STAT_REF = 1, 5, 7
SCOPE_STAT = "tf_op"


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited field; fixed-width fields skipped."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                break
        field, wire = key >> 3, key & 7
        if wire == 0 or wire == 2:
            value = shift = 0
            while True:
                b = buf[i]
                i += 1
                value |= (b & 0x7F) << shift
                shift += 7
                if b < 0x80:
                    break
            if wire == 2:
                value, i = buf[i:i + value], i + value
            yield field, value
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane")


def scopes_of_file(path) -> dict:
    """{plane name: {operation's name (the whole HLO line, as ProfileData
    gives it): its `tf_op`}} of the device planes of an `.xplane.pb`."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != _PLANES:
            continue
        parts = list(_fields(plane))
        name = next((bytes(v).decode() for f, v in parts
                     if f == _PLANE_NAME), "")
        if not name.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        stat_names = {}
        for f, entry in parts:
            if f == _STAT_METADATA:
                meta = dict(_fields(dict(_fields(entry))[_MAP_VALUE]))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        ops = out.setdefault(name, {})
        for f, entry in parts:
            if f != _EVENT_METADATA:
                continue
            op, scope = "", ""
            for mf, mv in _fields(dict(_fields(entry))[_MAP_VALUE]):
                if mf == _META_NAME:
                    op = bytes(mv).decode()
                elif mf == _META_STATS:
                    stat = dict(_fields(mv))
                    if stat_names.get(stat.get(_STAT_ID)) == SCOPE_STAT:
                        scope = (bytes(stat[_STAT_STR]).decode()
                                 if _STAT_STR in stat
                                 else stat_names.get(stat.get(_STAT_REF), ""))
            if op and scope:
                ops[op] = scope
    return out


def busy_by_scope(device_ops, scopes, window):
    """The arithmetic, on plain data: `device_ops` and `window` as
    trace_reduce.read gives them, `scopes` {device: {operation: op_name}}
    under the names `device_ops` uses. -> the busy time of the fullest
    device in the window, in all and by scope (`` for none), or None."""
    best = None
    for dev, ops in device_ops.items():
        table, by = scopes.get(dev, {}), {}
        for name, s, e in ops:
            by.setdefault(scope_of(table.get(name, "")), []).append((s, e))
        by = {sc: union(clip(iv, *window)) for sc, iv in by.items()}
        busy = total(union([x for iv in by.values() for x in iv]))
        if best is None or busy > best["busy"]:
            best = {"busy": busy,
                    "by_scope": {sc: total(iv) for sc, iv in by.items()}}
    return best


@functools.lru_cache(maxsize=1)
def _busy_file(path, mtime):
    device_ops, _, window = _read(path, mtime)
    if window is None:
        return None
    try:
        scopes = {dev: {trace_reduce.short_name(op): scope
                        for op, scope in table.items()}
                  for dev, table in scopes_of_file(path).items()}
    except (ValueError, IndexError, KeyError, UnicodeDecodeError) as e:
        harness.say(scopes_unread=f"{type(e).__name__}: {e}")
        return None
    r = busy_by_scope(device_ops, scopes, window)
    if r is None:
        return None
    ns = 1e-9
    by_type = {}
    for sc, t in r["by_scope"].items():
        key = sc.split(":")[0] + (":" if ":" in sc else "")
        by_type[key] = by_type.get(key, 0.0) + t * ns
    harness.say(busy_by_scope_type_s=dict(sorted(
        by_type.items(), key=lambda kv: -kv[1])))
    return {"busy_s": r["busy"] * ns,
            "scoped_s": sum(t for sc, t in r["by_scope"].items() if sc) * ns,
            "by_scope_s": {sc: t * ns for sc, t in r["by_scope"].items()}}


def busy_of_run(run):
    """`busy_by_scope` of the traced run's file, in seconds, or None."""
    found = _trace_of(run)
    return _busy_file(*found) if found else None
