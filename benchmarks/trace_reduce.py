"""From a profiler trace (`.xplane.pb`) to busy time, top operations and
idle gaps. Reads the file with `jax.profiler.ProfileData`, nothing else.

A device plane is one whose name starts with `/device:TPU:`; its line
`XLA Ops` holds one event per operation that ran on the chip. Busy time is
the union of those intervals (the interval-union arithmetic of
tools/trace_attribution.py), so operations that overlap are counted once.
Host spans are the events of the host planes whose names the cell's file
gives (`spans`: the program's own, innermost first): they share the trace's
clock, so an idle gap of the device is put down to what the host was doing
in it, split exactly among the spans that overlap it (`cover`).
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
TOP_OPS = 8
TOP_GAPS = 5
UNCOVERED = "uncovered"


def union(intervals):
    """Sorted, merged [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals):
    return sum(e - s for s, e in intervals)


def gaps(busy, lo, hi):
    """The idle intervals of [lo, hi] between merged busy intervals."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def intersect(a, b):
    """The parts of merged intervals `a` that lie in merged intervals `b`."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            out.append((max(s, b[k][0]), min(e, b[k][1])))
            k += 1
    return out


def subtract(a, b):
    """The parts of merged intervals `a` that lie in none of merged `b`."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        at, k = s, j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > at:
                out.append((at, b[k][0]))
            at = max(at, b[k][1])
            k += 1
        if at < e:
            out.append((at, e))
    return out


def cover(idle, spans, order):
    """-> {name: the parts of `idle` put down to that span}, `UNCOVERED`
    for what lies in none. A span takes of `idle` only what no span before
    it in `order` took, so where spans nest, list the innermost first.
    Exact: the parts are disjoint and add up to `idle`. `idle`: merged
    intervals; `spans`: {name: merged intervals}."""
    out, left = {}, list(idle)
    for name in order:
        out[name] = intersect(left, spans.get(name, []))
        left = subtract(left, out[name])
    out[UNCOVERED] = left
    return out


def attribute(gap, spans, names):
    """The name of the host span that holds most of `gap` by `cover`'s
    exact split (`names` innermost first: a root span that wraps the others
    holds only its own time); `UNCOVERED` where most of it lies in none."""
    parts = {n: total(iv) for n, iv in cover([gap], spans, names).items()}
    return max(parts, key=parts.get)      # the first of equals: by rank


def reduce_events(device_ops, host_spans, window, span_names):
    """The arithmetic, on plain data, so that a hand-built trace tests it.

    device_ops: {device: [(name, start, end)]}; host_spans: {name:
    [(start, end)]}; window: (start, end); all in one unit of time.
    -> busy_s per device and their mean, the idle share of the fullest
    device, top operations by summed time, longest idle gaps with the span
    the host was in."""
    lo, hi = window
    length = hi - lo
    busy = {}
    for dev, ops in device_ops.items():
        busy[dev] = union(clip([(s, e) for _, s, e in ops], lo, hi))
    if not busy or length <= 0:
        return None
    busy_total = {d: total(b) for d, b in busy.items()}
    fullest = max(busy_total, key=busy_total.get)
    by_op = {}
    for name, s, e in device_ops[fullest]:
        for cs, ce in clip([(s, e)], lo, hi):
            by_op[name] = by_op.get(name, 0) + (ce - cs)
    idle = sorted(gaps(busy[fullest], lo, hi), key=lambda g: g[0] - g[1])
    return {
        "busy": sum(busy_total.values()) / len(busy_total),
        "window": length,
        "idle_share": 1.0 - busy_total[fullest] / length,
        "device_ops": sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP_OPS],
        "idle_gaps": [(attribute(g, host_spans, span_names), g[1] - g[0])
                      for g in idle[:TOP_GAPS]],
        "idle_by_span": _idle_by_span(idle, host_spans, span_names),
    }


def _idle_by_span(idle, host_spans, span_names):
    """The idle time by span, split exactly; spans that hold none of it
    are left out."""
    parts = cover(sorted(idle), host_spans, span_names)
    return {n: total(iv) for n, iv in parts.items() if iv}


_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def short_name(op: str) -> str:
    """The trace names an operation by its whole HLO line. Keep the
    instruction's name and its first result shape: `%fusion.436
    f32[512,32,30000]`."""
    name, _, rest = op.partition(" = ")
    shape = _SHAPE.search(rest)
    return (name + (" " + shape.group(0) if shape else ""))[:120]


def find_xplane(trace_dir: str):
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def read(path: str, span_names, window_span: str):
    """-> (device_ops, host_spans, window) in nanoseconds. The window is the
    host span named `window_span` (the harness wraps the traced window in
    it); without it, the extent of the device's operations."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops, host_spans, window = {}, {}, None
    want = set(span_names) | {window_span}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        (short_name(ev.name), ev.start_ns,
                         ev.start_ns + ev.duration_ns)
                        for ev in line.events]
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in want:
                        host_spans.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
    if host_spans.get(window_span):
        s, e = host_spans.pop(window_span)[0]
        window = (s, e)
    elif device_ops:
        all_ops = [x for ops in device_ops.values() for x in ops]
        window = (min(s for _, s, _ in all_ops), max(e for _, _, e in all_ops))
    return device_ops, {k: union(v) for k, v in host_spans.items()}, window


def reduce_file(path: str, span_names, window_span="bench_window"):
    """-> reduce_events' result with seconds for nanoseconds, or None."""
    device_ops, host_spans, window = read(path, span_names, window_span)
    if not device_ops or window is None:
        return None
    r = reduce_events(device_ops, host_spans, window, span_names)
    if r is None:
        return None
    ns = 1e-9
    return {
        "busy_s": r["busy"] * ns, "window_s": r["window"] * ns,
        "idle_share": r["idle_share"],
        "device_ops": [[n, t * ns] for n, t in r["device_ops"]],
        "idle_gaps": [[n, t * ns] for n, t in r["idle_gaps"]],
        "idle_by_span": {n: t * ns for n, t in r["idle_by_span"].items()},
    }
