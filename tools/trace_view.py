#!/usr/bin/env python
"""Reconstruct per-request critical paths from span streams/bundles.

The read side of ISSUE 11's tracing: given one or more JSONL event
streams (`obs.metrics.enable_event_stream` output, `kind == "span"`
records) and/or flight-recorder bundles (`obs.flight_recorder`
JSON, schema paddle-tpu-flight-bundle/v1), this tool

- groups spans by `trace_id` — streams from SEVERAL processes can be
  passed together, so a trace that crosses the client/server or
  trainer/master boundary reassembles into one tree;
- picks each trace's root (the span whose parent is not in the trace;
  longest wins when a trace has several, e.g. a trainer trace, which
  holds one train.step root for every step of a train() call);
- walks the tree into a **critical path**: the time-ordered leaf
  segments that cover the root's duration, with uncovered gaps
  attributed to the enclosing span as "<name> (self)" — the
  "where did THIS request's time go" answer;
- prints the top-N slowest traces (or one trace by id) with their
  paths, or emits the whole analysis as JSON.

Pure stdlib, no jax (same contract as trace_attribution.py): span
analytics must run on any machine the stream was copied to.

Usage:
    python tools/trace_view.py FILE [FILE ...]
        [--top N] [--trace TRACE_ID] [--json]
"""

from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict

BUNDLE_SCHEMA = "paddle-tpu-flight-bundle/v1"
INCIDENT_SCHEMA = "paddle-tpu-fleet-incident/v1"


def _incident_events(doc: dict) -> list:
    """Stitch a fleet-incident bundle's events: the router's own ring
    plus every replica's flightz ring dump — the cross-process span
    set one trace_id ties back together."""
    events = list(doc.get("events", []))
    for ring in (doc.get("replicas") or {}).values():
        if isinstance(ring, dict):
            events.extend(ring.get("events", []))
    return events


def load_spans(path: str) -> list:
    """Spans from a JSONL stream, a flight-recorder bundle, or a
    fleet-incident bundle; the format is sniffed from content, not
    the filename."""
    with open(path) as f:
        first = f.read(1)
        f.seek(0)
        if first != "{":
            return []
        # try one-document bundle first; fall back to JSONL
        try:
            doc = json.load(f)
        except ValueError:
            doc = None
        if isinstance(doc, dict) and doc.get("schema") == BUNDLE_SCHEMA:
            events = doc.get("events", [])
        elif isinstance(doc, dict) and doc.get("schema") == INCIDENT_SCHEMA:
            events = _incident_events(doc)
        elif isinstance(doc, dict):
            events = [doc]
        else:
            f.seek(0)
            events = []
            for ln in f:
                ln = ln.strip()
                if not ln:
                    continue
                try:
                    events.append(json.loads(ln))
                except ValueError:
                    continue
    return [e for e in events
            if isinstance(e, dict) and e.get("kind") == "span"]


def group_traces(spans: list) -> dict:
    traces = defaultdict(list)
    for s in spans:
        tid = s.get("trace_id")
        if tid and s.get("span_id"):
            traces[tid].append(s)
    return dict(traces)


def _root_of(group: list):
    # root semantics are mirrored in paddle_tpu/__main__.py
    # _metrics_spans (this file stays standalone-stdlib, so it is not
    # importable from there without breaking portability) — change
    # both together
    ids = {s["span_id"] for s in group}
    roots = [s for s in group if s.get("parent_id", "") not in ids]
    pool = roots or group
    return max(pool, key=lambda s: float(s.get("dur_s", 0.0)))


def critical_path(group: list) -> dict:
    """One trace's analysis: root, total duration, and the ordered
    leaf segments covering it. Children are clipped to their parent's
    interval and to each other (clock skew between processes shows up
    as overlap, never as negative segments)."""
    children = defaultdict(list)
    ids = {s["span_id"] for s in group}
    for s in group:
        p = s.get("parent_id", "")
        if p and p in ids and p != s["span_id"]:
            children[p].append(s)
    root = _root_of(group)
    segments = []

    def walk(span, lo, hi):
        t0 = float(span.get("ts", 0.0))
        t1 = t0 + float(span.get("dur_s", 0.0))
        t0, t1 = max(t0, lo), min(t1, hi)
        if t1 <= t0 and span is not root:
            return
        kids = sorted(
            children.get(span["span_id"], ()),
            key=lambda s: float(s.get("ts", 0.0)),
        )
        if not kids:
            segments.append({
                "name": span.get("name", "?"),
                "dur_s": max(t1 - t0, 0.0),
                "status": span.get("status", "ok"),
            })
            return
        cur = t0
        for k in kids:
            k0 = float(k.get("ts", 0.0))
            if k0 > cur:
                segments.append({
                    "name": f"{span.get('name', '?')} (self)",
                    "dur_s": k0 - cur,
                    "status": span.get("status", "ok"),
                })
            walk(k, max(cur, t0), t1)
            cur = max(cur, k0 + float(k.get("dur_s", 0.0)))
        if cur < t1:
            segments.append({
                "name": f"{span.get('name', '?')} (self)",
                "dur_s": t1 - cur,
                "status": span.get("status", "ok"),
            })

    walk(root, float("-inf"), float("inf"))
    total = float(root.get("dur_s", 0.0))
    for seg in segments:
        seg["dur_ms"] = round(seg.pop("dur_s") * 1e3, 3)
        seg["frac"] = round(
            seg["dur_ms"] / (total * 1e3), 4
        ) if total > 0 else 0.0
    return {
        "trace_id": root.get("trace_id"),
        "root": root.get("name"),
        "status": root.get("status", "ok"),
        "dur_ms": round(total * 1e3, 3),
        "spans": len(group),
        "critical_path": segments,
    }


def analyze(paths: list, top: int = 10,
            trace_id: str = None) -> dict:
    spans = []
    for p in paths:
        spans.extend(load_spans(p))
    traces = group_traces(spans)
    if trace_id is not None:
        matches = [t for t in traces if t.startswith(trace_id)]
        if not matches:
            raise SystemExit(f"trace {trace_id!r} not found in "
                             f"{len(traces)} traces")
        picked = {t: traces[t] for t in matches}
    else:
        picked = traces
    analyzed = sorted(
        (critical_path(g) for g in picked.values()),
        key=lambda a: a["dur_ms"], reverse=True,
    )
    return {
        "files": paths,
        "span_count": len(spans),
        "trace_count": len(traces),
        "traces": analyzed[: max(top, 1)],
    }


def render(report: dict) -> str:
    lines = [
        f"{report['span_count']} spans / {report['trace_count']} "
        f"traces from {len(report['files'])} file(s); "
        f"slowest {len(report['traces'])}:"
    ]
    for t in report["traces"]:
        lines.append(
            f"trace {t['trace_id'][:16]:16s} root={t['root']:<22s} "
            f"{t['dur_ms']:10.3f} ms  {t['spans']:3d} spans  "
            f"status={t['status']}"
        )
        for seg in t["critical_path"]:
            lines.append(
                f"    {seg['name']:32s} {seg['dur_ms']:10.3f} ms "
                f"{100 * seg['frac']:6.1f}%"
                + ("" if seg["status"] == "ok"
                   else f"  [{seg['status']}]")
            )
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("files", nargs="+",
                    help="JSONL span streams and/or flight bundles")
    ap.add_argument("--top", type=int, default=10)
    ap.add_argument("--trace", default=None,
                    help="show one trace (id prefix ok)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    report = analyze(args.files, top=args.top, trace_id=args.trace)
    if args.json:
        print(json.dumps(report, indent=2))
    else:
        print(render(report))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
