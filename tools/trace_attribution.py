#!/usr/bin/env python
"""Attribute device time (or compiled-program bytes) to HLO categories.

The tool behind ROADMAP item 2's attribution requirement: given a
profiler capture (the Chrome-trace `trace.json.gz` that
`jax.profiler` writes from the XPlane — the
committed `tools/traces/*.trace.json.gz` files), name where the
device's wall time goes:

- per-category device-time shares — **conv**, **gemm**, **attention**
  (ops inside the attention named_scopes and Pallas/Mosaic
  custom-call attention kernels — so flash time is attributed, not
  lumped into "other"), **bn_elementwise** (BN statistics,
  activations, reductions, loop fusions), **layout** (copies,
  transposes, dtype converts, HBM<->scratch slices), **collective**,
  **infeed**, **other** — plus **bubble** = wall minus device-busy
  (union of op intervals inside the stepped window), the share no
  per-op table can show;
- a top-N HLOs-by-total-time table with per-op achieved HBM
  bandwidth (`bytes_accessed / duration`), which separates
  memory-bound fusions from compute-bound ones at a glance;
- a machine-readable `*.attrib.json` report, committed next to the
  trace so the roofline campaign argues from evidence.

Works on `.json` / `.json.gz` Chrome traces. Raw `.xplane.pb`
captures must first be exported to a trace (TensorBoard's profile
plugin or `tensorflow.python.profiler` does this); the committed
captures are already trace.json.gz.

**HLO-module captures** (`*.hlo.txt[.gz]`, written by
tools/profile_longctx.py or tools/profile_lm.py): when no device
profiler is reachable (this container has no TPU and the CPU profiler
emits no per-op plane), the same classifier attributes the REAL
compiled program's **bytes** statically — every top-level instruction
is charged its operand + output bytes (fusion internals excluded:
only fusion boundaries cross HBM), bucketed by the same categories.
That is how the committed longctx captures prove the flash byte
removal per-instruction: the dense program's attention category
carries the O(T^2) score tensors, the flash program's does not
(PERF.md round 8). While-loop bodies are counted once (the longctx
captures are loop-free by construction — the blocked flash unrolls at
the capture shape; the decode capture's per-iteration bytes are
multiplied by max_len in the PERF analysis, and the report carries
`while_instructions` so the caveat is machine-visible).

Usage:
    python tools/trace_attribution.py TRACE.json[.gz]
        [--out X.attrib.json] [--top 10] [--json]
    python tools/trace_attribution.py CAPTURE.hlo.txt[.gz] [...]

No jax / device runtime needed — pure stdlib, runs anywhere.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import sys
from collections import defaultdict

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

# the HLO parser + op classifier live in paddle_tpu/analysis/hlo_text
# (ISSUE 13): one parser shared with the static auditor
# (analysis/hlo_audit.py, tools/framework_lint.py), so the audit
# argues about the exact bytes this tool attributes. Names re-exported
# here for back-compat with existing callers/tests.
from paddle_tpu.analysis.hlo_text import (  # noqa: E402
    CATEGORIES,
    analyze_hlo,
    classify,
)

# v5e reference numbers for the table's context columns
HBM_PEAK_GBPS = 819.0


def _load_trace(path: str) -> dict:
    if path.endswith((".pb", ".xplane.pb")):
        raise SystemExit(
            f"{path}: raw XPlane protobuf — export it to a Chrome "
            "trace.json(.gz) first (TensorBoard profile plugin); the "
            "committed captures under tools/traces/ already are."
        )
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def _union_us(intervals) -> float:
    """Total covered length of possibly-overlapping [start, end)."""
    total = 0.0
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def analyze(path: str, top: int = 10) -> dict:
    """Parse one trace and return the attribution report dict."""
    doc = _load_trace(path)
    evs = doc.get("traceEvents", [])
    proc_names: dict = {}
    thread_names: dict = {}
    for e in evs:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            proc_names[e["pid"]] = e["args"]["name"]
        elif e.get("name") == "thread_name":
            thread_names[(e["pid"], e.get("tid"))] = e["args"]["name"]

    device_pids = {
        pid for pid, n in proc_names.items()
        if n.startswith("/device:")
    }
    if not device_pids:
        raise SystemExit(f"{path}: no /device:* process in trace")

    op_tids = {
        k for k, n in thread_names.items()
        if k[0] in device_pids and n == "XLA Ops"
    }
    step_tids = {
        k for k, n in thread_names.items()
        if k[0] in device_pids and n == "Steps"
    }

    ops = [
        e for e in evs
        if e.get("ph") == "X" and (e["pid"], e.get("tid")) in op_tids
    ]
    steps = [
        e for e in evs
        if e.get("ph") == "X" and (e["pid"], e.get("tid")) in step_tids
    ]
    if not ops:
        raise SystemExit(f"{path}: no XLA Ops events")

    # the measured window: the REAL steps (the profiler also logs
    # sub-ms pseudo-steps for trailing host fetches — drop anything
    # under half the longest step)
    if steps:
        max_dur = max(s["dur"] for s in steps)
        real = [s for s in steps if s["dur"] >= 0.5 * max_dur]
        w0 = min(s["ts"] for s in real)
        w1 = max(s["ts"] + s["dur"] for s in real)
        n_steps = len(real)
        step_ms = sum(s["dur"] for s in real) / n_steps / 1e3
    else:
        w0 = min(o["ts"] for o in ops)
        w1 = max(o["ts"] + o["dur"] for o in ops)
        n_steps, step_ms = 0, None
    wall_us = w1 - w0

    in_window = [
        o for o in ops if o["ts"] < w1 and o["ts"] + o["dur"] > w0
    ]
    busy_us = _union_us(
        (max(o["ts"], w0), min(o["ts"] + o["dur"], w1))
        for o in in_window
    )

    cat_time = defaultdict(float)
    cat_ops = defaultdict(int)
    cat_bytes = defaultdict(int)
    by_name: dict = {}
    for o in in_window:
        args = o.get("args", {})
        cat = classify(o["name"], args.get("hlo_category", ""),
                       args.get("long_name", ""))
        dur = o["dur"]
        nbytes = int(args.get("bytes_accessed", 0) or 0)
        cat_time[cat] += dur
        cat_ops[cat] += 1
        cat_bytes[cat] += nbytes
        rec = by_name.setdefault(
            o["name"],
            {"name": o["name"], "category": cat, "time_us": 0.0,
             "count": 0, "bytes_accessed": 0},
        )
        rec["time_us"] += dur
        rec["count"] += 1
        rec["bytes_accessed"] += nbytes

    # overlapping (async) ops can make the per-category sum exceed the
    # busy union; scale so category shares + bubble sum to exactly 1
    raw_sum = sum(cat_time.values())
    scale = busy_us / raw_sum if raw_sum > busy_us > 0 else 1.0

    categories = {}
    for cat in CATEGORIES:
        t = cat_time.get(cat, 0.0) * scale
        if cat_ops.get(cat, 0) == 0:
            continue
        categories[cat] = {
            "time_us": round(t, 1),
            "share": round(t / wall_us, 4) if wall_us else 0.0,
            "n_ops": cat_ops[cat],
            "bytes_accessed": cat_bytes[cat],
            "achieved_gbps": round(
                cat_bytes[cat] / (cat_time[cat] * 1e-6) / 1e9, 1
            ) if cat_time[cat] else 0.0,
        }

    bubble_us = max(wall_us - busy_us, 0.0)
    shares = {c: v["share"] for c, v in categories.items()}
    shares["bubble"] = round(bubble_us / wall_us, 4) if wall_us else 0.0

    top_hlos = sorted(
        by_name.values(), key=lambda r: -r["time_us"]
    )[:top]
    for r in top_hlos:
        r["time_us"] = round(r["time_us"], 1)
        r["share_of_busy"] = round(
            r["time_us"] / busy_us, 4
        ) if busy_us else 0.0
        r["avg_us"] = round(r["time_us"] / r["count"], 1)
        r["achieved_gbps"] = round(
            r["bytes_accessed"] / (r["time_us"] * 1e-6) / 1e9, 1
        ) if r["time_us"] else 0.0

    report = {
        "source": os.path.basename(path),
        "capture_kind": "profiler_trace",
        "devices": len(device_pids),
        "steps": n_steps,
        "step_ms": round(step_ms, 3) if step_ms else None,
        "wall_us": round(wall_us, 1),
        "device_busy_us": round(busy_us, 1),
        "bubble_us": round(bubble_us, 1),
        "overlap_scale": round(scale, 6),
        "hbm_peak_gbps": HBM_PEAK_GBPS,
        "shares": shares,
        "categories": categories,
        "top_hlos": top_hlos,
    }
    # the profiler run's own summary (flops, bytes, img/s) sits next
    # to the trace as <stem>.report.json — fold it in for context
    stem = path
    for suf in (".trace.json.gz", ".trace.json", ".json.gz", ".json"):
        if stem.endswith(suf):
            stem = stem[: -len(suf)]
            break
    sibling = stem + ".report.json"
    if os.path.exists(sibling):
        with open(sibling) as f:
            report["capture_report"] = json.load(f)
    return report


def render_hlo_text(report: dict) -> str:
    lines = [
        f"== hlo byte attribution: {report['source']} ==",
        f"instructions={report['n_instructions']} "
        f"total={report['total_bytes'] / 1e6:.1f} MB "
        f"(while bodies counted once: "
        f"{report['while_instructions']} while op(s))",
        "",
        f"{'category':16s} {'share':>7s} {'MB':>10s} {'ops':>6s}",
    ]
    cats = sorted(
        report["categories"].items(), key=lambda kv: -kv[1]["bytes"]
    )
    for cat, v in cats:
        lines.append(
            f"{cat:16s} {v['share'] * 100:6.2f}% "
            f"{v['bytes'] / 1e6:10.2f} {v['n_ops']:6d}"
        )
    lines += [
        "",
        f"top {len(report['top_hlos'])} HLOs by bytes:",
        f"{'hlo':40s} {'category':15s} {'share':>7s} {'MB':>10s}",
    ]
    for r in report["top_hlos"]:
        lines.append(
            f"{r['name'][:40]:40s} {r['category']:15s} "
            f"{r['share_of_bytes'] * 100:6.2f}% {r['bytes'] / 1e6:10.2f}"
        )
    return "\n".join(lines)


def render_text(report: dict) -> str:
    lines = [
        f"== trace attribution: {report['source']} ==",
        f"devices={report['devices']} steps={report['steps']} "
        f"step={report['step_ms']} ms  wall={report['wall_us']:.0f} us "
        f"busy={report['device_busy_us']:.0f} us "
        f"bubble={report['shares'].get('bubble', 0) * 100:.2f}%",
        "",
        f"{'category':16s} {'share':>7s} {'time_ms':>9s} {'ops':>6s} "
        f"{'GB/s':>8s}",
    ]
    cats = sorted(
        report["categories"].items(), key=lambda kv: -kv[1]["time_us"]
    )
    for cat, v in cats:
        lines.append(
            f"{cat:16s} {v['share'] * 100:6.2f}% "
            f"{v['time_us'] / 1e3:9.2f} {v['n_ops']:6d} "
            f"{v['achieved_gbps']:8.1f}"
        )
    lines.append(
        f"{'bubble':16s} {report['shares'].get('bubble', 0) * 100:6.2f}%"
    )
    lines += [
        "",
        f"top {len(report['top_hlos'])} HLOs by device time "
        f"(of busy; GB/s vs HBM peak {report['hbm_peak_gbps']:.0f}):",
        f"{'hlo':34s} {'category':15s} {'share':>7s} {'time_ms':>9s} "
        f"{'n':>4s} {'GB/s':>8s}",
    ]
    for r in report["top_hlos"]:
        lines.append(
            f"{r['name'][:34]:34s} {r['category']:15s} "
            f"{r['share_of_busy'] * 100:6.2f}% "
            f"{r['time_us'] / 1e3:9.2f} {r['count']:4d} "
            f"{r['achieved_gbps']:8.1f}"
        )
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "trace",
        help="trace.json[.gz] (profiler capture) or hlo.txt[.gz] "
             "(compiled-module capture)",
    )
    ap.add_argument("--out", default="",
                    help="write the attribution report here "
                         "(default: <trace stem>.attrib.json)")
    ap.add_argument("--no-out", action="store_true",
                    help="print only, write no report file")
    ap.add_argument("--top", type=int, default=10)
    ap.add_argument("--json", action="store_true",
                    help="print the JSON report instead of the table")
    args = ap.parse_args(argv)

    is_hlo = args.trace.endswith((".hlo.txt", ".hlo.txt.gz"))
    if is_hlo:
        report = analyze_hlo(args.trace, top=args.top)
    else:
        report = analyze(args.trace, top=args.top)
    if args.json:
        print(json.dumps(report, indent=2))
    elif is_hlo:
        print(render_hlo_text(report))
    else:
        print(render_text(report))
    if not args.no_out:
        out = args.out
        if not out:
            stem = args.trace
            for suf in (".hlo.txt.gz", ".hlo.txt", ".trace.json.gz",
                        ".trace.json", ".json.gz", ".json"):
                if stem.endswith(suf):
                    stem = stem[: -len(suf)]
                    break
            out = stem + ".attrib.json"
        with open(out, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
        print(f"\nwrote {out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
