"""Read, on the chip and at a cell's own size, what the program's spans say
of a window with no profiler on: the cell's trainer through the kind's own
set-up and window, a ring-only flight recorder as the spans' sink, and each
span's count, median and sum; beside it what one span costs where nothing
listens (no sink, no session), a loop of 1e5 in the same process.

    python benchmarks/tools/step_spans.py --workload <cell> --seed <n> \\
        --seconds <s>

One JSON line each; nothing here is part of a benchmark run.
"""

from __future__ import annotations

import argparse
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import harness, run as R  # noqa: E402


def span_cost_ns(n=100_000, repeats=5) -> dict:
    """The best of `repeats` loops of `n`: a child span and a step's root,
    inside a trace begun here, with no sink attached and no session on."""
    from paddle_tpu.obs import tracing

    def child():
        with tracing.span("x"):
            pass

    def root():
        with tracing.span("x", step_num=1, pass_id=0):
            pass

    def loop(f):
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter_ns()
            for _ in range(n):
                f()
            best = min(best, (time.perf_counter_ns() - t0) / n)
        return best

    with tracing.attach(None, or_begin=True):
        return {"empty_call_ns": loop(lambda: None),
                "child_span_ns": loop(child), "root_span_ns": loop(root)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload)
    devices = harness.require_chips(cell.chips)
    R.enable_cache()
    harness.say(span_cost=span_cost_ns(), device_kind=devices[0].device_kind)

    from benchmarks.kinds import train as T
    from paddle_tpu.obs import flight_recorder, metrics

    spans = harness.Spans()
    trainer, pool, feeder, _, _ = T.setup(cell, args.seed, devices,
                                          harness.CompileClock(), spans)
    counters = ("trainer.rows", "trainer.feed_bytes", "trainer.slow_steps")
    reg = metrics.get_registry()
    before = {c: reg.counter(c).get() for c in counters}
    rec = flight_recorder.enable_flight_recorder(capacity=1 << 16)
    try:
        loop = T.window(cell, trainer, pool, feeder, spans, args.seconds)
    finally:
        flight_recorder.disable_flight_recorder()
    by = {}
    for s in rec.spans():
        by.setdefault(s["name"], []).append(s["dur_s"])
    harness.say(
        steps=loop.steps, window_s=loop.t_last - loop.t_first,
        rate=loop.work / (loop.t_last - loop.t_first),
        spans={n: {"count": len(v), "median_s": statistics.median(v),
                   "max_s": max(v), "sum_s": sum(v)}
               for n, v in sorted(by.items())},
        counters={c: reg.counter(c).get() - before[c] for c in counters},
        slow_steps=[e for e in rec.snapshot() if e["kind"] == "slow_step"],
        timeline=trainer.last_timeline.totals())
    return 0


if __name__ == "__main__":
    sys.exit(main())
