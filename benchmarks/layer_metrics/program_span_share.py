"""A share of the traced window read off the program's own spans, as they
lie in the profiler's trace (benchmarks/program_spans.py). The metric's
file says which: `of: "window"` is the time of `spans` themselves over the
window (the thread that opens them, inside them), and nothing where the
trace holds none of them; `of: "idle"` is the device's
idle time that falls inside `spans`, each idle gap split among the spans
that overlap it by overlap, plus, with `uncovered`, the idle time inside no
span at all. Nothing where the program has no such spans."""

from benchmarks import program_spans


def read(run, args):
    r = program_spans.of_run(run)
    if r is None or not r["window_s"]:
        return None
    by = r["span_s"] if args["of"] == "window" else r["idle_s"]
    if args["of"] == "window" and not any(n in by for n in args["spans"]):
        return None
    share = sum(by.get(n, 0.0) for n in args["spans"])
    if args.get("uncovered"):
        share += r["idle_s"][program_spans.UNCOVERED]
    return 100.0 * share / r["window_s"]
