"""The step program's share of the chips' bf16 peak while the chip works:
the analytic FLOPs of the work that ended in the traced window (the
configuration's `*_flops_*` function, recomputation not counted) over the
seconds in which an operation ran on the device (`busy_s` of the trace, the
mean over the chips), over chips x the peak of the `device_kind`
(benchmarks/peaks.json). The time the chip waits for the host is the
device layer's (`device_idle_share`) and the entry points', not this."""


def read(run, args):
    trace = run.get("trace")
    if not run["flops"] or not trace or not trace["busy_s"]:
        return None
    peak = run["peak"]["bf16_flops"] * run["chips"]
    return 100.0 * run["flops"] / trace["busy_s"] / peak
