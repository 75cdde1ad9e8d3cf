"""Seconds of set-up that the program timed itself: the sum of the gauges
the metric's file names (`series`: each a `gauge` and its `labels`) in the
registry of the run's own process (`paddle_tpu.obs.metrics.get_registry()`),
read after the window. The trainer sets them once, as it is built and at
its first dispatch, so what the window or the reference compile later moves
none of them.

Nothing without a traced run's file (per-layer metrics are the traced
run's), and nothing where the registry holds none of the series: a program
that does not time its set-up, as the parent of the PR that brought this.
"""


def read(run, args):
    if not run.get("trace_file"):
        return None
    from paddle_tpu.obs import metrics

    reg = metrics.get_registry()
    found = [reg.gauge(s["gauge"]).get(**s.get("labels", {}))
             for s in args["series"]]
    found = [float(v) for v in found if v is not None]
    return sum(found) if found else None
