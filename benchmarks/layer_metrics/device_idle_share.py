"""1 - (union of the device-operation intervals of the fullest-loaded chip
/ the traced window), from the `.xplane.pb` (benchmarks/trace_reduce.py)."""


def read(run, args):
    trace = run.get("trace")
    if not trace:
        return None
    return 100.0 * trace["idle_share"]
