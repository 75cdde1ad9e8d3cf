"""Run one cell of the benchmark once, in a process of its own.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its kind of traffic and its metrics are found
by the names `BENCHMARK.json` gives them (benchmarks/harness.py); this file
holds no table of its own. It needs a TPU with as many chips as the cell
asks for and fails without one. The last line of standard output is the
result; the numbers compared, each beside its limit, are the last lines of
standard error and the result's last key.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import compare, harness, trace_reduce  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".bench_trace")
WINDOW_SPAN = "bench_window"


class Context:
    """What a kind's `run` is handed."""

    def __init__(self, cell, seed, seconds, trace, devices, t_start=T_START):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace, self.devices = trace, devices
        self.clock = harness.CompileClock()
        self.spans = harness.Spans()
        self.t_start = t_start
        self.setup_s = None
        self.tracer = self._tracer() if trace else contextlib.nullcontext()
        self.trace_file = None

    def setup_done(self, split: dict) -> None:
        """Called by the kind just before the window: set-up ends here."""
        self.setup_s = time.perf_counter() - self.t_start
        harness.say(setup_s=self.setup_s, **split)

    def memory(self, executables) -> dict:
        m = harness.device_memory(self.devices, executables)
        harness.say(memory=m)
        return m

    def free(self) -> None:
        import jax

        gc.collect()
        jax.clear_caches()
        gc.collect()

    @contextlib.contextmanager
    def _tracer(self):
        import jax

        out = os.path.join(TRACE_DIR, self.cell.name)
        shutil.rmtree(out, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(out, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                yield
        finally:
            jax.profiler.stop_trace()
            self.trace_file = trace_reduce.find_xplane(out)


def enable_cache():
    """The program's own rule for where the persistent cache lives (inside
    the checkout, or where JAX_COMPILATION_CACHE_DIR says); every program,
    however short its compile, is kept, so that only a cell's first run in
    a checkout compiles."""
    import jax

    from paddle_tpu.core import compile_cache

    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def measure(cell, seed, seconds, trace, devices, t_start=T_START,
            peak=None) -> dict:
    """Everything after the look for a chip: -> the result as a dict (the
    last line's keys) plus `log`."""
    ctx = Context(cell, seed, seconds, trace, devices, t_start)
    # what a "unit" of the kind's one rate is in this cell
    harness.say(cell=cell.name, rate_metric=cell.workload.get("rate_metric"),
                counts=cell.traffic.get("count", {}).get("unit"))
    if trace:
        ctx.seconds = min(seconds, cell.workload.get("trace_seconds", 3))
    run = cell.kind.run(ctx)
    dev = devices[0]
    run.update(chips=cell.chips,
               peak=peak or harness.peak_of(dev.device_kind))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run["memory"]["memory_peak_bytes"]}
    breakdown = None
    metrics = {}
    if trace:
        names = cell.workload.get("spans", [])
        reduced = (trace_reduce.reduce_file(ctx.trace_file, names, WINDOW_SPAN)
                   if ctx.trace_file else None)
        run.update(trace=reduced, trace_file=ctx.trace_file)
        if reduced:
            device.update(busy_s=reduced["busy_s"],
                          window_s=reduced["window_s"])
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
            harness.say(idle_by_span=reduced["idle_by_span"])
        else:
            run["problems"].append("the trace held no device operation")
            run["correct"] = False
        for m in cell.metrics("per_layer"):
            reader, data = cell.layer_metric(m["name"])
            value = reader.read(run, data)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.metrics("end_to_end"):
            if m["name"] == "setup_s":
                value = ctx.setup_s
            elif m["name"] == cell.workload["rate_metric"]:
                value = run["work"] / run["window_s"] if run["window_s"] else 0
            else:
                continue
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    harness.say(window_s=run["window_s"], work=run["work"],
                spans=run["spans"], problems=run["problems"], **run["log"])
    return {"correct": run["correct"], "attempted": run["attempted"],
            "failed": run["failed"], "metrics": metrics, "device": device,
            "breakdown": breakdown, "compared": run["compared"],
            "notes": run["notes"], "problems": run["problems"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        harness.fail("no program beside the benchmark: paddle_tpu/ is "
                     f"missing from {ROOT}", 3)
    cell = harness.Cell(args.workload)
    try:
        devices = harness.require_chips(cell.chips)
    except harness.NoChip as e:
        harness.fail(f"benchmarks/run.py: {e}", 2)
    cache = enable_cache()
    harness.say(workload=cell.name, seed=args.seed, seconds=args.seconds,
                trace=args.trace, platform=devices[0].platform,
                device_kind=devices[0].device_kind, devices=len(devices),
                cache_dir=cache,
                cache_entries=len(os.listdir(cache))
                if os.path.isdir(cache) else 0)
    result = measure(cell, args.seed, args.seconds, bool(args.trace), devices)
    compare.say_compared(result["compared"],
                         {**result["notes"], "problems": result["problems"]})
    print(harness.last_line(result["correct"], result["attempted"],
                            result["failed"], result["metrics"],
                            result["device"], result["compared"],
                            result["breakdown"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
