"""What every kind of cell shares: finding a cell's files by name, the look
for a chip, the clocks, the device's memory, and the result's last line.

Nothing here knows a cell, a kind or a metric by name: `BENCHMARK.json`
names them, and their files are found under `benchmarks/` by that name.
"""

from __future__ import annotations

import contextlib
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoChip(RuntimeError):
    pass


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_benchmark(root=ROOT) -> dict:
    return read_json(root, "BENCHMARK.json")


class Cell:
    """One entry of `workloads`, with the files its names lead to."""

    def __init__(self, name: str, root=ROOT, bench=None):
        self.root = root
        self.bench = bench or load_benchmark(root)
        entries = {w["name"]: w for w in self.bench["workloads"]}
        if name not in entries:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                             f"have {sorted(entries)}")
        self.entry = entries[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        here = os.path.join(root, "benchmarks")
        self.workload = read_json(here, "workloads", name + ".json")
        conf = {c["name"]: c for c in self.bench["configs"]}[
            self.entry["config"]]
        self.config = read_json(root, conf["file"])
        self.traffic = self.workload["traffic"]
        self.limits = self.workload["limits"]

    def module(self, package: str, name: str):
        return importlib.import_module(f"benchmarks.{package}.{name}")

    @property
    def kind(self):
        return self.module("kinds", self.workload["kind"])

    @property
    def model(self):
        return self.module("models", self.config["model"])

    def metrics(self, group: str) -> list:
        """The metrics of `end_to_end` or `per_layer` this cell reports."""
        return [m for m in self.bench[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def layer_metric(self, name: str):
        """-> (reader module, the metric's own data)."""
        data = read_json(self.root, "benchmarks", "layer_metrics",
                         name + ".json")
        return self.module("layer_metrics", data["reader"]), data


def peak_of(device_kind: str, root=ROOT) -> dict:
    table = read_json(root, "benchmarks", "peaks.json")["device_kinds"]
    if device_kind not in table:
        raise SystemExit(f"device kind {device_kind!r} is not in "
                         f"benchmarks/peaks.json ({sorted(table)}): add its "
                         "published peaks with their source")
    return table[device_kind]


def require_chips(chips: int):
    """The devices the cell runs on. Never sets or changes the platform."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found platform {devices[0].platform!r}, not a "
                     "TPU: the benchmark measures on the chip only")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


class CompileClock:
    """XLA compile seconds and persistent-cache traffic, from JAX's own
    monitoring events (a cache hit is timed as the load it is). After
    chip_smoke.py's."""

    _BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring as mon

        self.compile_s = 0.0
        self.hits = self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == self._BACKEND:
            self.compile_s += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self):
        return (self.compile_s, self.hits, self.misses)

    def programs_since(self, mark) -> int:
        """Programs compiled or loaded from the cache since `mark`."""
        return (self.hits - mark[1]) + (self.misses - mark[2])


class Spans:
    """Host spans of the harness's own calls: a perf_counter total per
    name, and the same span in the profiler's trace when one is on."""

    def __init__(self):
        self.total = {}
        self._open = {}

    def begin(self, name: str) -> None:
        import jax

        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
        self._open[name] = (ann, time.perf_counter())

    def end(self, name: str) -> None:
        if name not in self._open:
            return
        ann, t0 = self._open.pop(name)
        self.total[name] = self.total.get(name, 0.0) + (
            time.perf_counter() - t0)
        ann.__exit__(None, None, None)

    @contextlib.contextmanager
    def span(self, name: str):
        self.begin(name)
        try:
            yield
        finally:
            self.end(name)

    def reset(self) -> None:
        self.total.clear()


class HostWatch:
    """What the host did to the process in the window, for the log: a run
    on a one-chip machine shares its host, and a step that stalls for
    seconds reads as a slower program unless the log shows whose stall it
    was. A thread that sleeps `TICK` seconds at a time records its longest
    oversleep: a gap there as long as the stall means the whole process (or
    machine) stood still; none means only the main thread waited, on the
    device or its driver. Beside it the machine's counters over the window:
    CPU time stolen by the hypervisor, pressure stalls, page faults."""

    TICK = 0.02

    def __init__(self):
        import threading

        self.max_gap = 0.0
        self.max_gap_at = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._tick, daemon=True)

    def _tick(self):
        last = time.perf_counter()
        while not self._stop.wait(self.TICK):
            now = time.perf_counter()
            if now - last - self.TICK > self.max_gap:
                self.max_gap, self.max_gap_at = now - last - self.TICK, now
            last = now

    @staticmethod
    def _counters() -> dict:
        """Running totals; a source the machine lacks is left out."""
        out = {}

        def lines(path):
            try:
                with open(path) as f:
                    return f.read().splitlines()
            except OSError:
                return []

        for line in lines("/proc/stat")[:1]:
            out["steal_s"] = int(line.split()[8]) / os.sysconf("SC_CLK_TCK")
        for line in lines("/proc/vmstat"):
            key, _, value = line.partition(" ")
            if key in ("pgfault", "pgmajfault", "compact_stall", "pswpout"):
                out[key] = int(value)
        for line in lines("/proc/meminfo"):
            if line.startswith("MemAvailable:"):
                out["mem_available_mb"] = int(line.split()[1]) / 1024
        for res in ("cpu", "memory", "io"):
            for line in lines(f"/proc/pressure/{res}")[:1]:
                out[f"psi_{res}_some_s"] = int(
                    line.rsplit("total=", 1)[1]) / 1e6
        return out

    def __enter__(self):
        self._before = self._counters()
        self._t0 = time.perf_counter()
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()
        after = self._counters()
        self.report = {k: round(after[k] - v, 6)
                       for k, v in self._before.items() if k in after}
        self.report["longest_oversleep_s"] = round(self.max_gap, 4)
        if self.max_gap_at is not None:
            self.report["oversleep_at_s"] = round(
                self.max_gap_at - self._t0, 3)


def device_memory(devices, executables=()) -> dict:
    """Peak bytes on the fullest chip, two readings.

    `allocator`: `memory_stats()["peak_bytes_in_use"]`. On the v5e it has
    left out a running program's temporaries (PR 21: 0.47 GB after a step
    whose `memory_analysis()` says 9.17 GB). `held`: what the device really
    held while the window's largest program ran: the bytes in use now (live
    arrays: weights, optimizer state, inputs) plus that program's
    temporaries and its outputs that alias no argument. The line carries
    the larger of the two."""
    alloc = live = 0
    for d in devices:
        s = d.memory_stats() or {}
        alloc = max(alloc, int(s.get("peak_bytes_in_use", 0)))
        live = max(live, int(s.get("bytes_in_use", 0)))
    prog = 0
    for ma in executables:
        prog = max(prog, int(ma.temp_size_in_bytes)
                   + int(ma.output_size_in_bytes)
                   - int(ma.alias_size_in_bytes))
    return {"allocator_peak": alloc, "live": live, "program": prog,
            "held": live + prog, "memory_peak_bytes": max(alloc, live + prog)}


def say(**fields) -> None:
    """An earlier line of a run's log (standard output, JSON)."""
    print(json.dumps(fields), flush=True)


def last_line(correct, attempted, failed, metrics, device, compared,
              breakdown=None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown:
        out["breakdown"] = breakdown
    out["compared"] = compared          # comes last, by the contract
    return json.dumps(out)


def fail(message: str, code: int = 1):
    print(message, file=sys.stderr, flush=True)
    raise SystemExit(code)
