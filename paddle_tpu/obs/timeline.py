"""Per-step wall-time attribution for training hot loops.

Splits every trained batch's wall time into the places it can go, so
an input-pipeline stall is a tracked number like MFU instead of a
vibe. The trainer times each piece once, with `obs.tracing.span`, and
hands the finished span to `add`: the timeline's totals are the sums
of the very durations the spans carry. Which span feeds which part:

- **data_wait**        — `train.input_wait.feeder`: the training
                         thread blocked on the queue for the next fed
                         batch (a worker thread reads and feeds a step
                         ahead; its own time is `<prefix>
                         .feed_worker_s`, no part of a step's wall)
- **h2d**              — `train.h2d`: the training thread placing a
                         fed batch on the device (`dp.shard_batch`),
                         a step ahead between the dispatch and the
                         fetch of the step before, or late, before
                         its own dispatch: the staging copy and the
                         enqueue of the transfer
- **host_dispatch**    — `train.dispatch`: Python + runtime time to
                         *submit* the jitted step, whose arguments are
                         on the device (async dispatch: this returns
                         before the device finishes)
- **device_step**      — `train.fetch`, the host blocked on the loss,
                         plus `train.fence`, a full `block_until_ready`
                         every `sample_period` steps so the
                         parameter-update tail is measured too while
                         steady-state dispatch stays async
- **handlers**         — `train.handlers`: evaluators, the watchdog's
                         ladder, the EndIteration handler, logging
- **checkpoint_stall** — `train.checkpoint`: training-thread stalls
                         inside checkpoint saves

The timeline is pure bookkeeping (no jax — the *trainer* owns the
fencing; `fence_now()` only answers "is this a fenced step"). Totals
are mirrored into the process registry as counters under `<prefix>.`;
`fractions()` yields the `data_wait_frac` / `host_overhead_frac` /
`device_frac` split of a pass (`SGD.last_timeline`; they sum to
about 1), and `emit_pass()` writes one structured `timeline`
event per pass to the JSONL stream. `end_step` is the always-on
reader of the spans: a step that took over `SLOW_FACTOR` times the
median of the last `SLOW_WINDOW` is logged with its split, with or
without a profiler or a sink.

Before the steps there is set-up, and `process_age_s()` is its first
number: how old the process was when the trainer began to build.
"""

from __future__ import annotations

import collections
import logging
import os
import statistics
import time
from typing import Callable, Optional

from paddle_tpu.obs import metrics as _metrics

PARTS = ("data_wait", "h2d", "host_dispatch", "device_step", "handlers",
         "checkpoint_stall")
SPAN_PART = {
    "train.input_wait.feeder": "data_wait",
    "train.h2d": "h2d",
    "train.dispatch": "host_dispatch",
    "train.fetch": "device_step",
    "train.fence": "device_step",
    "train.handlers": "handlers",
    "train.checkpoint": "checkpoint_stall",
}
SLOW_FACTOR = 3.0
SLOW_WINDOW = 32
SLOW_MIN_HISTORY = 4     # steps before the median means anything


def process_age_s() -> Optional[float]:
    """Seconds since the kernel created this process: its start time
    in `/proc/self/stat` (field 22, in clock ticks since boot) against
    the boot clock. Everything that ran before the caller is in it,
    the interpreter's own start too: imports, the chip's
    initialisation, whatever the caller did first. None where the
    platform has no such file or no such clock."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        started = int(fields[19]) / os.sysconf("SC_CLK_TCK")
        return time.clock_gettime(time.CLOCK_BOOTTIME) - started
    except (OSError, ValueError, IndexError, AttributeError):
        return None


class StepTimeline:
    def __init__(self, sample_period: int = 16, prefix: str = "trainer",
                 registry=None,
                 compile_spent: Optional[Callable] = None):
        """`sample_period`: fence (block_until_ready) every Nth step;
        0 disables fencing (device_step then measures only the result
        fetches the loop makes anyway). `compile_spent(t0_ns, t1_ns)`:
        what this thread spent compiling in that stretch
        (`core.compile_cache.spent`, handed in by the trainer: nothing
        here imports jax); asked of a slow step alone."""
        self.sample_period = int(sample_period)
        self.prefix = prefix
        self._compile_spent = compile_spent
        self._reg = registry or _metrics.get_registry()
        self._log = logging.getLogger(f"paddle_tpu.{prefix}")
        self._totals_ns = {p: 0 for p in PARTS}
        self._step_ns = {}       # span name -> ns, of the open step
        self._walls_ns = collections.deque(maxlen=SLOW_WINDOW)
        self._steps = 0
        self._fenced = 0

    # ---- accumulation (trainer-side) ----
    def add(self, span) -> None:
        """A finished `tracing.Span` of one of SPAN_PART's names."""
        dt = span.dur_ns
        self._totals_ns[SPAN_PART[span.name]] += dt
        self._step_ns[span.name] = self._step_ns.get(span.name, 0) + dt
        self._reg.counter(
            f"{self.prefix}.{SPAN_PART[span.name]}_s").inc(dt * 1e-9)

    def step_done(self, n: int = 1) -> None:
        self._steps += n
        self._reg.counter(f"{self.prefix}.steps").inc(n)

    def end_step(self, root) -> bool:
        """The step's root span has closed: hold its wall against the
        recent median; True where it was a slow step (one WARNING, one
        `slow_step` event, `<prefix>.slow_steps` + 1)."""
        wall, split = root.dur_ns, self._step_ns
        self._step_ns = {}
        median = (statistics.median(self._walls_ns)
                  if len(self._walls_ns) >= SLOW_MIN_HISTORY else wall)
        slow = wall > SLOW_FACTOR * median
        if slow:
            median_s = median * 1e-9
            parts = {
                "input_wait_s": split.get("train.input_wait.feeder", 0),
                "h2d_s": split.get("train.h2d", 0),
                "dispatch_s": split.get("train.dispatch", 0),
                "fetch_s": split.get("train.fetch", 0),
                "fence_s": split.get("train.fence", 0),
                "handlers_s": split.get("train.handlers", 0),
            }
            parts = {k: round(v * 1e-9, 6) for k, v in parts.items()}
            if self._compile_spent is not None:
                # a step slow because it recompiled says so
                spent = self._compile_spent(root.t0_ns, root.t1_ns)
                parts["compile_s"] = round(
                    spent["trace_s"] + spent["lower_s"]
                    + spent["backend_s"], 6)
            self._reg.counter(f"{self.prefix}.slow_steps").inc()
            self._reg.event("slow_step", wall_s=round(wall * 1e-9, 6),
                            median_s=round(median_s, 6),
                            **root.labels, **parts)
            self._log.warning(
                "slow step %s: %.4f s against a median of %.4f s: %s",
                root.labels, wall * 1e-9, median_s, parts)
        self._walls_ns.append(wall)
        return slow

    def fence_now(self, step_index: int) -> bool:
        """True on fenced steps — the trainer then blocks until the
        whole step (parameter update included) has landed, so
        device_step covers the tail the loss fetch alone would miss."""
        if self.sample_period <= 0:
            return False
        fence = step_index % self.sample_period == 0
        if fence:
            self._fenced += 1
        return fence

    # ---- export ----
    @property
    def steps(self) -> int:
        return self._steps

    def totals(self) -> dict:
        """Seconds a part: the sum of its spans' nanoseconds."""
        return {p: ns * 1e-9 for p, ns in self._totals_ns.items()}

    def fractions(self) -> dict:
        """Shares of the loop's wall: the parts cover a step from the
        wait for its fed batch to the last handler's return, but for
        the BeginIteration handler. All zero before the first step."""
        wall = sum(self._totals_ns.values())
        names = {"data_wait": "data_wait_frac",
                 "h2d": "h2d_frac",
                 "host_dispatch": "host_overhead_frac",
                 "device_step": "device_frac",
                 "handlers": "handlers_frac",
                 "checkpoint_stall": "checkpoint_stall_frac"}
        return {names[p]: round(ns / wall, 4) if wall else 0.0
                for p, ns in self._totals_ns.items()}

    def emit_pass(self, pass_id: int, global_step: int) -> None:
        """One `timeline` event on the JSONL stream per pass (no-op
        without a stream) — the record `mc_preempt_recovery` and the
        fault tests read back."""
        self._reg.event(
            "timeline",
            pass_id=pass_id,
            global_step=global_step,
            steps=self._steps,
            fenced_steps=self._fenced,
            sample_period=self.sample_period,
            **{f"{p}_s": round(s, 6) for p, s in self.totals().items()},
            **self.fractions(),
        )
