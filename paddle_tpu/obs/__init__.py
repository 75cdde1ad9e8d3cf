"""Unified telemetry (ISSUE 10).

One process-wide registry of counters / gauges / histograms
(`obs.metrics`) that the trainer hot loop, the watchdog, the serving
stack and the master client all publish into, plus a JSONL event
stream for discrete structured events (watchdog skips/rollbacks,
preemption flushes, per-pass step timelines) and a per-step wall-time
attribution helper (`obs.timeline`).

The reference treated telemetry as a first-class subsystem
(utils/Stat.h StatSet/REGISTER_TIMER feeding the per-pass report,
TrainerInternal.cpp:177); `core/stat.py` is now a view over this
registry, so there is exactly one timer substrate in the process.

HARD CONSTRAINT (linted by `ast_lint.check_jax_import_fence`): no
module in this package imports `jax` at module top level. The registry
must stay importable in the serving TCP front end, the master client
and data workers without dragging in the device runtime.
"""

from paddle_tpu.obs.metrics import (  # noqa: F401
    MetricsRegistry,
    EventStream,
    enable_event_stream,
    get_registry,
)
from paddle_tpu.obs.timeline import StepTimeline  # noqa: F401
from paddle_tpu.obs import tracing  # noqa: F401
from paddle_tpu.obs.flight_recorder import (  # noqa: F401
    BoundedBundleDir,
    FlightRecorder,
    enable_flight_recorder,
    disable_flight_recorder,
    get_flight_recorder,
)
from paddle_tpu.obs.aggregate import (  # noqa: F401
    BurnRateMonitor,
    FleetAggregator,
    SnapshotMergeError,
    merge_snapshots,
    quantile,
    snapshot_delta,
)
