"""SGD trainer — the event-driven training loop.

Reference: python/paddle/v2/trainer.py:24,110,145-176 (SGD.train with
event_handler), driving the same semantics as the C++ Trainer pass/batch
loop (trainer/Trainer.cpp:261,492; TrainerInternal::trainOneBatch
TrainerInternal.cpp:66). One jit-compiled TrainStep replaces
forwardBackward + updater; the whole mesh runs it SPMD.
"""

from __future__ import annotations

import contextlib
import itertools
import logging
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core import compile_cache as _compile_cache
from paddle_tpu.core import flags as _flags
from paddle_tpu.core import rng as _rng
from paddle_tpu.core.config import ModelConf, OptimizationConf
from paddle_tpu.core.stat import GLOBAL_STATS
from paddle_tpu.data.reader import Buffered
from paddle_tpu.obs import metrics as _obs
from paddle_tpu.obs import tracing as _tracing
from paddle_tpu.obs.timeline import StepTimeline, process_age_s
from paddle_tpu.evaluators import create_evaluator
from paddle_tpu.network import Network
from paddle_tpu.optimizers import create_optimizer
from paddle_tpu.parallel.dp import TrainStep, shard_batch
from paddle_tpu.trainer import async_checkpoint as actp
from paddle_tpu.trainer import checkpoint as ckpt
from paddle_tpu.trainer import watchdog as wdg
from paddle_tpu.trainer.events import (
    BeginIteration,
    BeginPass,
    EndIteration,
    EndPass,
    TestResult,
)

log = logging.getLogger("paddle_tpu.trainer")

_BASE_PRNG_IMPL = None  # captured at first SGD init (process default)


class _NullPreemptionGuard:
    """Stand-in when there is no save_dir to flush to: SIGTERM keeps
    its process-default meaning."""

    preempted = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_PASS_OVER = object()  # what next() gives when the reader has ended

# Fed batches that may wait in the queue between the pass's worker and
# the training thread: one to be taken at once and one behind it, so
# that a feeder a little slower than the step for a batch or two does
# not reach the chip. Beside them one is in the step, one may be on the
# device for the next step and one in the worker's hand. A constant:
# nothing is better served by another.
FEED_AHEAD = 2


def _feed_size(feed) -> tuple:
    """(rows, bytes) of a fed batch: the leading dimension of its
    first leaf, and every leaf's bytes."""
    leaves = jax.tree_util.tree_leaves(feed)
    rows = leaves[0].shape[0] if leaves and leaves[0].ndim else 0
    return rows, sum(x.nbytes for x in leaves)


class _FedBatches:
    """The one source of a pass's fed batches, for both loops:
    `(batch_id, feed, placed)` in the reader's order from batch
    `first` on. `feed` is what the feeder made, on the host (numpy:
    what evaluators, handlers and the counters read); `placed` is the
    same batch on the device (`dp.shard_batch`), the step's argument.

    A worker thread runs `SGD._feed_ahead` up to FEED_AHEAD batches
    ahead; everything here runs on the training thread. `next()` takes
    the next fed batch under `train.input_wait.feeder` (counted
    `trainer.feed_ahead_ready` where it was there already, else
    `trainer.feed_ahead_waited`), places it unless `place_next` has
    (`trainer.feed_placed_late`, else `trainer.feed_placed_ahead`),
    fires its BeginIteration and counts its rows and bytes.

    `place_next()` is the loop's between a step's dispatch and its
    fetch: where the queue holds something it takes it and places it
    (`train.h2d`), so that the transfer runs while the device is busy
    with the step before and the next dispatch is an enqueue alone.
    It never waits for the worker: a device that is busy must not find
    the thread that fetches its loss blocked on the queue. The pass's
    end or the worker's exception taken there is kept for the next
    `next()`, after the step's EndIteration, where it arrives without
    this. One batch ahead at most: the device can use no more.

    Closing it, on any way out of the pass, stops and joins the worker
    and drops what was fed ahead, the placed batch with the queue's."""

    def __init__(self, trainer, reader, feeder, first, pass_id,
                 event_handler, tl, context):
        self._trainer, self._pass_id = trainer, pass_id
        self._event_handler, self._tl = event_handler, tl
        self._taken = None  # place_next's: (item, placed, raised)
        self._ahead = Buffered(
            lambda: trainer._feed_ahead(reader, feeder, first, context),
            FEED_AHEAD, name="feed-ahead",
        )

    def __iter__(self):
        return self

    def _place(self, feed):
        return shard_batch(feed, self._trainer.mesh, timeline=self._tl)

    def place_next(self) -> None:
        if self._taken is not None or not self._ahead.ready():
            return
        try:
            item = next(self._ahead, _PASS_OVER)
        except Exception as exc:  # the worker's: the next next() raises it
            self._taken = (None, None, exc)
            return
        placed = None if item is _PASS_OVER else self._place(item[1])
        self._taken = (item, placed, None)

    def __next__(self):
        taken, self._taken = self._taken, None
        with _tracing.span("train.input_wait.feeder") as waited:
            if taken is None:
                ready = self._ahead.ready()
                item, placed = next(self._ahead, _PASS_OVER), None
            else:
                ready = True
                item, placed, raised = taken
                if raised is not None:
                    raise raised
            if item is _PASS_OVER:
                waited.discard()
        if item is _PASS_OVER:
            raise StopIteration
        self._tl.add(waited)
        reg = _obs.get_registry()
        reg.counter(
            "trainer.feed_ahead_ready" if ready
            else "trainer.feed_ahead_waited").inc()
        batch_id, feed = item
        reg.counter(
            "trainer.feed_placed_late" if placed is None
            else "trainer.feed_placed_ahead").inc()
        if placed is None:
            placed = self._place(feed)
        self._event_handler(BeginIteration(self._pass_id, batch_id))
        rows, nbytes = _feed_size(feed)  # a fed batch goes to the step
        reg.counter("trainer.rows").inc(rows)
        reg.counter("trainer.feed_bytes").inc(nbytes)
        return batch_id, feed, placed

    def close(self) -> None:
        self._taken = None
        self._ahead.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class SGD:
    """Usage (mirrors paddle.v2.trainer.SGD):

        trainer = SGD(model_conf, opt_conf, mesh=mesh, evaluators=[...])
        trainer.train(reader=batched_reader, feeder=feeder,
                      num_passes=10, event_handler=handler)
    """

    def __init__(
        self,
        model_conf: ModelConf,
        opt_conf: OptimizationConf,
        mesh=None,
        evaluators: Optional[list] = None,
        seed: int = 0,
        params: Optional[dict] = None,
        watchdog=None,
        steps_per_dispatch: Optional[int] = None,
    ):
        """`watchdog`: None = follow the `watchdog` flag (default on);
        False disables; True or a `wdg.WatchdogConfig` enables with
        the given knobs. Enabled, the train step skips non-finite
        updates on device and `train` runs the escalation ladder
        (skip -> LR backoff -> rollback -> abort) plus SIGTERM-safe
        preemption (trainer/watchdog.py).

        `steps_per_dispatch`: None = the flag (default 1). N > 1 runs
        N consecutive batches as ONE jitted scan-of-steps dispatch
        (ROADMAP 5d: the bench trick promoted to a trainer option) —
        short-step models amortize the per-program dispatch floor
        N-fold while walking the bit-identical training trajectory
        (per-step RNG is derived inside the scan exactly as the
        sequential loop derives it). Events, evaluators and the
        watchdog still observe every batch; the differences are
        chunk-granular: LR backoff takes effect on the NEXT chunk,
        preemption checkpoints at chunk boundaries (un-dispatched
        buffered batches are replayed by the deterministic reader —
        still exactly-once), and a `train.step` span covers a chunk,
        labelled with its `steps` (a scan dispatch has no per-batch
        host boundary)."""
        if watchdog is None:
            watchdog = bool(_flags.get_flag("watchdog"))
        if watchdog is True:
            watchdog = wdg.WatchdogConfig()
        self.watchdog_conf = watchdog or None
        if steps_per_dispatch is None:
            steps_per_dispatch = int(
                _flags.get_flag("steps_per_dispatch") or 1
            )
        if steps_per_dispatch < 1:
            raise ValueError(
                f"steps_per_dispatch must be >= 1, got "
                f"{steps_per_dispatch}"
            )
        self.steps_per_dispatch = steps_per_dispatch
        self.last_watchdog_report: Optional[wdg.WatchdogReport] = None
        self._resume_skip_batches = 0
        self.opt_conf = opt_conf
        self.mesh = mesh
        # set-up, told from inside (`_build_part`, `_note_first_dispatch`)
        self._build_s = {}
        self._setup_pending = True
        self._start_to_build_s = process_age_s()
        if self._start_to_build_s is not None:
            _obs.get_registry().gauge("process.start_to_build_s").set(
                self._start_to_build_s)
        with self._build_part("all"):
            self._build(model_conf, evaluators, seed, params)
        self.global_step = 0

    @contextlib.contextmanager
    def _build_part(self, part: str):
        """A stretch of the trainer's build under the span
        `trainer.build.<part>` (the root, `all`: `trainer.build`); its
        duration is the gauge `trainer.build_s{part=}` and the `setup`
        event's: one pair of clock reads, three readers."""
        name = "trainer.build" + ("" if part == "all" else "." + part)
        with _tracing.span(name) as built:
            yield
        self._build_s[part] = built.dur_s
        _obs.get_registry().gauge("trainer.build_s").set(
            built.dur_s, part=part)

    def _build(self, model_conf, evaluators, seed, params) -> None:
        with self._build_part("network"):
            self.net = Network(model_conf)
        with self._build_part("optimizer"):
            self.opt = create_optimizer(
                self.opt_conf, self.net.param_confs)
        self.evaluator_confs = evaluators or []
        # FP-exception trap (TrainerMain.cpp:49 feenableexcept): jax
        # re-runs NaN-producing ops un-jitted and raises. Set from the
        # flag unconditionally so a previous trainer's setting does not
        # leak into this one.
        jax.config.update(
            "jax_debug_nans", bool(_flags.get_flag("trap_fp"))
        )
        # always sync (like trap_fp above): flag None restores whatever
        # impl the PROCESS started with (env/JAX config), not a
        # hardcoded default — so flag-less trainers never clobber a
        # user's JAX_DEFAULT_PRNG_IMPL choice
        global _BASE_PRNG_IMPL
        if _BASE_PRNG_IMPL is None:
            _BASE_PRNG_IMPL = jax.config.jax_default_prng_impl
        jax.config.update(
            "jax_default_prng_impl",
            _flags.get_flag("prng_impl") or _BASE_PRNG_IMPL,
        )
        with self._build_part("params"):
            key = _rng.root_key(seed or _flags.get_flag("seed"))
            init_key, self.step_key = jax.random.split(key)
            self.params = (params if params is not None
                           else self.net.init_params(init_key))
            self.state = self.net.init_state()
        with self._build_part("opt_state"):
            self.opt_state = self.opt.init_state(self.params)
        eval_layers = {
            c[k]
            for c in self.evaluator_confs
            for k in ("input", "label", "query_id")
            if k in c
        }
        self.step_fn = TrainStep(
            self.net, self.opt, mesh=self.mesh, keep_outputs=eval_layers,
            watchdog=self.watchdog_conf is not None,
        )
        with self._build_part("place"):
            self.params, self.opt_state, self.state = self.step_fn.place(
                self.params, self.opt_state, self.state
            )

    # ---- eval-only forward (jitted separately, no grad) ----
    def _eval_forward(self, feed):
        if not hasattr(self, "_fwd"):
            from paddle_tpu.analysis.recompile_guard import (
                RecompileGuard,
            )

            eval_guard = self._eval_guard = RecompileGuard(
                "eval_forward"
            )
            keep = (
                set(self.net.output_names)
                | set(self.net.cost_names)
                | {
                    c[k]
                    for c in self.evaluator_confs
                    for k in ("input", "label", "query_id")
                    if k in c
                }
            )

            def fwd(params, state, feed):
                eval_guard.note(params, feed)
                outs, _ = self.net.forward(
                    params, feed, state=state, train=False
                )
                costs = [outs[n].value for n in self.net.cost_names]
                return {k: v for k, v in outs.items() if k in keep}, costs

            self._fwd = jax.jit(fwd)
        return self._fwd(self.params, self.state, feed)

    def _make_evaluators(self):
        return [create_evaluator(c) for c in self.evaluator_confs]

    def _log_parameter_stats(self, pass_id: int, batch_id: int) -> None:
        """Per-parameter value statistics every
        --show_parameter_stats_period batches (the reference's
        TrainerInternal.cpp:80-90 avg/max-abs dump; grads are
        step-internal here, so value stats are the observable)."""
        for name in sorted(self.params):
            v = self.params[name]
            log.info(
                "param stats pass %d batch %d %s: shape=%s "
                "avg_abs=%.6f max_abs=%.6f",
                pass_id, batch_id, name, tuple(v.shape),
                float(jnp.mean(jnp.abs(v))),
                float(jnp.max(jnp.abs(v))),
            )

    def train_batch(self, feed) -> float:
        """Run ONE jitted train step on an already-fed Arg dict and
        return the cost — the TrainerInternal::trainOneBatch unit
        (TrainerInternal.cpp:66), used by the --job=time harness."""
        cost, _finite, _outs = self.run_step(feed)
        return cost

    def run_step(self, feed, lr_scale: float = 1.0,
                 timeline=None, fed=None) -> tuple:
        """One step on an already-fed Arg dict; returns
        (cost, finite, outs). The public stepping unit for external
        loops (paddle.v2's trainer drives this): their feed may be
        numpy, and is then placed on the device inside
        `train.dispatch` (`TrainStep.__call__`). `SGD.train` hands the
        feed as `dp.shard_batch` placed it and, as `fed`, its source
        of batches (`_FedBatches`): between this step's dispatch and
        its fetch, while the device is busy with it, the next batch
        is placed, if the queue has it (`train.h2d`). The placed
        batch is the caller's: nothing here keeps it past the call.

        In watchdog mode the step returns the 2-float health vector
        [loss, all_finite] — ONE device->host fetch carries both, so
        the finiteness verdict costs no extra transfer over the loss
        fetch the loop always made — and a non-finite batch's update
        was already skipped on device.

        Spans: `train.dispatch` (the enqueue: the jitted call returns
        before the device finishes; an external loop's numpy feed is
        transferred in it), `train.h2d` (the next batch's placement,
        with `fed`) and `train.fetch` (the host blocked on the loss).
        `timeline`: an obs.StepTimeline that is handed dispatch and
        fetch; on its fenced steps the params are fenced with
        block_until_ready under `train.fence`, so the update tail is
        measured too; every other step stays async beyond the loss
        fetch."""
        with _tracing.span("train.dispatch") as dispatched:
            rng = _rng.split_for_step(self.step_key, self.global_step)
            (
                self.params,
                self.opt_state,
                self.state,
                loss,
                outs,
            ) = self.step_fn(
                self.params, self.opt_state, self.state, feed,
                self.global_step, rng, lr_scale=lr_scale,
            )
        if self._setup_pending:
            self._note_first_dispatch(dispatched)
        self.global_step += 1
        if fed is not None:
            fed.place_next()
        with _tracing.span("train.fetch") as fetched:
            if self.step_fn.watchdog:
                health = np.asarray(loss)  # the single host fetch
                result = float(health[0]), bool(health[1]), outs
            else:
                result = float(loss), True, outs
        self._after_step(timeline, 1, dispatched, fetched, outs)
        return result

    def _note_first_dispatch(self, dispatched) -> None:
        """The first `train.dispatch` of this trainer's life says what
        it spent, once: the gauges `trainer.first_dispatch_s` (the
        span's own duration) and `trainer.first_dispatch.trace_s`,
        `.lower_s`, `.backend_s`, `.cache_hits`, `.cache_misses` (what
        the compile watch saw end inside it on this thread: the step's
        and every function's it pulled in; `backend_s` is a compile
        where the cache missed and a load where it hit). No later step,
        pass or `train()` call touches them, nor does a later lowering
        of the step. With them ONE `setup` event (no-op without a
        sink): the operator's reading of a restart."""
        self._setup_pending = False
        spent = _compile_cache.spent(dispatched.t0_ns, dispatched.t1_ns)
        reg = _obs.get_registry()
        reg.gauge("trainer.first_dispatch_s").set(dispatched.dur_s)
        for key, value in spent.items():
            reg.gauge("trainer.first_dispatch." + key).set(value)
        reg.event(
            "setup",
            start_to_build_s=self._start_to_build_s,
            build_s={k: round(v, 6) for k, v in self._build_s.items()},
            first_dispatch_s=round(dispatched.dur_s, 6),
            **{k: round(v, 6) for k, v in spent.items()},
        )

    def _after_step(self, timeline, n, dispatched, fetched,
                    outs=None) -> None:
        """Hand a dispatch's spans to the timeline, and fence where it
        says so. The layers' counters (`Network.stat_outputs`: a few floats
        a layer, extra outputs of the step) ride that fence: they are
        read only there, once the device has been waited for anyway, so
        no step gains a fetch."""
        if timeline is None:
            return
        timeline.add(dispatched)
        timeline.add(fetched)
        if timeline.fence_now(self.global_step):
            with _tracing.span("train.fence") as fenced:
                jax.block_until_ready(self.params)
                self._publish_layer_stats(outs)
            timeline.add(fenced)
        timeline.step_done(n)

    def _publish_layer_stats(self, outs) -> None:
        if not outs or not self.net.stat_outputs:
            return
        reg = _obs.get_registry()
        for name, layer in self.net.stat_outputs.items():
            if name not in outs:
                continue
            values = np.asarray(outs[name].value)
            # a chunk of steps stacks them: the last step's
            values = values.reshape(-1, values.shape[-1])[-1]
            layer.publish_stats(values, reg)

    def run_steps(self, feeds, lr_scale: float = 1.0,
                  timeline=None, fed=None) -> tuple:
        """Run len(feeds) consecutive steps in ONE jitted dispatch
        (lax.scan over the train step — multi-step pipelining,
        ROADMAP 5d). Returns (costs, finites, outs): per-batch cost
        and finiteness lists in step order (one [n]-row device->host
        fetch carries all of them), and the kept outputs with leaves
        stacked [n, ...] (slice leaf[i] for batch i's evaluator view).
        The per-step RNG/optimizer trajectory is identical to calling
        run_step n times. All feeds in one call must share one shape
        signature (they compile per distinct stacked shape). Spans,
        `timeline` and `fed` as in run_step; stacking the feeds (on
        the device, where they are placed already) is part of
        `train.dispatch`."""
        n = len(feeds)
        with _tracing.span("train.dispatch", steps=n) as dispatched:
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *feeds
            )
            (
                self.params,
                self.opt_state,
                self.state,
                losses,
                outs,
            ) = self.step_fn.multi(
                self.params, self.opt_state, self.state, stacked,
                self.global_step, self.step_key, lr_scale=lr_scale,
            )
        if self._setup_pending:
            self._note_first_dispatch(dispatched)
        self.global_step += n
        if fed is not None:
            fed.place_next()
        with _tracing.span("train.fetch") as fetched:
            health = np.asarray(losses)  # the single host fetch
        if self.step_fn.watchdog:
            costs = [float(h) for h in health[:, 0]]
            finites = [bool(h) for h in health[:, 1]]
        else:
            costs = [float(h) for h in health]
            finites = [True] * n
        self._after_step(timeline, n, dispatched, fetched, outs)
        return costs, finites, outs

    def train(
        self,
        reader: Callable,
        feeder: Callable,
        num_passes: int = 1,
        event_handler: Optional[Callable] = None,
        test_reader: Optional[Callable] = None,
        save_dir: Optional[str] = None,
        start_pass: int = 0,
        checkpoint_mode: Optional[str] = None,
        skip_batches: Optional[int] = None,
    ):
        """reader yields raw batches (lists of sample tuples); feeder
        converts them to Arg dicts.

        The input path runs a step ahead, off this thread. Every pass
        starts ONE worker thread (`feed-ahead`, after its BeginPass)
        that calls `reader()`, takes its batches in order, skips those
        a resume has trained already, calls `feeder(raw)` and puts the
        result into a FIFO queue of FEED_AHEAD fed batches
        (`data.reader.Buffered`, the reference's DoubleBuffer,
        DataProvider.h:249). This thread takes them out in the same
        order. So for every batch k: reader -> feeder (worker) ->
        placement on the device -> BeginIteration(k) -> step ->
        EndIteration(k) (this thread), the batches, their order and
        every loss being what an inline feeder gives; what is new is
        that the reader and the feeder of the batches after k, as far
        as the queue lets the worker run, may run BEFORE
        BeginIteration(k+1) and while batch k is in its step. A reader
        or feeder may assume: it is called from one thread at a time,
        in order, once a batch; it may not assume that thread to be
        the main one, nor read what a BeginIteration handler of its
        own batch writes, and should keep to numpy: the worker makes
        no JAX array.

        The transfer to the device runs a step ahead too, on this
        thread (`_FedBatches`, `dp.shard_batch`): between step k's
        dispatch and its fetch, while the device is busy with k, batch
        k+1 is taken from the queue IF it is there (this never waits
        for the worker) and placed, under `train.h2d`; step k+1's
        dispatch is then an enqueue alone. A batch that was not there
        (a pass's first, and whenever the worker is behind) is placed
        before its own dispatch. The step gets the placed batch;
        evaluators, handlers and counters get the feeder's own, on
        the host, which the loop keeps beside it until EndIteration:
        no evaluator gains a device-to-host copy. A fed batch is this
        call's for as long as anything here refers to it (the queue,
        the placed batch and its transfer, the step, a chunk of
        `steps_per_dispatch` feeds, the evaluators, a handler that
        keeps it) and is let go after its EndIteration: a feeder may
        hand out memory again once nothing refers to it, as
        `data.feeder.DataFeeder` does, and never before. A reader's or
        feeder's exception is raised here, by the wait for the batch
        it belonged to, after every batch before it has been trained
        (also where the placement took it from the queue: it is kept
        until then). Handlers, evaluators, the watchdog, checkpoints
        and every JAX call stay on this thread.

        However the call ends (the last pass over, a handler's
        exception, `Preempted`, `WatchdogAbort`) the worker is stopped
        and joined first. Batches fed ahead and not trained, the placed
        one among them, are dropped: a preemption's checkpoint counts
        trained batches only, and the deterministic reader replays the
        rest after the resume. A watchdog rollback keeps the queue and
        the placed batch: the data stream does not roll back.

        Spans: `train.input_wait.feeder` is this thread blocked on the
        queue for the next fed batch (nothing of the reader runs here,
        so `train.input_wait.reader` is not opened); `train.h2d` is a
        batch's placement, a sibling of `train.dispatch` under
        `train.step`; the worker's own work is `feed_ahead.reader` and
        `feed_ahead.feeder`, in the call's trace and under no step.
        Counters: `trainer.feed_ahead_ready` / `.feed_ahead_waited`
        (steps whose fed batch was, or was not, in the queue when
        asked for), `trainer.feed_placed_ahead` / `.feed_placed_late`
        (steps whose batch was on the device when the step was
        dispatched, and steps that placed it themselves) and
        `trainer.feed_worker_s` (the worker's seconds in the reader
        and the feeder).

        checkpoint_mode: None = the `checkpoint_mode` flag; "sync" =
        blocking per-pass save_pass; "async" = overlapped sharded
        writes (trainer/async_checkpoint.py) where only the
        device->host snapshot blocks the loop.

        skip_batches: batches of `start_pass` to skip before training
        resumes — the mid-pass preemption-resume offset. None = use the
        offset the last `resume()` recorded from a mid-pass checkpoint
        (0 when the checkpoint was an ordinary end-of-pass save)."""
        event_handler = event_handler or (lambda e: None)
        log_period = _flags.get_flag("log_period")
        ckpt_mode = checkpoint_mode or _flags.get_flag("checkpoint_mode")
        if ckpt_mode not in ("sync", "async"):
            raise ValueError(f"unknown checkpoint_mode {ckpt_mode!r}")
        if save_dir and ckpt_mode == "async":
            self._ensure_async_ckpt(save_dir)
        if skip_batches is None:
            skip_batches = self._resume_skip_batches
        self._resume_skip_batches = 0
        wd = (
            wdg.Watchdog(self.watchdog_conf)
            if self.watchdog_conf is not None else None
        )
        if wd is not None:
            self.last_watchdog_report = wd.report
        # per-step wall-time attribution: every piece of a step is
        # timed once, by a `tracing.span`, and the same span feeds the
        # timeline (the `trainer.*` counters, `last_timeline`, one
        # `timeline` event per pass), the event stream or flight
        # recorder where one is attached, and the profiler's trace
        # where a session is on. The device is fenced every
        # `timeline_sample_period` steps.
        tl = StepTimeline(
            sample_period=_flags.get_flag("timeline_sample_period"),
            compile_spent=_compile_cache.spent,
        )
        self.last_timeline = tl
        # one trace per train() call, every step a `train.step` root
        # in it. Joins the launching process's trace when the carrier
        # env var is set (tracing.CARRIER_ENV), or the caller's where
        # the thread is in one, else begins its own.
        trace = _tracing.attach_from_env(or_begin=True)
        # SIGTERM -> flag; checked at batch boundaries only, so the
        # in-flight jitted step always completes before the flush.
        # Installed only when there is somewhere to flush to.
        guard = (
            wdg.PreemptionGuard() if save_dir
            else _NullPreemptionGuard()
        )
        ok = False
        try:
          with guard, trace:
            self.last_trace_id = _tracing.current()[0]
            context = _tracing.context()  # the workers' spans join it
            for pass_id in range(start_pass, num_passes):
                event_handler(BeginPass(pass_id))
                evals = self._make_evaluators()
                costs = []
                first = skip_batches if pass_id == start_pass else 0
                with _FedBatches(
                    self, reader, feeder, first, pass_id,
                    event_handler, tl, context,
                ) as fed:
                    run_pass = (
                        self._run_pass_pipelined
                        if self.steps_per_dispatch > 1
                        else self._run_pass
                    )
                    run_pass(
                        pass_id, first, fed, event_handler, evals,
                        costs, tl, wd, guard, save_dir, ckpt_mode,
                        log_period,
                    )
                results = {ev.name: ev.result() for ev in evals}
                if test_reader is not None:
                    tr = self.test(test_reader, feeder)
                    event_handler(
                        TestResult(pass_id, tr["cost"], tr["evaluators"])
                    )
                if save_dir:
                    with _tracing.span(
                        "train.checkpoint", pass_id=pass_id,
                        mode=ckpt_mode,
                    ) as saved, GLOBAL_STATS.timer("checkpoint_save"):
                        if ckpt_mode == "async":
                            # every process commits its own shard; only the
                            # host snapshot inside save() blocks the loop
                            self._async_ckpt.save(
                                pass_id,
                                self.params,
                                self.opt_state,
                                self.state,
                                meta={"global_step": self.global_step},
                            )
                        else:
                            ckpt.save_pass(
                                save_dir,
                                pass_id,
                                jax.device_get(self.params),
                                jax.device_get(self.opt_state),
                                jax.device_get(self.state),
                                meta={"global_step": self.global_step},
                                save_only_one=_flags.get_flag("save_only_one"),
                            )
                    tl.add(saved)
                    if wd is not None:
                        # candidate only: promoted to the rollback
                        # target after `good_batches` healthy batches
                        # (watchdog.py "good checkpoint" rule)
                        wd.on_checkpoint(pass_id)
                # per-pass timer report (the WITH_TIMER StatSet dump,
                # TrainerInternal.cpp:177 area / utils/Stat.h:189) —
                # reset after logging so each pass reports only itself
                log.info("pass %d %s", pass_id, GLOBAL_STATS.report())
                GLOBAL_STATS.reset()
                # one structured timeline record per pass on the
                # event stream (cumulative over this train() call),
                # plus the human-readable fractions in the log
                tl.emit_pass(pass_id, self.global_step)
                log.info("pass %d timeline %s", pass_id,
                         tl.fractions())
                event_handler(EndPass(pass_id, results))
                if pass_id == start_pass:
                    # warmup over: every steady-state shape (incl.
                    # the ragged reader tail) has traced once — arm
                    # the jit-cache-miss tracker (ISSUE 13; the
                    # `recompile_guard` flag: off/record/strict). A
                    # retrace from here on is a silent compile stall
                    # in the hot loop.
                    rg_mode = _flags.get_flag("recompile_guard")
                    if rg_mode and rg_mode != "off":
                        self.step_fn.recompile_guard.arm(
                            strict=(rg_mode == "strict")
                        )
            ok = True
        finally:
            # drain in-flight async writes on EVERY exit path so a
            # background failure surfaces here, with the training
            # stack attached, not in a daemon thread; when already
            # unwinding, a drain failure must not mask the
            # training error
            if save_dir and ckpt_mode == "async":
                if ok:
                    self._async_ckpt.wait()
                else:
                    try:
                        self._async_ckpt.wait()
                    except Exception:
                        log.exception(
                            "async checkpoint drain failed while "
                            "handling a training error"
                        )

    def _feed_ahead(self, reader, feeder, first, context):
        """A pass's `(batch_id, feed)` in the reader's order, made on
        the pass's worker thread (numpy only, no JAX): the reader's
        next batch under `feed_ahead.reader`, the feeder's call under
        `feed_ahead.feeder`. Batches before `first` are read and not
        fed. `trainer.feed_worker_s` counts the seconds in both."""
        busy = _obs.get_registry().counter("trainer.feed_worker_s")
        batch_iter = iter(reader())
        for batch_id in itertools.count():
            with _tracing.attach(context):
                with _tracing.span("feed_ahead.reader") as read:
                    raw = next(batch_iter, _PASS_OVER)
                busy.inc(read.dur_s)
                if raw is _PASS_OVER:
                    return
                if batch_id < first:
                    continue
                with _tracing.span(
                        "feed_ahead.feeder", batch_id=batch_id) as fed:
                    feed = feeder(raw)
                busy.inc(fed.dur_s)
            yield batch_id, feed

    def _after_batch(self, pass_id, batch_id, cost, finite, outs, feed,
                     evals, costs, wd, save_dir, ckpt_mode,
                     event_handler, log_period, observe=True):
        """What the loop does with one batch's result (the body of
        `train.handlers`): evaluators, the watchdog's ladder (unless
        `observe` is off), the EndIteration handler, the log lines.
        Returns the ladder's action."""
        if finite:
            costs.append(cost)
            for ev in evals:
                ev.add_batch(outs, feed)
        action = None
        if wd is not None and observe:
            action = self._watchdog_act(
                wd, cost, finite, save_dir, ckpt_mode,
            )
        logged = (batch_id + 1) % log_period == 0
        results = (
            {ev.name: ev.result() for ev in evals} if logged else {}
        )
        event_handler(EndIteration(pass_id, batch_id, cost, results))
        if logged:
            log.info(
                "pass %d batch %d cost %.5f %s", pass_id, batch_id,
                float(np.mean(costs[-log_period:]))
                if costs else float("nan"),
                results,
            )
        stats_period = _flags.get_flag("show_parameter_stats_period")
        if stats_period and (batch_id + 1) % stats_period == 0:
            self._log_parameter_stats(pass_id, batch_id)
        return action

    def _run_pass(self, pass_id, first, fed, event_handler, evals,
                  costs, tl, wd, guard, save_dir, ckpt_mode, log_period):
        """One pass, a step a batch: each `train.step` span covers the
        wait for the batch's feed (and its placement, where the step
        before could not place it), its dispatch, the next batch's
        placement, its fetch, and its handlers. The step gets the
        placed batch, evaluators and handlers the host's."""
        batch_id = first - 1
        while True:
            with _tracing.span(
                "train.step", step_num=self.global_step,
                pass_id=pass_id, batch_id=batch_id + 1,
            ) as step:
                item = next(fed, None)
                if item is None:
                    step.discard()
                    return
                batch_id, feed, placed = item
                cost, finite, outs = self.run_step(
                    placed, wd.lr_scale() if wd else 1.0, timeline=tl,
                    fed=fed,
                )
                with _tracing.span("train.handlers") as handled:
                    self._after_batch(
                        pass_id, batch_id, cost, finite, outs, feed,
                        evals, costs, wd, save_dir, ckpt_mode,
                        event_handler, log_period,
                    )
                tl.add(handled)
            tl.end_step(step)
            if guard.preempted:
                # the in-flight batch completed and is counted in
                # batch_id+1: the flush loses zero completed-batch
                # work; what was fed ahead is dropped, and the
                # deterministic reader replays it after the resume
                self._preempt_flush(
                    save_dir, ckpt_mode, pass_id, batch_id + 1
                )
                raise wdg.Preempted(pass_id, batch_id + 1, save_dir)

    def _run_pass_pipelined(self, pass_id, first, fed, event_handler,
                            evals, costs, tl, wd, guard, save_dir,
                            ckpt_mode, log_period):
        """One pass with steps_per_dispatch > 1: batches are buffered
        and dispatched as scan-of-steps chunks (run_steps). Per-batch
        semantics preserved: BeginIteration fires when a batch is
        collected (before its step runs), EndIteration/evaluators/
        watchdog observe every batch in order after its chunk lands.
        Chunk-granular differences are documented on __init__. A
        shape-signature change (e.g. a ragged final reader batch)
        closes the chunk early and opens the next with the odd batch,
        so mixed shapes cost one extra compile, never an error. One
        `train.step` span covers a chunk: its batches' input waits
        and placements (each batch is placed as it is collected, the
        chunk stacked on the device), one dispatch, the next chunk's
        first batch placed if the queue has it, one fetch, the
        handlers of all its batches."""
        spd = self.steps_per_dispatch
        done_upto = first  # batches of this pass fully trained

        def _sig(feed):
            return (
                jax.tree_util.tree_structure(feed),
                tuple(
                    (getattr(x, "shape", None), getattr(x, "dtype", None))
                    for x in jax.tree_util.tree_leaves(feed)
                ),
            )

        def _check_preempt():
            if guard.preempted:
                # buffered batches were never dispatched — drop them;
                # the deterministic reader replays them after resume,
                # so every batch still trains exactly once
                self._preempt_flush(
                    save_dir, ckpt_mode, pass_id, done_upto
                )
                raise wdg.Preempted(pass_id, done_upto, save_dir)

        def flush(buf):
            cs, fs, outs = self.run_steps(
                [placed for _, _, placed in buf],
                wd.lr_scale() if wd else 1.0, timeline=tl, fed=fed,
            )
            with _tracing.span("train.handlers") as handled:
                observe = True
                for j, (bid, feed, _) in enumerate(buf):
                    action = self._after_batch(
                        pass_id, bid, cs[j], fs[j],
                        jax.tree_util.tree_map(lambda x: x[j], outs)
                        if fs[j] and evals else None,
                        feed, evals, costs, wd, save_dir, ckpt_mode,
                        event_handler, log_period, observe=observe,
                    )
                    if action == wdg.ROLLBACK:
                        # the chunk's remaining batches trained on the
                        # now-rolled-back trajectory; their costs are
                        # discarded progress — stop feeding the ladder
                        observe = False
            tl.add(handled)

        held = None  # (item, sig) whose signature closed a chunk
        pass_over = False
        while not pass_over:
            buf, sig = ([held[0]], held[1]) if held else ([], None)
            held = None
            with _tracing.span(
                "train.step", step_num=self.global_step, pass_id=pass_id,
            ) as step:
                while len(buf) < spd:
                    _check_preempt()
                    item = next(fed, None)
                    if item is None:
                        pass_over = True
                        break
                    fsig = _sig(item[1])
                    if buf and fsig != sig:
                        held = (item, fsig)
                        break
                    buf.append(item)
                    sig = fsig
                if buf:
                    step.set_label("batch_id", buf[0][0])
                    step.set_label("steps", len(buf))
                    flush(buf)
                    done_upto = buf[-1][0] + 1
                else:
                    step.discard()
            if buf:
                tl.end_step(step)
        _check_preempt()

    def _watchdog_act(self, wd, cost, finite, save_dir, ckpt_mode):
        """Run the ladder on one batch's (cost, finite) verdict;
        perform the rollback here (the trainer owns params/resume);
        returns the ladder's action (the pipelined loop stops
        observing a chunk after ROLLBACK)."""
        action = wd.observe(cost, finite, self.global_step - 1)
        if action == wdg.ROLLBACK:
            target = wd.good_pass
            with GLOBAL_STATS.timer("watchdog_rollback"):
                if ckpt_mode == "async" and getattr(
                    self, "_async_ckpt", None
                ) is not None:
                    # commit in-flight writes (and surface write
                    # errors) before reading manifests back
                    self._async_ckpt.wait()
                try:
                    self.resume(save_dir, pass_id=target)
                except (FileNotFoundError, ValueError, OSError) as e:
                    # the promoted pass can be rotated away
                    # (save_only_one / keep_last) before a rollback
                    # needs it: out of rungs — abort with the report,
                    # never a raw load traceback
                    wd.report.aborted = True
                    wd.report.abort_reason = (
                        f"rollback target pass {target} unloadable "
                        f"({type(e).__name__}: {e}) — rotated away?"
                    )
                    wd.record_event("abort", self.global_step,
                                    reason=wd.report.abort_reason)
                    log.error("watchdog abort: %s",
                              wd.report.abort_reason)
                    raise wdg.WatchdogAbort(wd.report) from e
            log.warning(
                "watchdog: rolled back to checkpoint pass %d "
                "(global_step %d)", target, self.global_step,
            )
            wd.on_rollback(target, self.global_step)
        elif action == wdg.ABORT:
            log.error("watchdog abort: %s", wd.report.abort_reason)
            raise wdg.WatchdogAbort(wd.report)
        return action

    def _preempt_flush(self, save_dir, ckpt_mode, pass_id,
                       batches_done):
        """SIGTERM landed: flush a mid-pass checkpoint covering every
        COMPLETED batch, so the respawned process resumes at
        `batches_done` with zero lost work."""
        meta = {
            "global_step": self.global_step,
            "mid_pass": True,
            "batch_in_pass": batches_done,
        }
        with GLOBAL_STATS.timer("preempt_flush"):
            if ckpt_mode == "async":
                self._ensure_async_ckpt(save_dir)
                self._async_ckpt.save(
                    pass_id, self.params, self.opt_state, self.state,
                    meta=meta,
                )
                self._async_ckpt.wait()
            else:
                ckpt.save_pass(
                    save_dir, pass_id,
                    jax.device_get(self.params),
                    jax.device_get(self.opt_state),
                    jax.device_get(self.state),
                    meta=meta,
                )
        _obs.get_registry().counter("trainer.preemptions").inc()
        _obs.get_registry().event(
            "preempt_flush", global_step=self.global_step,
            pass_id=pass_id, batch_in_pass=batches_done,
        )
        log.warning(
            "preempted: flushed pass %d at batch %d to %s; exiting "
            "for resume", pass_id, batches_done, save_dir,
        )

    def recompile_violations(self) -> list:
        """Steady-state retraces recorded by the train step's armed
        recompile guard (the `recompile_guard` flag; ISSUE 13) —
        empty means the hot loop never recompiled after warmup."""
        return list(self.step_fn.recompile_guard.violations)

    def test(self, reader: Callable, feeder: Callable) -> dict:
        """Evaluation pass (reference: trainer/Tester.h)."""
        evals = self._make_evaluators()
        costs = []
        n = 0
        for raw in reader():
            feed = feeder(raw)
            outs, batch_costs = self._eval_forward(feed)
            costs.append(float(np.mean([np.mean(c) for c in batch_costs])))
            for ev in evals:
                ev.add_batch(outs, feed)
            n += 1
        return {
            "cost": float(np.mean(costs)) if costs else float("nan"),
            "evaluators": {ev.name: ev.result() for ev in evals},
        }

    def _ensure_async_ckpt(self, save_dir: str):
        cur = getattr(self, "_async_ckpt", None)
        if cur is not None and cur.save_dir == save_dir:
            return cur
        if cur is not None:
            cur.close()
        self._async_ckpt = actp.AsyncCheckpointer(
            save_dir,
            keep_last=1 if _flags.get_flag("save_only_one") else 0,
        )
        return self._async_ckpt

    def resume(self, save_dir: str, pass_id: int = -1) -> int:
        """Load a checkpoint; returns the next pass id (start_pass
        semantics of trainer/ParamUtil.h). Reads whichever format is
        newest and COMPLETE: async sharded passes (manifest-verified,
        torn shards skipped) or synchronous save_pass directories.

        A MID-PASS checkpoint (the preemption flush) returns its own
        pass id — the pass is unfinished — and records the number of
        already-trained batches; the next `train()` call skips exactly
        that many batches of its first pass, so a SIGTERM/resume cycle
        replays nothing and loses nothing."""
        self._resume_skip_batches = 0
        if pass_id >= 0:
            use_async = (
                pass_id in actp.list_passes(save_dir)
                and actp.verify_pass(save_dir, pass_id)[0]
            )
        else:
            async_latest = actp.latest_complete_pass(save_dir)
            sync_passes = ckpt.list_sync_passes(save_dir)
            use_async = async_latest >= 0 and (
                not sync_passes or async_latest >= sync_passes[-1]
            )
        if use_async:
            # pass the already-resolved id: load_pass(-1) would re-hash
            # every pass a second time to find the latest
            tree, meta = actp.load_pass(
                save_dir, pass_id if pass_id >= 0 else async_latest
            )
            params = tree["params"]
            opt_state = tree.get("opt_state")
            state = tree.get("state")
        else:
            params, opt_state, state, meta = ckpt.load_pass(
                save_dir, pass_id
            )
        self.params = {k: jax.numpy.asarray(v) for k, v in params.items()}
        if opt_state is not None:
            self.opt_state = jax.tree_util.tree_map(
                jax.numpy.asarray, opt_state
            )
        if state is not None:
            self.state = jax.tree_util.tree_map(jax.numpy.asarray, state)
        self.params, self.opt_state, self.state = self.step_fn.place(
            self.params, self.opt_state, self.state
        )
        self.global_step = meta.get("global_step", 0)
        if meta.get("mid_pass"):
            self._resume_skip_batches = int(
                meta.get("batch_in_pass", 0)
            )
            return meta["pass_id"]
        return meta["pass_id"] + 1


class Inferencer:
    """Inference runner (reference: python/paddle/v2/inference.py:9,93 and
    the C-API serving path capi/gradient_machine.h:73): load a merged
    model or pass (net, params), jit the forward, return numpy outputs."""

    def __init__(self, net: Network, params: dict, state=None, outputs=None):
        self.net = net
        self.params = params
        self.state = state or net.init_state()
        self.output_names = outputs or net.output_names

        def fwd(params, state, feed):
            outs, _ = self.net.forward(
                params, feed, state=state, train=False,
                outputs=self.output_names,
            )
            return {n: outs[n] for n in self.output_names}

        self._fwd = jax.jit(fwd)

    @classmethod
    def from_merged(cls, path: str, outputs=None):
        conf, params, state = ckpt.load_merged(path)
        return cls(Network(conf), params, state, outputs)

    def infer(self, feed: dict) -> dict:
        outs = self._fwd(self.params, self.state, feed)
        return {
            n: np.asarray(a.value if a.value is not None else a.ids)
            for n, a in outs.items()
        }
