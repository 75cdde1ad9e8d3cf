"""Kind `train`: a v2-API user's training loop, `SGD.train(reader, feeder)`.

Set-up builds ONE trainer on seeded weights, drives it through its first
steps by the window's own call and feed (`SGD.train` over
`data.reader.batched` and `data.feeder.DataFeeder`), keeps what the
comparison reads, and hands the same trainer to the window. The window is
one more `SGD.train` call whose reader ends the pass when the time is up:
the rate (`train_units_per_s`, the kind's one rate) is all the units of
the steps that ended in it, which are what the cell's `traffic.count`
counts (rows, or the real steps of one length group: images, tokens), over
the seconds from its first BeginIteration to its last EndIteration.
The reference follows the first steps after the window has closed, the
device's memory has been read and the trainer is freed.
"""

from __future__ import annotations

import statistics
import time

from benchmarks import compare, harness, traffic
from benchmarks.reference import train as ref_train

FIRST_STEPS = 3          # the reference follows these
WARM_STEPS = 4           # set-up drives these; the 4th is past the fetches


def optimizer(cfg):
    from paddle_tpu.core.config import OptimizationConf

    o = cfg["optimizer"]
    kw = {"learning_method": o["method"], "learning_rate": o["learning_rate"]}
    if o["method"] == "momentum":
        kw["momentum"] = o["momentum"]
    if o["method"] == "adam":
        kw.update(adam_beta1=o["beta1"], adam_beta2=o["beta2"],
                  adam_epsilon=o["epsilon"])
    return OptimizationConf(**kw)


def feeder_for(slots):
    from paddle_tpu.data import feeder as F

    types = {}
    for s in slots:
        types[s["name"]] = {
            "dense": lambda s: F.dense_vector(s["dim"]),
            "ids": lambda s: F.integer_value(s["vocab"]),
            "ids_seq": lambda s: F.integer_value_sequence(s["vocab"]),
        }[s["type"]](s)
    feeding = {s["name"]: i for i, s in enumerate(slots)}
    return F.DataFeeder(feeding, types)


def build_trainer(cell, seed, devices, params):
    """The program, configured as the configuration states."""
    import jax

    from paddle_tpu.core import flags
    from paddle_tpu.core.mesh import make_mesh
    from paddle_tpu.trainer import SGD

    cfg = cell.config
    if cfg["matmul_precision"] != "float32":
        flags.set_flag("matmul_precision", cfg["matmul_precision"])
    flags.set_flag("recompile_guard", "record")
    jax.config.update("jax_default_prng_impl", "rbg")
    mesh = None
    if cell.workload.get("mesh"):
        mesh = make_mesh(dict(cell.workload["mesh"]), devices=list(devices))
    return SGD(cell.model.program_conf(cfg), optimizer(cfg), mesh=mesh,
               seed=1 + int(seed) % (2 ** 30), params=params)


class Loop:
    """The reader, the feeder and the event handler of one `SGD.train`
    call, with the harness's spans around its own reader and feeder calls
    (since the program feeds a step ahead these run on its worker thread;
    `input_wait_share` alone still reads them)."""

    def __init__(self, pool, feeder, spans, unit, stream, stop):
        self.pool, self.feeder, self.spans = pool, feeder, spans
        self.unit, self.stream, self.stop = unit, stream, stop
        self.rows = []               # pool rows of every batch yielded
        self.work = 0                # units of the steps that have ended
        self.steps = 0
        self.losses = []
        self.ends = []               # when each step ended
        self.feeds = []              # seconds in the feeder, each batch
        self.t_first = self.t_last = None
        self.on_end = None

    def samples(self):
        """The v2 reader: one sample at a time; the pass ends at a batch
        boundary once `stop` says so."""
        for rows in self.pool.batches(self.stream):
            if self.stop(self):
                return
            self.rows.append(rows)
            for i in rows:
                yield self.pool.sample(i)

    def reader(self):
        from paddle_tpu.data.reader import batched

        it = batched(self.samples, self.pool.batch)()
        while True:
            self.spans.begin("reader")
            try:
                raw = next(it)
            except StopIteration:
                return
            finally:
                self.spans.end("reader")
            yield raw

    def feed(self, raw):
        t0 = time.perf_counter()
        with self.spans.span("feeder"):
            out = self.feeder(raw)
        self.feeds.append(time.perf_counter() - t0)
        return out

    def handle(self, event):
        from paddle_tpu.trainer.events import BeginIteration, EndIteration

        if isinstance(event, BeginIteration) and self.t_first is None:
            self.t_first = time.perf_counter()
        if isinstance(event, EndIteration):
            self.t_last = time.perf_counter()
            self.ends.append(self.t_last)
            self.work += self.pool.count(self.rows[event.batch_id], self.unit)
            self.steps += 1
            self.losses.append(float(event.cost))
            if self.on_end is not None:
                self.on_end(self)

    def run(self, trainer):
        trainer.train(reader=self.reader, feeder=self.feed, num_passes=1,
                      event_handler=self.handle)


def _program_readings(trainer, opt, params0_fn, loop):
    """Set-up's first steps -> what the comparison reads of the program."""
    import jax

    got = {}

    def on_end(lp):
        if lp.steps == 1:
            g = {k: ref_train.first_gradient_from_state(opt, s)
                 for k, s in trainer.opt_state.items()}
            got["grad1"] = jax.jit(ref_train.leaf_norms)(g)
        if lp.steps == FIRST_STEPS:
            p0 = params0_fn()
            got["delta"] = ref_train.delta(dict(trainer.params), p0)

    loop.on_end = on_end
    loop.run(trainer)
    return {"loss": loop.losses[:FIRST_STEPS],
            "grad1": {k: float(v) for k, v in got["grad1"].items()},
            "delta": {k: float(v) for k, v in got["delta"].items()}}


def _step_memory(trainer, feed_like):
    """`memory_analysis()` of the step executable the window runs: the jit's
    own cached lowering, so this compiles nothing."""
    from paddle_tpu.core import rng as _rng

    r = _rng.split_for_step(trainer.step_key, trainer.global_step)
    args = (trainer.params, trainer.opt_state, trainer.state, feed_like,
            trainer.global_step, r)
    if trainer.step_fn.watchdog:
        args += (1.0,)
    if trainer.mesh is not None:
        from paddle_tpu.parallel.dp import shard_batch

        args = args[:3] + (shard_batch(feed_like, trainer.mesh),) + args[4:]
    compiled = trainer.step_fn._step.lower(*args).compile()
    return compiled.memory_analysis(), compiled


def reference_readings(cell, seed, pool, mode="f32", fault=None):
    """The reference (or, in a lower `mode`, the control) through the
    first steps' batches."""
    import jax.numpy as jnp

    cfg = cell.config
    ref = cell.model.reference
    params = ref_train.init_params(ref.param_spec(cfg), seed)
    batches = []
    for rows, _ in zip(pool.batches(0), range(FIRST_STEPS)):
        cols = cell.model.reference_batch(pool.arrays(rows))
        batches.append({k: jnp.asarray(v) for k, v in cols.items()})
    return ref_train.first_steps(_reference_loss(cell, mode),
                                 cfg["optimizer"], params, batches,
                                 fault=fault)


_LOSSES = {}


def _reference_loss(cell, mode):
    """One closure per (cell, precision), so that its jitted step is too."""
    key = (cell.name, mode)
    if key not in _LOSSES:
        cfg, ref = cell.config, cell.model.reference
        _LOSSES[key] = lambda p, b: ref.loss(cfg, p, b, mode)
    return _LOSSES[key]


def setup(cell, seed, devices, clock, spans):
    """-> (trainer, pool, feeder, program readings, split of set-up)."""
    cfg = cell.config
    t0 = time.perf_counter()
    pool = traffic.Pool(cell.traffic, seed)
    t_pool = time.perf_counter()
    spec = cell.model.reference.param_spec(cfg)
    trainer = build_trainer(cell, seed, devices,
                            ref_train.init_params(spec, seed))
    have = {k: tuple(v.shape) for k, v in trainer.params.items()}
    want = {k: tuple(s) for k, (s, _) in spec.items()}
    if have != want:
        raise SystemExit("the reference's parameters are not the "
                         f"program's: {set(have) ^ set(want) or 'shapes'}")
    t_build = time.perf_counter()
    c0 = clock.mark()
    feeder = feeder_for(cell.traffic["slots"])
    loop = Loop(pool, feeder, spans, cell.traffic["count"], 0,
                stop=lambda lp: len(lp.rows) >= WARM_STEPS)
    readings = _program_readings(
        trainer, cfg["optimizer"],
        lambda: ref_train.init_params(spec, seed), loop)
    t_warm = time.perf_counter()
    split = {"pool_s": t_pool - t0, "build_s": t_build - t_pool,
             "first_steps_s": t_warm - t_build,
             "compile_or_load_s": clock.compile_s - c0[0],
             "cache_hits": clock.hits - c0[1],
             "cache_misses": clock.misses - c0[2]}
    return trainer, pool, feeder, readings, split


def window(cell, trainer, pool, feeder, spans, seconds):
    """One `SGD.train` call of `seconds` seconds. -> the Loop."""
    deadline = []

    def stop(lp):
        if not deadline:
            deadline.append(time.perf_counter() + seconds)
        return time.perf_counter() >= deadline[0]

    spans.reset()
    loop = Loop(pool, feeder, spans, cell.traffic["count"], 1, stop)
    loop.run(trainer)
    return loop


def _spread(xs):
    return [min(xs), statistics.median(xs), max(xs)] if xs else None


def _by_tenth(ends, values):
    """The median of a step's `values` in each tenth of the window, so
    that a run's log shows whether the host's speed drifted within it."""
    if not ends:
        return None
    t0, span = ends[0], max(ends[-1] - ends[0], 1e-9)
    tenths = [[] for _ in range(10)]
    for t, v in zip(ends, values):
        tenths[min(9, int(10 * (t - t0) / span))].append(v)
    return [round(statistics.median(x), 4) if x else None for x in tenths]


def _longest_step(loop):
    """Which step took longest, when it ended, and how much of it was the
    feeder's call for its batch (on the program's worker thread)."""
    if not loop.ends:
        return None
    steps = [b - a for a, b in zip([loop.t_first] + loop.ends, loop.ends)]
    i = max(range(len(steps)), key=steps.__getitem__)
    return {"index": i, "s": round(steps[i], 4),
            "ended_at_s": round(loop.ends[i] - loop.t_first, 3),
            "feeder_s": round(loop.feeds[i], 4)}


def run(ctx) -> dict:
    """Set-up, window, comparison. `ctx` is run.py's: cell, seed, seconds,
    devices, clock, spans, tracer, t_start."""
    cell = ctx.cell
    trainer, pool, feeder, prog, split = setup(
        cell, ctx.seed, ctx.devices, ctx.clock, ctx.spans)
    ctx.setup_done(split)
    c0 = ctx.clock.mark()
    with harness.HostWatch() as host, ctx.tracer:
        loop = window(cell, trainer, pool, feeder, ctx.spans, ctx.seconds)
    window_s = (loop.t_last - loop.t_first) if loop.steps else 0.0
    violations = trainer.recompile_violations()
    compiled_in_window = ctx.clock.programs_since(c0)

    feed_like = feeder([pool.sample(i) for i in loop.rows[-1]])
    ma, _ = _step_memory(trainer, feed_like)
    memory = ctx.memory([ma])
    flops = loop.work * cell.model.train_flops_per_row(cell.config,
                                                       cell.traffic)
    timeline = getattr(trainer, "last_timeline", None)
    del trainer, feed_like
    ctx.free()

    t_ref = time.perf_counter()
    ref = reference_readings(cell, ctx.seed, pool)
    numbers = compare.training_numbers(prog, ref)
    ok, rows = compare.judge(numbers, cell.limits)
    notes = {"grad1_leaf": numbers["grad1_leaf"],
             "delta_leaf": numbers["delta_leaf"],
             "program_loss": prog["loss"], "reference_loss": ref["loss"],
             "reference_s": time.perf_counter() - t_ref}
    problems = []
    if violations:
        problems.append(f"recompiled in the window: {violations[:2]}")
    if compiled_in_window:
        problems.append(f"{compiled_in_window} compilations in the window")
    if not loop.steps:
        problems.append("no step ended in the window")
    return {
        "correct": ok and not problems, "compared": rows, "notes": notes,
        "problems": problems, "attempted": loop.steps, "failed": 0,
        "work": loop.work, "window_s": window_s, "flops": flops,
        "steps": loop.steps, "memory": memory,
        "spans": dict(ctx.spans.total),
        "log": {"steps_in_window": loop.steps,
                "step_s_min_median_max": _spread(
                    [b - a for a, b in zip(loop.ends, loop.ends[1:])]),
                "feeder_s_min_median_max": _spread(loop.feeds),
                "step_s_by_tenth": _by_tenth(
                    loop.ends, [b - a for a, b in
                                zip([loop.t_first] + loop.ends, loop.ends)]),
                "feeder_s_by_tenth": _by_tenth(loop.ends, loop.feeds),
                "longest_step": _longest_step(loop), "host": host.report,
                "first_loss": loop.losses[:1], "last_loss": loop.losses[-1:],
                "timeline": timeline.fractions() if timeline else None},
    }
