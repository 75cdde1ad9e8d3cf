"""The `paddle` CLI dispatcher.

Reference: paddle/scripts/submit_local.sh.in:3-13 — subcommands
train / pserver / merge_model / dump_config / make_diagram / version —
plus trainer/TrainerMain.cpp and trainer/MergeModel.cpp. TPU-native
differences: there is no pserver process (data parallelism is one pjit
program; `master` serves the elastic-input role instead), and `serve`
runs the continuous-batching inference server (paddle_tpu/serving).

A config file is a Python source that defines:
    get_config() -> (ModelConf, OptimizationConf)
and optionally:
    train_reader() / test_reader()   (batched sample readers)
    feeder(batch) -> feed dict of Args

Usage:  python -m paddle_tpu <cmd> [args]   (installed alias: paddle)
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import sys


def _load_config(path: str):
    spec = importlib.util.spec_from_file_location("_paddle_config", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not hasattr(mod, "get_config"):
        raise SystemExit(
            f"{path} must define get_config() -> (ModelConf, "
            f"OptimizationConf)"
        )
    return mod


def cmd_version(args):
    from paddle_tpu import __version__

    print(f"paddle_tpu {__version__}")
    import jax

    print(f"jax {jax.__version__}, devices: {jax.devices()}")
    return 0


def cmd_dump_config(args):
    if _is_v1_config(args.config):
        from paddle_tpu.compat.config_parser import parse_config

        tc = parse_config(args.config, args.config_args)
        model_conf, opt_conf = tc.model, tc.opt
    else:
        mod = _load_config(args.config)
        model_conf, opt_conf = mod.get_config()
    doc = {
        "model": json.loads(model_conf.to_json()),
        "optimization": vars(opt_conf),
    }
    out = json.dumps(doc, indent=2, default=str)
    if args.output:
        with open(args.output, "w") as f:
            f.write(out)
    else:
        print(out)
    return 0


def cmd_train(args):
    from paddle_tpu.core import compile_cache
    from paddle_tpu.launch import distributed_init_from_env
    from paddle_tpu.obs import flight_recorder as _flight
    from paddle_tpu.trainer import SGD
    from paddle_tpu.trainer import events
    from paddle_tpu.trainer import watchdog as wdg

    # PADDLE_FLIGHT_DIR=<dir> arms the anomaly flight recorder
    # (watchdog rungs dump span/timeline/event bundles there)
    _flight.enable_from_env()
    compile_cache.enable()

    # under `paddle launch` every worker carries the rendezvous env —
    # join it before any device use (cluster_train trainer_id wiring)
    distributed_init_from_env()

    # --job=test needs only the config's TEST data source; everything
    # else drives the train source. The config is parsed exactly once.
    which = "test" if args.job == "test" else "train"
    if _is_v1_config(args.config):
        # UNMODIFIED reference v1 config: the `paddle train --config X
        # --config_args Y` path (trainer/TrainerMain.cpp:32 +
        # config_parser.py:3724) — model + optimizer + data provider
        # all come from the config file itself
        model_conf, opt_conf, reader, feeder, evaluators = _v1_setup(
            args.config, args.config_args, which
        )
    else:
        mod = _load_config(args.config)
        model_conf, opt_conf = mod.get_config()
        if which == "test":
            if not hasattr(mod, "test_reader"):
                raise SystemExit(
                    f"{args.config} must define test_reader() for "
                    "--job=test"
                )
            reader = mod.test_reader()
        else:
            reader = mod.train_reader()
        feeder = getattr(mod, "feeder", None)
        if feeder is None:
            raise SystemExit(f"{args.config} must define feeder(batch)")
        evaluators = getattr(mod, "evaluators", None) or []
    trainer = SGD(model_conf, opt_conf, evaluators=evaluators)

    if args.job == "test":
        # evaluation-only pass (trainer/Tester.h; `paddle train
        # --job=test`), optionally on a saved checkpoint
        # (--save_dir/--pass_id = --init_model_path semantics)
        if args.save_dir:
            trainer.resume(args.save_dir, args.pass_id)
        res = trainer.test(reader, feeder)
        print(
            f"test cost {res['cost']:.6f} "
            + " ".join(
                f"{k}={v}" for k, v in res["evaluators"].items()
            )
        )
        return 0

    if args.job == "time":
        # --job=time (trainer/TrainerBenchmark.cpp, the harness behind
        # the reference's published numbers, benchmark/paddle/image/
        # run.sh:10): warm up, then report ms/batch over the next
        # batches
        import time as _time

        want = args.time_batches + 5
        batches = []
        while len(batches) < want:
            got_any = False
            for b in reader():
                got_any = True
                batches.append(b)
                if len(batches) == want:
                    break
            if not got_any:  # empty source: error out, don't spin
                raise SystemExit("data source produced no batches")
        feeds = [feeder(b) for b in batches]
        for f in feeds[:5]:  # warmup/compile
            trainer.train_batch(f)
        t0 = _time.perf_counter()
        for f in feeds[5:]:
            trainer.train_batch(f)
        n = len(feeds) - 5
        ms = (_time.perf_counter() - t0) / max(n, 1) * 1e3
        print(f"time: {ms:.3f} ms/batch over {n} batches")
        return 0

    def handler(ev):
        if isinstance(ev, events.EndIteration) and (
            ev.batch_id % args.log_period == 0
        ):
            print(
                f"pass {ev.pass_id} batch {ev.batch_id} "
                f"cost {ev.cost:.6f}"
            )

    # auto-resume: a respawned (preempted or crashed) worker picks up
    # from the newest complete checkpoint in save_dir — including a
    # MID-PASS preemption flush, which resumes at the exact batch
    # (--from_scratch opts out)
    start_pass = 0
    if args.save_dir and not args.from_scratch:
        try:
            start_pass = trainer.resume(args.save_dir)
            print(
                f"resuming from {args.save_dir}: start pass "
                f"{start_pass}, skip {trainer._resume_skip_batches} "
                f"batches", flush=True,
            )
        except (FileNotFoundError, ValueError):
            pass  # no (complete) checkpoint yet: fresh start
    try:
        trainer.train(
            reader=reader,
            feeder=feeder,
            num_passes=args.num_passes,
            event_handler=handler,
            save_dir=args.save_dir or None,
            start_pass=start_pass,
        )
    except wdg.Preempted as p:
        # the contract launch.py keys on: checkpoint flushed, exit
        # EXIT_PREEMPTED (75), respawn resumes losslessly
        print(f"PREEMPTED pass {p.pass_id} batch {p.batches_done}",
              flush=True)
        return wdg.EXIT_PREEMPTED
    except wdg.WatchdogAbort as a:
        print("WATCHDOG_ABORT " + json.dumps(a.report.to_dict()),
              flush=True)
        return 1
    return 0


def _is_v1_config(path: str) -> bool:
    """A config is v2-native iff its module BINDS the name `get_config`
    at top level (def / assignment / import); everything else is an
    unmodified v1 file for compat parse_config. Decided from the AST —
    a substring match would misroute a v1 config that merely mentions
    get_config in a comment or defines get_configuration."""
    import ast

    with open(path) as f:
        try:
            tree = ast.parse(f.read(), path)
        except SyntaxError:
            return True  # py2-era source: certainly a v1 config

    # bindings anywhere at module scope count, including under try/if
    # (guarded imports); class/function BODIES don't bind module names,
    # so those subtrees are not descended into
    def binds(node) -> bool:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return node.name == "get_config"
        if isinstance(node, ast.ClassDef):
            return False
        def target_binds(t) -> bool:
            if isinstance(t, ast.Name):
                return t.id == "get_config"
            if isinstance(t, (ast.Tuple, ast.List)):
                return any(target_binds(e) for e in t.elts)
            if isinstance(t, ast.Starred):
                return target_binds(t.value)
            return False

        if isinstance(node, ast.Assign) and any(
            target_binds(t) for t in node.targets
        ):
            return True
        if isinstance(
            node, (ast.AnnAssign, ast.AugAssign)
        ) and target_binds(node.target):
            return True
        if isinstance(node, (ast.With, ast.AsyncWith)) and any(
            item.optional_vars is not None
            and target_binds(item.optional_vars)
            for item in node.items
        ):
            return True
        if isinstance(node, (ast.For, ast.AsyncFor)) and target_binds(
            node.target
        ):
            return True
        if isinstance(node, ast.NamedExpr) and target_binds(node.target):
            return True
        if isinstance(node, (ast.Import, ast.ImportFrom)) and any(
            (alias.asname or alias.name) == "get_config"
            for alias in node.names
        ):
            return True
        return any(binds(c) for c in ast.iter_child_nodes(node))

    return not any(binds(node) for node in tree.body)


def _v1_setup(config_path, config_args, which="train"):
    """Build (model, opt, batched_reader, feeder) from an unmodified v1
    config: parse it ONCE, load the data-provider module for the
    requested source (train or test), annotate data-layer slot types
    from that provider's declaration, and wire the feeder by data-layer
    order (tuple samples) or name (dict samples)."""
    from paddle_tpu.compat.config_parser import (
        apply_data_types,
        parse_config,
    )
    from paddle_tpu.data.feeder import DataFeeder
    from paddle_tpu.data.reader import batched

    tc = parse_config(config_path, config_args)
    ds = tc.data_sources
    if ds is None or not getattr(ds, f"{which}_list"):
        raise SystemExit(
            f"{config_path} declares no {which} data source "
            "(define_py_data_sources2)"
        )
    reader_creator, types = (
        ds.train_reader() if which == "train" else ds.test_reader()
    )
    apply_data_types(tc.model, types)
    data_names = [
        lc.name for lc in tc.model.layers if lc.type == "data"
    ]
    # the config's inputs() declaration fixes provider-slot order
    order = [
        n for n in (tc.model.input_layer_names or data_names)
        if n in data_names
    ] or data_names
    if isinstance(types, dict):
        feeding = {n: n for n in types}
        type_map = dict(types)
    else:
        feeding = {n: i for i, n in enumerate(order)}
        type_map = dict(zip(order, types))
    feeder = DataFeeder(feeding, type_map)
    reader = batched(
        reader_creator, tc.opt.batch_size, drop_last=False
    )
    return tc.model, tc.opt, reader, feeder, tc.evaluators


def cmd_merge_model(args):
    from paddle_tpu.trainer import checkpoint as ckpt

    mod = _load_config(args.config)
    model_conf, _ = mod.get_config()
    params, _, state, _ = ckpt.load_pass(args.model_dir, args.pass_id)
    ckpt.merge_model(args.output, model_conf, params, state)
    print(f"merged {args.model_dir} (pass {args.pass_id}) -> {args.output}")
    return 0


def cmd_infer(args):
    import numpy as np

    from paddle_tpu.trainer.trainer import Inferencer

    inf = Inferencer.from_merged(args.model)
    print(f"outputs: {inf.output_names}")
    if args.example:
        # feed zero batches of the declared shapes as a smoke test
        from paddle_tpu.core.arg import Arg

        feed = {}
        T = 4  # smoke-test time steps for sequence inputs
        for lc in inf.net.conf.layers:
            if lc.type != "data":
                continue
            a = lc.attrs
            is_seq = a.get("is_seq", False)
            lead = (args.batch, T) if is_seq else (args.batch,)
            lens = (
                np.full(args.batch, T, np.int32) if is_seq else None
            )
            if a.get("is_ids"):
                feed[lc.name] = Arg(
                    ids=np.zeros(lead, np.int32), seq_lens=lens
                )
            else:
                feed[lc.name] = Arg(
                    value=np.zeros(lead + tuple(a["dim"]), np.float32),
                    seq_lens=lens,
                )
        outs = inf.infer(feed)
        for n, v in outs.items():
            print(f"{n}: shape {v.shape}")
    return 0


def cmd_master(args):
    from paddle_tpu.native.master import Master
    from paddle_tpu.native.recordio import count_chunks

    m = Master(lease_seconds=args.timeout, failure_max=args.failure_max)
    total = 0
    for path in args.chunks:
        n = count_chunks(path)
        m.add_chunk_tasks(path, n)
        total += n
    print(
        f"elastic master over {len(args.chunks)} files / {total} chunk "
        f"tasks; Ctrl-C to stop"
    )
    import time

    try:
        while True:
            time.sleep(30)
            if args.snapshot:
                m.snapshot(args.snapshot)
    except KeyboardInterrupt:
        if args.snapshot:
            m.snapshot(args.snapshot)
    return 0


def cmd_serve(args):
    """Run the continuous-batching inference server (serving/). The
    config file defines `get_server() -> serving.InferenceServer` with
    its models already registered; this command owns the TCP front end
    and the drain-on-shutdown lifecycle (SIGTERM/SIGINT -> stop
    admission, finish or cleanly reject in-flight work, exit 0)."""
    import json as _json
    import signal
    import time as _time

    from paddle_tpu.core import compile_cache
    from paddle_tpu.obs import flight_recorder as _flight
    from paddle_tpu.serving.tcp import ServingTCPServer

    # PADDLE_FLIGHT_DIR=<dir> arms the anomaly flight recorder
    # (breaker opens / shed spikes / SLO breaches dump bundles there)
    _flight.enable_from_env()
    compile_cache.enable()

    spec = importlib.util.spec_from_file_location("_serve_config",
                                                  args.config)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not hasattr(mod, "get_server"):
        raise SystemExit(
            f"{args.config} must define get_server() -> InferenceServer"
        )
    server = mod.get_server()
    # optional `load_model(name, tag) -> model` in the config enables
    # the {"admin": "swap_model"} frame (zero-downtime rollout)
    tcp = ServingTCPServer(server, port=args.port,
                           model_loader=getattr(mod, "load_model",
                                                None))
    print(f"LISTENING {tcp.port}", flush=True)

    stopping = []
    signal.signal(signal.SIGTERM, lambda *_: stopping.append(1))
    signal.signal(signal.SIGINT, lambda *_: stopping.append(1))
    try:
        while not stopping:
            _time.sleep(0.1)
    finally:
        # stop NEW connections first, drain with established clients
        # still attached (their in-flight responses must land), then
        # close what remains
        tcp.stop_accepting()
        server.shutdown(drain=True, timeout=args.drain_timeout)
        tcp.stop(drain=True)
        print("DRAINED " + _json.dumps(server.stats()), flush=True)
    return 0


def cmd_metrics(args):
    """One-shot telemetry dump (ISSUE 10). Without arguments, prints
    the CURRENT process's registry snapshot (text or --json) — mostly
    useful from code or a REPL. With --stream FILE, summarizes a JSONL
    event stream another process wrote (enable_event_stream /
    METRICS_FILE): event counts by kind, watchdog rungs, the last
    per-pass timeline record and the last trainer's `setup` record.
    Deliberately jax-free: inspecting telemetry must not initialize a
    device runtime."""
    from paddle_tpu.obs import metrics as om

    if args.spans:
        if not args.stream:
            raise SystemExit("--spans needs --stream FILE")
        return _metrics_spans(args)
    if args.stream:
        from paddle_tpu.testing_faults import read_metrics_records

        recs = read_metrics_records(args.stream)
        kinds = {}
        for r in recs:
            kinds[r.get("kind", "?")] = kinds.get(r.get("kind", "?"), 0) + 1
        wd = {}
        for r in recs:
            if r.get("kind") == "watchdog":
                wd[r["event"]] = wd.get(r["event"], 0) + 1
        timelines = [r for r in recs if r.get("kind") == "timeline"]
        setups = [r for r in recs if r.get("kind") == "setup"]
        summary = {
            "stream": args.stream,
            "events": len(recs),
            "by_kind": kinds,
            "watchdog_events": wd,
            "last_timeline": timelines[-1] if timelines else None,
            "last_setup": setups[-1] if setups else None,
        }
        if args.json:
            print(json.dumps(summary, indent=2))
        else:
            print(f"event stream {args.stream}: {len(recs)} events")
            for k, n in sorted(kinds.items()):
                print(f"  {k:20s} {n}")
            if wd:
                print("watchdog ladder:")
                for k, n in sorted(wd.items()):
                    print(f"  {k:20s} {n}")
            if timelines:
                t = timelines[-1]
                print(
                    "last timeline: pass %s step %s  "
                    "data_wait=%.1f%% h2d=%.1f%% host=%.1f%% "
                    "device=%.1f%% ckpt=%.1f%%" % (
                        t.get("pass_id"), t.get("global_step"),
                        100 * t.get("data_wait_frac", 0),
                        100 * t.get("h2d_frac", 0),
                        100 * t.get("host_overhead_frac", 0),
                        100 * t.get("device_frac", 0),
                        100 * t.get("checkpoint_stall_frac", 0),
                    )
                )
            if setups:
                u = setups[-1]
                before = u.get("start_to_build_s")
                print(
                    "last setup: before the build %s  build %.2f s  "
                    "first dispatch %.2f s (trace %.2f lower %.2f "
                    "compile or load %.2f; cache %d hits %d misses)" % (
                        "not known" if before is None
                        else "%.2f s" % before,
                        u.get("build_s", {}).get("all", 0),
                        u.get("first_dispatch_s", 0),
                        u.get("trace_s", 0), u.get("lower_s", 0),
                        u.get("backend_s", 0),
                        u.get("cache_hits", 0), u.get("cache_misses", 0),
                    )
                )
        return 0
    reg = om.get_registry()
    if args.json:
        print(json.dumps(reg.snapshot(), indent=2))
    else:
        print(reg.render_text())
    return 0


def _metrics_spans(args):
    """`metrics --stream FILE --spans` (ISSUE 11): per-span-name
    count/p50/p99 table plus the top-N slowest traces, computed from
    the span events on a JSONL stream. Jax-free like the rest of the
    metrics paths — span analytics must run on any box the stream was
    copied to."""
    from paddle_tpu.testing_faults import read_metrics_records

    spans = read_metrics_records(args.stream, kind="span")
    if not spans:
        print(f"event stream {args.stream}: no span events")
        return 0

    def pctl(sorted_vals, q):
        return sorted_vals[int(q * (len(sorted_vals) - 1))]

    by_name = {}
    for s in spans:
        by_name.setdefault(s.get("name", "?"), []).append(
            float(s.get("dur_s", 0.0))
        )
    rows = []
    for name, durs in by_name.items():
        durs.sort()
        rows.append({
            "name": name,
            "count": len(durs),
            "p50_ms": round(pctl(durs, 0.50) * 1e3, 3),
            "p99_ms": round(pctl(durs, 0.99) * 1e3, 3),
            "max_ms": round(durs[-1] * 1e3, 3),
        })
    rows.sort(key=lambda r: r["p99_ms"] * r["count"], reverse=True)

    # slowest traces: each trace scored by its root span (no parent
    # within the trace), falling back to its longest span. Root
    # semantics mirror tools/trace_view.py::_root_of — that file must
    # stay standalone-stdlib (copyable to any box without this
    # package), so the few lines are duplicated, not imported; change
    # both together.
    by_trace = {}
    for s in spans:
        by_trace.setdefault(s.get("trace_id", "?"), []).append(s)
    traces = []
    for tid, group in by_trace.items():
        ids = {g.get("span_id") for g in group}
        roots = [g for g in group
                 if g.get("parent_id", "") not in ids]
        root = max(roots or group,
                   key=lambda g: float(g.get("dur_s", 0.0)))
        traces.append({
            "trace_id": tid,
            "root": root.get("name"),
            "dur_ms": round(float(root.get("dur_s", 0.0)) * 1e3, 3),
            "spans": len(group),
            "status": root.get("status", "ok"),
        })
    traces.sort(key=lambda t: t["dur_ms"], reverse=True)
    traces = traces[: args.top]

    if args.json:
        print(json.dumps(
            {"stream": args.stream, "span_count": len(spans),
             "by_name": rows, "slowest_traces": traces}, indent=2,
        ))
        return 0
    print(f"event stream {args.stream}: {len(spans)} spans")
    print(f"{'span':28s} {'count':>7s} {'p50_ms':>10s} "
          f"{'p99_ms':>10s} {'max_ms':>10s}")
    for r in rows:
        print(f"{r['name']:28s} {r['count']:7d} {r['p50_ms']:10.3f} "
              f"{r['p99_ms']:10.3f} {r['max_ms']:10.3f}")
    print(f"top {len(traces)} slowest traces:")
    for t in traces:
        print(f"  {t['trace_id'][:16]:16s} {t['root'] or '?':24s} "
              f"{t['dur_ms']:10.3f} ms  {t['spans']:4d} spans  "
              f"{t['status']}")
    return 0


def cmd_fleetz(args):
    """Live fleet snapshot (ISSUE 17): scrape every replica's metricz
    twice, `--interval` apart, merge the snapshots into one fleet
    view (counters summed, histograms merged bucket-wise), and print
    a per-replica health table + fleet quantiles + active threshold
    breaches. Deliberately jax-free, like `metrics`: the operator box
    watching a fleet must not need a device runtime."""
    import time as _t

    from paddle_tpu.obs import aggregate as agg
    from paddle_tpu.serving.tcp import ServeClient

    replicas = {}
    for i, spec in enumerate(args.addr):
        if "=" in spec:
            name, _, a = spec.partition("=")
        else:
            name, a = f"r{i}", spec
        replicas[name] = a

    def scrape():
        snaps, stats, errors = {}, {}, {}
        for name, a in replicas.items():
            try:
                c = ServeClient(a, retries=0,
                                admin_timeout=args.timeout)
                resp = c.metricz()
                c.close()
                snaps[name] = resp.get("metricz", {})
                stats[name] = resp.get("stats", {})
            except Exception as e:
                errors[name] = f"{type(e).__name__}: {e}"
        return snaps, stats, errors

    t0 = _t.time()
    first, _, _ = scrape()
    _t.sleep(args.interval)
    second, stats, errors = scrape()
    dt = _t.time() - t0

    both = {n: s for n, s in second.items() if n in first}
    prev = agg.merge_snapshots({n: first[n] for n in both})
    cur = agg.merge_snapshots(second)
    delta = agg.snapshot_delta(prev, cur)
    rates = agg.counter_rates(delta, dt)

    family_sum = agg.family_total

    def merged_latency(histograms):
        """All serving.admitted_latency_s series (one per model)
        folded into one distribution."""
        return agg.family_histogram(histograms,
                                    "serving.admitted_latency_s")

    table = []
    for name in sorted(replicas):
        if name in errors:
            table.append({"replica": name, "up": False,
                          "error": errors[name]})
            continue
        st = stats.get(name, {}) or {}
        dsnap = agg.snapshot_delta(
            agg.merge_snapshots({name: first.get(name, {})}),
            agg.merge_snapshots({name: second.get(name, {})}),
        )
        admitted = family_sum(dsnap["counters"], "serving.admitted")
        shed = family_sum(dsnap["counters"], "serving.shed")
        total = admitted + shed
        lat = merged_latency(dsnap["histograms"])
        p99 = agg.quantile(lat, 0.99) if lat else None
        table.append({
            "replica": name,
            "up": True,
            "queue_depth": st.get("queue_depth"),
            "admitted": admitted,
            "shed": shed,
            "shed_frac": round(shed / total, 4) if total else 0.0,
            "p99_ms": round(p99 * 1e3, 3) if p99 is not None else None,
        })

    fleet_lat = merged_latency(delta["histograms"])
    fleet = {
        "replicas_up": sum(1 for r in table if r.get("up")),
        "replicas_down": sum(1 for r in table if not r.get("up")),
        "admitted_rate_rps": round(
            family_sum(rates, "serving.admitted"), 3),
        "shed_rate_rps": round(family_sum(rates, "serving.shed"), 3),
        "p50_ms": None,
        "p99_ms": None,
    }
    for q, key in ((0.50, "p50_ms"), (0.99, "p99_ms")):
        v = agg.quantile(fleet_lat, q) if fleet_lat else None
        fleet[key] = round(v * 1e3, 3) if v is not None else None

    alerts = []
    for r in table:
        if not r.get("up"):
            alerts.append({"alert": "replica_down",
                           "replica": r["replica"]})
            continue
        if args.slo_ms > 0 and r.get("p99_ms") is not None \
                and r["p99_ms"] > args.slo_ms:
            alerts.append({"alert": "p99_slo", "replica": r["replica"],
                           "p99_ms": r["p99_ms"],
                           "slo_ms": args.slo_ms})
        if r.get("shed_frac", 0.0) > args.shed_threshold:
            alerts.append({"alert": "shedding", "replica": r["replica"],
                           "shed_frac": r["shed_frac"]})

    if args.json:
        print(json.dumps({"interval_s": round(dt, 3),
                          "replicas": table, "fleet": fleet,
                          "alerts": alerts}, indent=2))
        return 1 if alerts else 0
    print(f"fleet of {len(replicas)} replicas "
          f"({fleet['replicas_up']} up), {dt:.1f}s window")
    print(f"{'replica':12s} {'state':6s} {'queue':>6s} {'adm':>8s} "
          f"{'shed':>8s} {'shed%':>7s} {'p99_ms':>9s}")
    for r in table:
        if not r.get("up"):
            print(f"{r['replica']:12s} {'DOWN':6s} {r['error']}")
            continue
        p99 = f"{r['p99_ms']:9.3f}" if r["p99_ms"] is not None \
            else f"{'-':>9s}"
        print(f"{r['replica']:12s} {'up':6s} "
              f"{str(r['queue_depth'] if r['queue_depth'] is not None else '-'):>6s} "
              f"{r['admitted']:8.0f} {r['shed']:8.0f} "
              f"{100 * r['shed_frac']:6.1f}% {p99}")
    print(f"fleet: {fleet['admitted_rate_rps']} rps admitted, "
          f"{fleet['shed_rate_rps']} rps shed, "
          f"p50={fleet['p50_ms']} ms p99={fleet['p99_ms']} ms "
          f"(merged buckets)")
    if alerts:
        print("active alerts:")
        for a in alerts:
            print("  " + json.dumps(a))
    else:
        print("no active alerts")
    return 1 if alerts else 0


def cmd_make_diagram(args):
    """Emit a graphviz .dot of the layer graph (the reference's
    `paddle make_diagram`, scripts/submit_local.sh.in:3-13)."""
    from paddle_tpu.plot import make_diagram

    if _is_v1_config(args.config):
        # an unmodified v1 config file (settings()/outputs() style)
        from paddle_tpu.compat.config_parser import parse_config

        model_conf = parse_config(args.config, args.config_args).model
    else:
        mod = _load_config(args.config)
        model_conf, _ = mod.get_config()
    dot = make_diagram(model_conf, title=args.config)
    if args.output:
        with open(args.output, "w") as f:
            f.write(dot)
    else:
        print(dot, end="")
    return 0


def _cmd_launch(args):
    from paddle_tpu import launch as _launch

    cmd = args.command
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        raise SystemExit("launch: give the worker command after --")
    args.command = cmd
    return _launch.main(args)


def main(argv=None):
    p = argparse.ArgumentParser(prog="paddle", description=__doc__)
    sub = p.add_subparsers(dest="cmd", required=True)

    sp = sub.add_parser("train", help="train a config")
    sp.add_argument("--config", required=True)
    sp.add_argument("--config_args", default="",
                    help="v1 config interpolation, e.g. batch_size=64")
    sp.add_argument("--job", choices=["train", "time", "test"],
                    default="train",
                    help="time = ms/batch harness (TrainerBenchmark"
                         ".cpp); test = evaluation pass (Tester.h)")
    sp.add_argument("--time_batches", type=int, default=10)
    sp.add_argument("--pass_id", type=int, default=-1,
                    help="with --job=test --save_dir: checkpoint pass")
    sp.add_argument("--num_passes", type=int, default=1)
    sp.add_argument("--save_dir", default="")
    sp.add_argument("--log_period", type=int, default=10)
    sp.add_argument("--from_scratch", action="store_true",
                    help="ignore existing checkpoints in --save_dir "
                         "instead of auto-resuming")
    sp.set_defaults(fn=cmd_train)

    sp = sub.add_parser("dump_config", help="print config as JSON")
    sp.add_argument("--config", required=True)
    sp.add_argument("--config_args", default="")
    sp.add_argument("--output", default="")
    sp.set_defaults(fn=cmd_dump_config)

    sp = sub.add_parser("merge_model", help="pack config+weights")
    sp.add_argument("--config", required=True)
    sp.add_argument("--model_dir", required=True)
    sp.add_argument("--pass_id", type=int, default=-1)
    sp.add_argument("--output", required=True)
    sp.set_defaults(fn=cmd_merge_model)

    sp = sub.add_parser("infer", help="load a merged model")
    sp.add_argument("--model", required=True)
    sp.add_argument("--example", action="store_true",
                    help="run a zero-batch smoke forward")
    sp.add_argument("--batch", type=int, default=1)
    sp.set_defaults(fn=cmd_infer)

    sp = sub.add_parser("master", help="run the elastic input master")
    sp.add_argument("chunks", nargs="+")
    sp.add_argument("--timeout", type=float, default=60.0)
    sp.add_argument("--failure_max", type=int, default=3)
    sp.add_argument("--snapshot", default="")
    sp.set_defaults(fn=cmd_master)

    sp = sub.add_parser(
        "serve",
        help="run the continuous-batching inference server "
             "(bounded queue, load shedding, deadlines, drain)",
    )
    sp.add_argument("--config", required=True,
                    help="python file defining get_server()")
    sp.add_argument("--port", type=int, default=0,
                    help="TCP port (0 = ephemeral, printed as "
                         "LISTENING <port>)")
    sp.add_argument("--drain_timeout", type=float, default=30.0)
    sp.set_defaults(fn=cmd_serve)

    sp = sub.add_parser(
        "metrics",
        help="one-shot telemetry snapshot (process registry, or "
             "--stream FILE to summarize a JSONL event stream)",
    )
    sp.add_argument("--json", action="store_true",
                    help="JSON instead of text")
    sp.add_argument("--stream", default="",
                    help="summarize this JSONL event-stream file "
                         "instead of the in-process registry")
    sp.add_argument("--spans", action="store_true",
                    help="with --stream: per-span-name count/p50/p99 "
                         "table and the top-N slowest traces")
    sp.add_argument("--top", type=int, default=10,
                    help="with --spans: slowest traces to list")
    sp.set_defaults(fn=cmd_metrics)

    sp = sub.add_parser(
        "fleetz",
        help="live fleet snapshot: scrape replicas' metricz, merge "
             "into one fleet view (per-replica health table, fleet "
             "p50/p99 from merged buckets, active alerts)",
    )
    sp.add_argument("--addr", action="append", required=True,
                    help="replica address, repeatable: host:port or "
                         "name=host:port")
    sp.add_argument("--interval", type=float, default=1.0,
                    help="seconds between the two scrapes the "
                         "delta/rate view is computed over")
    sp.add_argument("--timeout", type=float, default=2.0,
                    help="per-replica scrape timeout")
    sp.add_argument("--slo-ms", type=float, default=0.0,
                    dest="slo_ms",
                    help="admitted-p99 SLO in ms (0 = no p99 alert)")
    sp.add_argument("--shed-threshold", type=float, default=0.5,
                    dest="shed_threshold",
                    help="per-replica shed-fraction alert threshold")
    sp.add_argument("--json", action="store_true")
    sp.set_defaults(fn=cmd_fleetz)

    sp = sub.add_parser("make_diagram", help="emit graphviz dot of a config")
    sp.add_argument("--config", required=True)
    sp.add_argument("--config_args", default="")
    sp.add_argument("--output", default="")
    sp.set_defaults(fn=cmd_make_diagram)

    sp = sub.add_parser(
        "launch",
        help="start a multi-host job (the cluster_train/paddle.py "
             "ssh launcher, TPU-shaped: one jax.distributed process "
             "per host)",
    )
    sp.add_argument("--hosts", required=True,
                    help="comma-separated host list; first runs the "
                         "coordinator. localhost spawns locally")
    sp.add_argument("--nproc-per-host", type=int, default=1)
    sp.add_argument("--port", type=int, default=7164,
                    help="coordinator port on the first host")
    sp.add_argument("--ssh-opts", default="",
                    help="extra ssh options, e.g. '-i key.pem'")
    sp.add_argument("--max-respawns", type=int, default=3,
                    dest="max_respawns",
                    help="per-rank restarts after a preemption exit "
                         "(code 75) before it counts as a failure")
    sp.add_argument("command", nargs=argparse.REMAINDER,
                    help="the per-process command (after --), e.g. "
                         "python -m paddle_tpu train --config cfg.py")
    sp.set_defaults(fn=_cmd_launch)

    sp = sub.add_parser("version", help="print versions")
    sp.set_defaults(fn=cmd_version)

    args = p.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
