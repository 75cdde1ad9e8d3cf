"""Multi-chip HLO capture tool (ISSUE 15): compile the sharded
programs and write per-row captures next to the committed traces:

  tools/traces/<row>.hlo.txt.gz     compiled partitioned HLO module
  tools/traces/<row>.report.json    mesh/shape context + the parsed
                                    collective byte table

Rows (all compile-only — no tensor is ever materialized, so the full
T=32768 ring/ulysses programs capture fine on a laptop):

  mc_longctx_ring_t32768     ring-sharded flash train grad step
  mc_longctx_ulysses_t32768  ulysses (all-to-all) flash train step
  mc_dp_train                data-parallel train step (grad allreduce)
  mc_sparse_lookup           row-sharded embedding gather + psum
  mc_sparse_update           its backward: the row-sparse scatter
  mc_sparse_shard_step       elastic hot-cache tier: fused sparse
                             lookup+update step over per-shard caches

The committed captures are what `tools/framework_lint.py spmd-audit`
(analysis/spmd_audit.py) audits against tools/traces/
audit_budgets.json: replication floor, collective byte budgets,
schedule safety. After an INTENTIONAL sharding/perf change, re-run
this tool, re-baseline the budgets by hand, and refresh the committed
*.audit.json with `framework_lint.py spmd-audit --write-audit`.

Usage: python tools/profile_multichip.py [--rows a,b,...]
       [--devices 8] [--t 32768] [--out-dir tools/traces]
       [--synthetic]   # scaled-down shapes (CI smoke; not committed)
"""

import argparse
import gzip
import json
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

ROWS = (
    "mc_longctx_ring_t32768",
    "mc_longctx_ulysses_t32768",
    "mc_dp_train",
    "mc_sparse_lookup",
    "mc_sparse_update",
    "mc_sparse_shard_step",
)


def _ensure_cpu_mesh(n: int) -> None:
    """Force an n-virtual-device CPU backend BEFORE jax initializes
    (this tool owns its process from main())."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()


def _write(out_dir, row, text, report):
    from paddle_tpu.analysis import hlo_text as _hlo

    lines = text.splitlines()
    stem = os.path.join(out_dir, row)
    with gzip.open(stem + ".hlo.txt.gz", "wt") as f:
        f.write(text)
    report = {
        **report,
        "num_partitions": _hlo.num_partitions(text),
        # the parsed collective byte table — the baseline the
        # collective byte budgets in audit_budgets.json pin (+~10%)
        "collectives": _hlo.collective_summary(
            _hlo.parse_collectives(lines)
        ),
    }
    with open(stem + ".report.json", "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(json.dumps({"row": row, **report}))


def capture_longctx(mode, t, n_dev, out_dir, synthetic):
    """The mc_longctx ring/ulysses rows: profile_longctx.py's
    longctx_conf with seq_parallel=mode, time dim sharded over the
    mesh `seq` axis, fwd+bwd grad step."""
    import jax

    from profile_longctx import longctx_conf, longctx_feed
    from paddle_tpu.core.config import OptimizationConf
    from paddle_tpu.core.mesh import (
        DATA_AXIS, SEQ_AXIS, make_mesh, set_mesh,
    )
    from paddle_tpu.network import Network
    from paddle_tpu.optimizers import create_optimizer
    from paddle_tpu.parallel.dp import TrainStep

    bs = 1
    if synthetic:
        t_run, d, heads, layers, classes = 32 * n_dev, 64, n_dev, 1, 64
    else:
        t_run, d, heads, layers, classes = t, 512, 8, 2, 512
    conf = longctx_conf(
        t_run, d, heads, layers, classes,
        attn_impl="flash", seq_parallel=mode,
    )
    feed = longctx_feed(bs, t_run, classes)
    mesh = make_mesh({DATA_AXIS: 1, SEQ_AXIS: n_dev})
    set_mesh(mesh)  # the ring/ulysses layers resolve it via get_mesh
    try:
        net = Network(conf)
        params = net.init_params(jax.random.key(0))
        opt = create_optimizer(
            OptimizationConf(learning_method="adam",
                             learning_rate=1e-3),
            net.param_confs,
        )
        step = TrainStep(net, opt, mesh=mesh, donate=False)
        params, opt_state, state = step.place(
            params, opt.init_state(params), net.init_state()
        )
        # aot() compiles without executing — the T=32768 program is
        # captured, never run
        _run, text = step.aot(
            params, opt_state, state, feed, 0, jax.random.key(1)
        )
    finally:
        set_mesh(make_mesh())
    row = f"mc_longctx_{mode}_t{t_run}"
    _write(out_dir, row, text, {
        "model": "longctx_conf full train step "
                 "(the mc_longctx rows)",
        "seq_parallel": mode,
        "attn_impl": "flash",
        "batch_size": bs,
        "seq_len": t_run,
        "d_model": d,
        "heads": heads,
        "layers": layers,
        "mesh": {"data": 1, "seq": n_dev},
        "backend": jax.default_backend(),
        "synthetic": synthetic,
    })


def capture_dp_train(n_dev, out_dir, synthetic):
    """The data-parallel train step: batch sharded over `data`, params
    replicated BY DESIGN (so no replication floor in its policy) —
    the captured invariant is the gradient all-reduce."""
    import numpy as np

    import jax

    from paddle_tpu.core.arg import id_arg
    from paddle_tpu.core.config import OptimizationConf
    from paddle_tpu.core.mesh import DATA_AXIS, make_mesh
    from paddle_tpu.dsl import (
        classification_cost, data, embedding, fc, model, seq_pool,
    )
    from paddle_tpu.network import Network
    from paddle_tpu.optimizers import create_optimizer
    from paddle_tpu.parallel.dp import TrainStep

    D, T, CLS = (16, 8, 4) if synthetic else (128, 32, 64)
    V = 64 if synthetic else 8192
    with model() as m:
        ids = data("ids", dim=(), is_ids=True, is_seq=True)
        lbl = data("label", dim=(), is_ids=True)
        emb = embedding(ids, size=D, vocab_size=V)
        pooled = seq_pool(emb, pool_type="average")
        h = fc(pooled, size=2 * D, act="relu")
        out = fc(h, size=CLS, act="softmax")
        classification_cost(out, lbl)
    net = Network(m.conf)
    mesh = make_mesh({DATA_AXIS: n_dev})
    params = net.init_params(jax.random.key(0))
    opt = create_optimizer(
        OptimizationConf(learning_method="momentum",
                         learning_rate=0.01, momentum=0.9),
        net.param_confs,
    )
    step = TrainStep(net, opt, mesh=mesh, donate=False)
    params, opt_state, state = step.place(
        params, opt.init_state(params), net.init_state()
    )
    b = 8 * n_dev
    feed = {
        "ids": id_arg(
            np.zeros((b, T), np.int32),
            seq_lens=np.full((b,), T, np.int32),
        ),
        "label": id_arg(np.zeros((b,), np.int32)),
    }
    _run, text = step.aot(
        params, opt_state, state, feed, 0, jax.random.key(1)
    )
    _write(out_dir, "mc_dp_train", text, {
        "model": "embedding+fc classifier, dp train step "
                 "(grad allreduce witness)",
        "batch_size": b,
        "vocab": V,
        "d_model": D,
        "mesh": {"data": n_dev},
        "backend": jax.default_backend(),
        "synthetic": synthetic,
    })


def _sparse_setup(n_dev, synthetic):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.core.mesh import MODEL_AXIS, make_mesh

    V, D, N = (64 * n_dev, 16, 32) if synthetic else (65536, 64, 4096)
    mesh = make_mesh({MODEL_AXIS: n_dev})
    table = jax.device_put(
        jnp.zeros((V, D), jnp.float32),
        NamedSharding(mesh, P(MODEL_AXIS, None)),
    )
    ids = jax.device_put(
        jnp.zeros((N,), jnp.int32), NamedSharding(mesh, P())
    )
    return mesh, table, ids, V, D, N


def capture_sparse_lookup(n_dev, out_dir, synthetic):
    """Row-sharded embedding gather: every shard takes its own rows,
    one psum combines partials. The audit pins the table SHARDED
    (replication floor below the table bytes) and forbids the
    all-gather repartition that would pull the whole table onto every
    chip."""
    import jax

    from paddle_tpu.parallel.sparse import embedding_lookup

    mesh, table, ids, V, D, N = _sparse_setup(n_dev, synthetic)
    text = jax.jit(
        lambda tbl, i: embedding_lookup(tbl, i, mesh)
    ).lower(table, ids).compile().as_text()
    _write(out_dir, "mc_sparse_lookup", text, {
        "model": "parallel/sparse.py embedding_lookup "
                 "(row-sharded table, psum combine)",
        "vocab": V, "dim": D, "ids": N,
        "mesh": {"model": n_dev},
        "backend": jax.default_backend(),
        "synthetic": synthetic,
    })


def capture_sparse_update(n_dev, out_dir, synthetic):
    """The lookup's backward: the row-sparse scatter-add into the
    sharded table. The cotangent arrives replicated, each shard
    scatters only its own rows — NO collective should touch the [V,D]
    table, and its gradient must stay sharded."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel.sparse import embedding_lookup

    mesh, table, ids, V, D, N = _sparse_setup(n_dev, synthetic)
    y = jax.device_put(jnp.ones((N, D), jnp.float32))

    def loss(tbl, i, y):
        return jnp.sum(embedding_lookup(tbl, i, mesh) * y)

    text = jax.jit(
        jax.grad(loss)
    ).lower(table, ids, y).compile().as_text()
    _write(out_dir, "mc_sparse_update", text, {
        "model": "embedding_lookup backward: row-sparse scatter into "
                 "the sharded table",
        "vocab": V, "dim": D, "ids": N,
        "mesh": {"model": n_dev},
        "backend": jax.default_backend(),
        "synthetic": synthetic,
    })


def capture_sparse_shard_step(n_dev, out_dir, synthetic):
    """The elastic sparse-CTR tier (ISSUE 20): one fused
    lookup+update step over the per-shard HOT caches of a logically
    2**30-row table (sparse_shard.step_program). The program's shapes
    are (hot-cache, batch) ONLY — rows_total never reaches the
    device, so this capture at 2**30 is byte-identical to one at
    2**20: the audit-visible V-independence claim. Policy: one psum
    (all-reduce) combines lookup partials; the update is a LOCAL
    masked delta scatter — any all-gather here means the hot caches
    were repartitioned onto every chip, which is exactly the failure
    the tier exists to avoid."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.core.mesh import MODEL_AXIS, make_mesh
    from paddle_tpu.parallel import sparse_shard as ss

    if synthetic:
        C, D, N = 64, 8, 32
    else:
        C, D, N = 131072, 64, 4096
    k, n_state = N, 1
    rows_total = 1 << 30  # documentation only: NOT a program shape
    mesh = make_mesh({MODEL_AXIS: n_dev})
    S = n_dev * C
    sharded = NamedSharding(mesh, P(MODEL_AXIS, None))
    repl = NamedSharding(mesh, P())
    cache = jax.device_put(jnp.zeros((S, D), jnp.float32), sharded)
    state = (jax.device_put(jnp.zeros((S, D), jnp.float32),
                            sharded),)
    slots = jax.device_put(jnp.zeros((N,), jnp.int32), repl)
    uslots = jax.device_put(jnp.zeros((k,), jnp.int32), repl)
    inv = jax.device_put(jnp.zeros((N,), jnp.int32), repl)
    grads = jax.device_put(jnp.zeros((N, D), jnp.float32), repl)
    prog = ss.step_program(
        mesh, MODEL_AXIS, S, D, N, k, n_state, "float32",
        ss.adagrad_row_update(0.01),
    )
    text = prog.lower(cache, state, slots, uslots, inv,
                      grads).compile().as_text()
    _write(out_dir, "mc_sparse_shard_step", text, {
        "model": "parallel/sparse_shard.py step_program (fused "
                 "lookup psum + local adagrad delta scatter over "
                 "per-shard hot caches)",
        "rows_total": rows_total,
        "hot_capacity_per_shard": C, "dim": D, "ids": N,
        "num_slots": k, "optimizer": "adagrad(1 slot)",
        "mesh": {"model": n_dev},
        "backend": jax.default_backend(),
        "synthetic": synthetic,
    })


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default=",".join(ROWS))
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--t", type=int, default=32768)
    ap.add_argument("--out-dir", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "traces"))
    ap.add_argument("--synthetic", action="store_true",
                    help="scaled-down shapes (smoke/tests; NOT for "
                         "the committed captures)")
    args = ap.parse_args(argv)

    _ensure_cpu_mesh(args.devices)
    os.makedirs(args.out_dir, exist_ok=True)

    rows = [r.strip() for r in args.rows.split(",") if r.strip()]
    unknown = [r for r in rows if r not in ROWS]
    if unknown:
        raise SystemExit(
            f"unknown row(s) {unknown}; registered: {list(ROWS)}"
        )
    for row in rows:
        if row == "mc_longctx_ring_t32768":
            capture_longctx("ring", args.t, args.devices,
                            args.out_dir, args.synthetic)
        elif row == "mc_longctx_ulysses_t32768":
            capture_longctx("ulysses", args.t, args.devices,
                            args.out_dir, args.synthetic)
        elif row == "mc_dp_train":
            capture_dp_train(args.devices, args.out_dir,
                             args.synthetic)
        elif row == "mc_sparse_lookup":
            capture_sparse_lookup(args.devices, args.out_dir,
                                  args.synthetic)
        elif row == "mc_sparse_update":
            capture_sparse_update(args.devices, args.out_dir,
                                  args.synthetic)
        elif row == "mc_sparse_shard_step":
            capture_sparse_shard_step(args.devices, args.out_dir,
                                      args.synthetic)


if __name__ == "__main__":
    main()
