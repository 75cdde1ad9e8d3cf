"""Long-context attention capture tool (ISSUE 12 / PERF.md round 8):
compile the longctx train step (`longctx_conf` below) with
`attn_impl` dense AND flash, and write per-arm captures next to the
committed traces:

  tools/traces/longctx_t{T}_{impl}.hlo.txt.gz   compiled HLO module
  tools/traces/longctx_t{T}_{impl}.report.json  shape + XLA cost
                                                analysis (flops,
                                                bytes accessed) +
                                                optional measured ms

`tools/trace_attribution.py CAPTURE.hlo.txt.gz` then produces the
committed `*.attrib.json` byte attribution whose `attention` category
proves the flash byte removal on the real compiled program — the
no-TPU-needed half of the proof. On a TPU host, add `--trace-dir` to
also capture an XPlane profile of the same step (the time half), and
`--run` to measure step wall time on whatever backend this runs on.

Compilation allocates no tensors, so the dense arm compiles at the
full shape (B=4, T=4096) even on a laptop; `--run` at that
shape needs the memory for the real [B,H,T,T] scores — that being
prohibitive is the point.

Usage: python tools/profile_longctx.py [--t 4096] [--bs 4]
       [--impls dense,flash] [--out-dir tools/traces] [--run]
       [--trace-dir DIR]
"""

import argparse
import gzip
import json
import os
import sys
import time

import numpy as np

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def longctx_conf(t, d=512, heads=8, layers=2, classes=512,
                 attn_impl="dense", seq_parallel="none",
                 vocab=32000):
    """The long-context self-attention model every longctx row (single
    chip AND the T>=32k ring/Ulysses multichip rows) measures:
    embedding -> N causal MHA blocks with residual fc -> per-token
    classification. One builder so the A/B arms differ ONLY in
    attn_impl / seq_parallel."""
    from paddle_tpu import dsl

    with dsl.model() as m:
        ids = dsl.data("ids", dim=(), is_ids=True, is_seq=True)
        lbl = dsl.data("label", dim=(), is_ids=True, is_seq=True)
        x = dsl.embedding(ids, size=d, vocab_size=vocab)
        for _ in range(layers):
            att = dsl._add(
                "multi_head_attention", [x], size=d,
                num_heads=heads, causal=True,
                seq_parallel=seq_parallel, attn_impl=attn_impl,
            )
            x = dsl.addto(att, dsl.fc(att, size=d, act="relu"))
        out = dsl.fc(x, size=classes, act="")
        dsl.classification_cost(out, lbl)
    return m.conf


def longctx_feed(bs, t, classes=512, vocab=32000, seed=0):
    from paddle_tpu.core.arg import id_arg

    rng = np.random.default_rng(seed)
    lens = np.full((bs,), t, np.int32)
    return {
        "ids": id_arg(
            rng.integers(0, vocab, (bs, t)).astype(np.int32), lens
        ),
        "label": id_arg(
            rng.integers(0, classes, (bs, t)).astype(np.int32), lens
        ),
    }



def build_step(conf, feed, seed=0):
    """jitted fwd+bwd+update-free grad step (the byte-dominant part of
    the train step; optimizer elementwise adds O(params) bytes
    identically to both arms)."""
    import jax

    from paddle_tpu.network import Network

    net = Network(conf)
    params = net.init_params(jax.random.key(seed))
    state = net.init_state()
    key = jax.random.key(1)

    def loss(p, f):
        return net.loss_fn(p, f, state=state, rng=key, train=True)[0]

    gf = jax.jit(lambda p, f: jax.grad(loss)(p, f))
    return gf, params


def build_update_step(conf, feed, seed=0):
    """jitted fwd+bwd+SGD-update step with DONATED param/opt buffers —
    the capture the donation audit (analysis/hlo_audit.py, ISSUE 13)
    runs on: every donated buffer must appear in the compiled
    module's input_output_alias map, else the step keeps params live
    twice and HBM footprint silently doubles. Returns
    (jitted_fn, params, opt_state, donated_buffer_count)."""
    import jax

    from paddle_tpu.core.config import OptimizationConf
    from paddle_tpu.network import Network
    from paddle_tpu.optimizers import create_optimizer

    net = Network(conf)
    params = net.init_params(jax.random.key(seed))
    state = net.init_state()
    opt = create_optimizer(
        OptimizationConf(learning_method="momentum",
                         learning_rate=0.01, momentum=0.9),
        net.param_confs,
    )
    opt_state = opt.init_state(params)
    key = jax.random.key(1)

    def update(p, ost, f):
        def loss(p, f):
            return net.loss_fn(
                p, f, state=state, rng=key, train=True
            )[0]

        grads = jax.grad(loss)(p, f)
        return opt.update(grads, p, ost, 0)

    uf = jax.jit(update, donate_argnums=(0, 1))
    donated = len(jax.tree_util.tree_leaves(params)) + len(
        jax.tree_util.tree_leaves(opt_state)
    )
    return uf, params, opt_state, donated


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--t", type=int, default=4096)
    ap.add_argument("--bs", type=int, default=4)
    ap.add_argument("--d", type=int, default=512)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--classes", type=int, default=512)
    ap.add_argument("--impls", default="dense,flash")
    ap.add_argument("--update-step", action="store_true",
                    help="capture the full train-update step with "
                         "DONATED param/opt buffers (writes "
                         "longctx_t{T}_{impl}_train.* — the donation"
                         "-audit capture, ISSUE 13)")
    ap.add_argument("--out-dir", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "traces"))
    ap.add_argument("--run", action="store_true",
                    help="also execute + time 3 steps per arm")
    ap.add_argument("--trace-dir", default="",
                    help="XPlane profiler capture dir (TPU hosts)")
    args = ap.parse_args()

    import jax

    from paddle_tpu.core import flags as _flags

    _flags.set_flag("matmul_precision", "bfloat16")
    jax.config.update("jax_default_prng_impl", "rbg")

    from paddle_tpu.parallel.ring import attention_hbm_bytes

    os.makedirs(args.out_dir, exist_ok=True)
    feed = longctx_feed(args.bs, args.t, args.classes)
    for impl in args.impls.split(","):
        conf = longctx_conf(
            args.t, args.d, args.heads, args.layers, args.classes,
            attn_impl=impl,
        )
        if args.update_step:
            uf, params, opt_state, donated = build_update_step(
                conf, feed
            )
            compiled = uf.lower(params, opt_state, feed).compile()
            stem = os.path.join(
                args.out_dir, f"longctx_t{args.t}_{impl}_train"
            )
            with gzip.open(stem + ".hlo.txt.gz", "wt") as f:
                f.write(compiled.as_text())
            report = {
                "model": "longctx_conf full update step "
                         "(donated params+opt buffers)",
                "attn_impl": impl,
                "batch_size": args.bs,
                "seq_len": args.t,
                "d_model": args.d,
                "heads": args.heads,
                "layers": args.layers,
                "backend": jax.default_backend(),
                # the donation audit's contract: at least this many
                # input buffers must appear in input_output_alias
                "donated_arg_buffers": donated,
            }
            with open(stem + ".report.json", "w") as f:
                json.dump(report, f, indent=2)
                f.write("\n")
            print(json.dumps({"impl": impl, **report}))
            continue
        gf, params = build_step(conf, feed)
        compiled = gf.lower(params, feed).compile()
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, list) else ca
        try:
            temp_bytes = compiled.memory_analysis().temp_size_in_bytes
        except Exception:
            temp_bytes = None  # not every backend reports it
        stem = os.path.join(
            args.out_dir, f"longctx_t{args.t}_{impl}"
        )
        with gzip.open(stem + ".hlo.txt.gz", "wt") as f:
            f.write(compiled.as_text())
        hd = args.d // args.heads
        report = {
            "model": "longctx_conf grad step",
            "attn_impl": impl,
            "batch_size": args.bs,
            "seq_len": args.t,
            "d_model": args.d,
            "heads": args.heads,
            "layers": args.layers,
            "backend": jax.default_backend(),
            "xla_flops": ca.get("flops", 0),
            "xla_bytes_accessed": ca.get("bytes accessed", 0),
            # peak temp memory: the reason dense T>=32k cannot exist
            # on one chip at all (the [B,H,T,T] scores), independent
            # of bandwidth
            "hbm_temp_bytes": temp_bytes,
            "analytic_attn_hbm_bytes": args.layers
            * attention_hbm_bytes(
                args.bs, args.t, args.t, args.heads, hd, impl
            ),
        }
        if args.run:
            import jax.numpy as jnp

            dfeed = jax.device_put(feed)
            r = gf(params, dfeed)
            float(jax.tree_util.tree_leaves(r)[0].ravel()[0])
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                r = gf(params, dfeed)
                float(jax.tree_util.tree_leaves(r)[0].ravel()[0])
                best = min(best, time.perf_counter() - t0)
            report["fwd_bwd_ms"] = round(best * 1e3, 2)
            report["tokens_per_s"] = round(
                args.bs * args.t / best, 0
            )
        with open(stem + ".report.json", "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
        print(json.dumps({"impl": impl, **report}))
        if args.trace_dir:
            from paddle_tpu.core import profiler

            tdir = os.path.join(args.trace_dir, impl)
            dfeed = jax.device_put(feed)
            with profiler.trace(tdir):
                for _ in range(3):
                    r = gf(params, dfeed)
                float(jax.tree_util.tree_leaves(r)[0].ravel()[0])
            print(f"trace written to {tdir}")


if __name__ == "__main__":
    main()
