"""Read, on the chip and at a cell's own size, the readings its limits are
set from, each against the float32 reference on the same seed and each put
through `compare.judge` with the cell's own limits, so that the line says
`correct` as a run would: the control (the reference in the program's place,
computed in fp8, and in bf16 for diagnosis), the planted fault
(`half_batch`), and with `--program 1` the program itself (its first steps
as `kinds/train.setup` drives them, the trainer let go before the reference
starts), with every number `compare.training_numbers` gives and not only
those a limit names. For a cell whose float32 reference fills the chip, so
that `tools/readings.py`, which keeps the program's trainer alive beside it,
cannot hold both.

    python benchmarks/tools/control_readings.py --workload <cell> \\
        --seeds 1,2 [--program 1] [--modes fp8,bf16] [--faults half_batch] \\
        [--flips bf16]

`--flips <mode>`: for a reference that has `chosen` (a routed model), the
share of (layer, token) pairs whose set of experts differs between float32
and `<mode>` activations on the first batch, and of single selections.

One JSON line a seed; nothing here is part of a benchmark run.
"""

from __future__ import annotations

import argparse
import gc
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import compare, harness, run as R, traffic  # noqa: E402
from benchmarks.kinds import train as T  # noqa: E402


def flips(cell, seed, pool, mode) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import train as ref_train

    ref = cell.model.reference
    params = ref_train.init_params(ref.param_spec(cell.config), seed)
    cols = cell.model.reference_batch(pool.arrays(next(pool.batches(0))))
    ids = jnp.asarray(cols["ids"])
    pick = jax.jit(lambda p, m: ref.chosen(cell.config, p, ids, m),
                   static_argnums=1)
    a, b = pick(params, "f32"), pick(params, mode)
    same = a == b
    return {"token_layer_sets_flipped": float(1 - jnp.mean(jnp.all(same, -1))),
            "selections_flipped": float(1 - jnp.mean(same)),
            "by_layer": [float(1 - jnp.mean(jnp.all(s, -1))) for s in same]}


def program_readings(cell, seed, devices, clock) -> dict:
    """The program's first steps, as a run's set-up takes them."""
    import jax

    trainer, _, _, prog, _ = T.setup(cell, seed, devices, clock,
                                     harness.Spans())
    del trainer
    gc.collect()
    jax.clear_caches()
    return prog


def judged(cell, numbers) -> dict:
    """The numbers, and what the cell's limits make of them."""
    ok, rows = compare.judge(numbers, cell.limits)
    over = [name for name, value, limit in rows
            if not (value is not None and abs(value) <= limit)]
    return dict(numbers, correct=ok, over=over)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--modes", default="fp8")
    ap.add_argument("--faults", default="half_batch")
    ap.add_argument("--flips", default="")
    ap.add_argument("--program", type=int, default=0)
    args = ap.parse_args()

    cell = harness.Cell(args.workload)
    devices = harness.require_chips(cell.chips)
    clock = harness.CompileClock()
    R.enable_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        pool = traffic.Pool(cell.traffic, seed)
        prog = (program_readings(cell, seed, devices, clock)
                if args.program else None)
        gc.collect()
        ref = T.reference_readings(cell, seed, pool)
        out = {"seed": seed, "reference_loss": ref["loss"],
               "reference_s": time.perf_counter() - t0}
        if prog:
            out["program"] = judged(cell, compare.training_numbers(prog, ref))
        for mode in filter(None, args.modes.split(",")):
            gc.collect()
            low = T.reference_readings(cell, seed, pool, mode=mode)
            out[f"control_{mode}"] = judged(
                cell, compare.training_numbers(low, ref))
            out[f"control_{mode}_loss"] = low["loss"]
        for fault in filter(None, args.faults.split(",")):
            gc.collect()
            out[f"fault_{fault}"] = judged(cell, compare.training_numbers(
                T.reference_readings(cell, seed, pool, fault=fault), ref))
        if args.flips:
            out["flips_" + args.flips] = flips(cell, seed, pool, args.flips)
        out["s"] = time.perf_counter() - t0
        harness.say(**out)


if __name__ == "__main__":
    main()
