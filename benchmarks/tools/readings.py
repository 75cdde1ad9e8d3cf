"""Read, on the chip and at a cell's own size, the numbers its limits are
set from: the program's over a dozen seeds (the lower reading), the
control's (the reference in the program's place, computed in fp8) and each
planted fault's (the upper). One process, so that programs compile once.

    python benchmarks/tools/readings.py --workload <cell> --seeds 1,2,3 \\
        [--control-seeds 3] [--bf16 1]

One JSON line a seed; nothing here is part of a benchmark run.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import compare, harness, run as R  # noqa: E402


_numbers = compare.training_numbers


def train(cell, seeds, n_control, bf16, devices):
    from benchmarks import traffic
    from benchmarks.kinds import train as T
    from benchmarks.reference import train as ref_train

    clock, spans = harness.CompileClock(), harness.Spans()
    trainer = None
    spec = cell.model.reference.param_spec(cell.config)
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        if trainer is None:
            trainer, pool, feeder, prog, _ = T.setup(cell, seed, devices,
                                                     clock, spans)
        else:       # the same compiled step, a fresh state from this seed
            pool = traffic.Pool(cell.traffic, seed)
            p = ref_train.init_params(spec, seed)
            trainer.params, trainer.opt_state, trainer.state = (
                trainer.step_fn.place(p, trainer.opt.init_state(p),
                                      trainer.net.init_state()))
            trainer.global_step = 0
            loop = T.Loop(pool, feeder, spans, cell.traffic["count"], 0,
                          stop=lambda lp: len(lp.rows) >= T.WARM_STEPS)
            prog = T._program_readings(
                trainer, cell.config["optimizer"],
                lambda: ref_train.init_params(spec, seed), loop)
        ref = T.reference_readings(cell, seed, pool)
        out = {"seed": seed, "program": _numbers(prog, ref),
               "loss": [prog["loss"], ref["loss"]]}
        if i < n_control:
            out["control_fp8"] = _numbers(
                T.reference_readings(cell, seed, pool, mode="fp8"), ref)
            out["fault_half_batch"] = _numbers(
                T.reference_readings(cell, seed, pool, fault="half_batch"),
                ref)
            if cell.workload.get("mesh"):   # the exchange left out
                out["fault_quarter_batch"] = _numbers(
                    T.reference_readings(cell, seed, pool,
                                         fault="quarter_batch"), ref)
            if bf16:
                out["reference_bf16"] = _numbers(
                    T.reference_readings(cell, seed, pool, mode="bf16"), ref)
        out["s"] = time.perf_counter() - t0
        harness.say(**out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--bf16", type=int, default=0)
    args = ap.parse_args()
    cell = harness.Cell(args.workload)
    devices = harness.require_chips(cell.chips)
    R.enable_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    train(cell, seeds, args.control_seeds, args.bf16, devices)


if __name__ == "__main__":
    main()
