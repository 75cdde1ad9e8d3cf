"""The comparison that decides `correct`: numbers, each beside its limit.

Training (the contract's measure): a gap between two norms, the
program's and the reference's, NOT the norm of their difference, since
under bf16 a gradient keeps its norm and little of its direction. The gap
of a leaf is measured against the reference's norm of that leaf or of the
median leaf, whichever is larger (some gradients are all but zero), and the
number compared is the worst leaf's.
"""

from __future__ import annotations

import json
import statistics
import sys

# a leaf whose reference gradient is under this share of the median leaf's
# moves by round-off alone under Adam: left out of the change
TINY_GRADIENT = 1e-3


def worst_leaf_gap(prog: dict, ref: dict, skip=()) -> tuple:
    """-> (gap, leaf) over the leaves of `ref` not in `skip`."""
    floor = statistics.median(ref.values())
    worst, where = 0.0, ""
    for name, r in ref.items():
        if name in skip:
            continue
        if name not in prog:
            return float("inf"), name
        gap = abs(prog[name] - r) / max(r, floor, 1e-30)
        if not gap <= worst:           # a NaN is the worst gap there is
            worst, where = (gap if gap == gap else float("inf")), name
    return worst, where


def tiny_gradient_leaves(ref_grad: dict) -> set:
    floor = TINY_GRADIENT * statistics.median(ref_grad.values())
    return {k for k, v in ref_grad.items() if v < floor}


def training_numbers(prog: dict, ref: dict) -> dict:
    """`prog` and `ref`: {"loss": [..], "grad1": {leaf: norm}, "delta":
    {leaf: norm}}. -> {name: value} of the numbers compared."""
    out = {}
    for i, (a, b) in enumerate(zip(prog["loss"], ref["loss"])):
        gap = abs(a - b) / max(abs(b), 1e-30)
        out[f"loss{i + 1}_gap"] = gap if gap == gap else float("inf")
    if len(prog["loss"]) != len(ref["loss"]):
        out["loss1_gap"] = float("inf")
    out["grad1_gap"], out["grad1_leaf"] = worst_leaf_gap(
        prog["grad1"], ref["grad1"])
    skip = tiny_gradient_leaves(ref["grad1"])
    out["delta_gap"], out["delta_leaf"] = worst_leaf_gap(
        prog["delta"], ref["delta"], skip=skip)
    # steadier from seed to seed than the worst leaf: the median leaf's gap
    # and the gap of the norm over all leaves together
    out["grad1_median_gap"] = median_leaf_gap(prog["grad1"], ref["grad1"])
    out["delta_median_gap"] = median_leaf_gap(prog["delta"], ref["delta"],
                                              skip)
    out["grad1_total_gap"] = total_gap(prog["grad1"], ref["grad1"])
    out["delta_total_gap"] = total_gap(prog["delta"], ref["delta"], skip)
    return out


def median_leaf_gap(prog: dict, ref: dict, skip=()) -> float:
    floor = statistics.median(ref.values())
    gaps = [abs(prog.get(k, float("inf")) - r) / max(r, floor, 1e-30)
            for k, r in ref.items() if k not in skip]
    gap = statistics.median(gaps)
    return gap if gap == gap else float("inf")


def total_gap(prog: dict, ref: dict, skip=()) -> float:
    def norm(d):
        return sum(v * v for k, v in d.items() if k not in skip) ** 0.5

    if set(ref) - set(prog):
        return float("inf")
    gap = abs(norm(prog) - norm(ref)) / max(norm(ref), 1e-30)
    return gap if gap == gap else float("inf")


def judge(numbers: dict, limits: dict) -> tuple:
    """-> (correct, [[name, value, limit], ...]) for the numbers that have
    a limit; a limited number that is missing or not finite fails."""
    rows, ok = [], True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = (value is not None and value == value
                and abs(value) <= limit)
        ok = ok and good
        rows.append([name, value, limit])
    return ok, rows


def say_compared(rows, notes=None, stream=None) -> None:
    """The run's last lines on standard error: each number, its limit."""
    stream = stream or sys.stderr
    for name, value, limit in rows:
        verdict = "ok" if (value is not None and value == value
                           and abs(value) <= limit) else "OVER"
        print(f"compared {name} = {value!r} limit {limit!r} {verdict}",
              file=stream)
    if notes:
        print("compared notes " + json.dumps(notes), file=stream)
    stream.flush()
