"""Cells cut to sizes a CPU test can hold. Widths shrink here and nowhere
else: the benchmark's own runs use the files as committed."""

import copy

from benchmarks import harness

PEAK = {"bf16_flops": 1e12}


def shrink(cell, mesh=None):
    cell.config = copy.deepcopy(cell.config)
    cell.workload = copy.deepcopy(cell.workload)
    cell.traffic = cell.workload["traffic"]
    t = cell.traffic
    cell.config.update(image_shape=[64, 64, 3], num_classes=10)
    t.update(batch=32, pool=64)
    t["slots"][0]["dim"] = 64 * 64 * 3
    t["slots"][1]["vocab"] = 10
    if mesh:
        cell.workload["mesh"] = mesh
        cell.chips = 1
        for n in mesh.values():
            cell.chips *= n
    # float32 on the CPU: the reference's own precision, so that a sound
    # run reads rounding and a fault reads as itself
    cell.config["matmul_precision"] = "float32"
    return cell


def cell(name, mesh=None):
    return shrink(harness.Cell(name), mesh)
