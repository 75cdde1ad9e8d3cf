"""A kernel's share of its roofline in the traced window: the time the
chip's peaks allow one step's calls of the kernel, over the time they took.

The kernel's operations are found in the trace by scope: those whose scope
path (the `tf_op` of the operation's metadata, as
`program_spans.scopes_of_file` reads it; forward, recomputed forward and
backward alike carry the `jax.named_scope` they were traced under) holds
the metric's `scope`, and whose HLO line holds `op` (`custom-call`: the
Pallas kernel itself, not the transposes and casts around it). Every such
operation runs once a step, so a step's kernel time is the sum over them of
each one's mean duration in the window, whatever the window cuts off.

The allowed time is the larger of operations / the chip's bf16 peak
(benchmarks/peaks.json) and bytes / its HBM bandwidth (the metric's own
file, with its source), both counted for one step, forward and backward,
by the function of the configuration's module that the file names (`cost`),
from the configuration and the traffic of the file's `cell` alone.

Nothing where the trace holds no such operation (a program without the
kernel, as the parent of the PR that brought it), and nothing without a
trace.
"""

import functools

from benchmarks import harness, program_spans, trace_reduce


def kernel_seconds_a_step(device_ops, scopes, window, scope, op="custom-call"):
    """The arithmetic, on plain data: `device_ops` {device: [(name, start,
    end)]} and `window` as trace_reduce.read gives them, `scopes` {device:
    {operation's name: (scope path, HLO line)}}. -> (seconds-unit time a
    step on the fullest device, operations found) or None."""
    best = None
    for dev, ops in device_ops.items():
        table = scopes.get(dev, {})
        by_op = {}
        for name, s, e in ops:
            path, line = table.get(name, ("", ""))
            if scope in path and op in line:
                for cs, ce in trace_reduce.clip([(s, e)], *window):
                    if (cs, ce) == (s, e):          # whole calls only
                        by_op.setdefault(name, []).append(e - s)
        if by_op:
            t = sum(sum(d) / len(d) for d in by_op.values())
            if best is None or t > best[0]:
                best = (t, len(by_op))
    return best


@functools.lru_cache(maxsize=1)
def _scopes(path, mtime):
    """{device: {operation's short name: (scope path, HLO line)}}, parsed
    once for every metric this reader serves; None where unreadable."""
    try:
        return {dev: {trace_reduce.short_name(line): (scope, line)
                      for line, scope in table.items()}
                for dev, table in program_spans.scopes_of_file(path).items()}
    except (ValueError, IndexError, KeyError, UnicodeDecodeError):
        return None


def read(run, args):
    found = program_spans._trace_of(run)
    if not found:
        return None
    device_ops, _, window = program_spans._read(*found)
    scopes = _scopes(*found)
    if window is None or scopes is None:
        return None
    got = kernel_seconds_a_step(device_ops, scopes, window, args["scope"],
                                args.get("op", "custom-call"))
    if got is None or not got[0]:
        return None
    cell = harness.Cell(args["cell"])
    cost = getattr(cell.model, args["cost"])(cell.config, cell.traffic)
    peak = run["peak"]["bf16_flops"] * run["chips"]
    allowed = max(cost["flops"] / peak,
                  cost["bytes"] / (args["hbm_bytes_per_s"] * run["chips"]))
    harness.say(kernel_roofline={
        "scope": args["scope"], "operations": got[1],
        "kernel_s_a_step": got[0] * 1e-9, "allowed_s_a_step": allowed,
        "flops_a_step": cost["flops"], "bytes_a_step": cost["bytes"]})
    return 100.0 * allowed / (got[0] * 1e-9)
