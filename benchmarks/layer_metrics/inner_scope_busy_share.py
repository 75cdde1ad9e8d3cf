"""The share of the device's busy time (of the fullest chip, in the traced
window) that went to operations whose scope PATH holds the metric's `holds`:
a `jax.named_scope` INSIDE a layer (`attn.gate`, `moe.route`), wherever it
stands in the path (forward, recomputed forward and backward alike carry
it). `scope_busy_share` sees an operation's outermost scope, its layer,
alone. Nothing where no operation of the trace carries the name (a program
without the scope, as the parent of the PR that brought it), and nothing
without a trace."""

from benchmarks import program_spans
from benchmarks.layer_metrics import kernel_roofline
from benchmarks.trace_reduce import clip, total, union


def busy_under(device_ops, scopes, window, holds):
    """The arithmetic, on plain data: `device_ops` {device: [(name, start,
    end)]} and `window` as trace_reduce.read gives them, `scopes` {device:
    {operation's name: (scope path, HLO line)}}, as `kernel_roofline._scopes`
    parses them once for both readers. -> (the busy time of the fullest device
    in the window, the part of it under `holds`) or None."""
    best = None
    for dev, ops in device_ops.items():
        table = scopes.get(dev, {})
        every = union(clip([(s, e) for _, s, e in ops], *window))
        mine = union(clip([(s, e) for name, s, e in ops
                           if holds in table.get(name, ("",))[0]], *window))
        if best is None or total(every) > best[0]:
            best = (total(every), total(mine))
    return best


def read(run, args):
    found = program_spans._trace_of(run)
    if not found:
        return None
    device_ops, _, window = program_spans._read(*found)
    paths = kernel_roofline._scopes(*found)
    if window is None or paths is None:
        return None
    got = busy_under(device_ops, paths, window, args["holds"])
    if got is None or not got[0] or not got[1]:
        return None
    return 100.0 * got[1] / got[0]
