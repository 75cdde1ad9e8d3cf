"""The share of the device's busy time (of the fullest chip, in the traced
window) that went to operations whose scope is one of the metric's `scopes`:
`batch_norm:` names every layer of that type, `optimizer` the scope itself
(benchmarks/program_spans.py says where an operation's scope is read).
Nothing where no operation of the trace carries a scope."""

from benchmarks import program_spans


def read(run, args):
    r = program_spans.busy_of_run(run)
    if r is None or not r["busy_s"] or not r["scoped_s"]:
        return None
    mine = sum(t for scope, t in r["by_scope_s"].items()
               if program_spans.in_classes(scope, args["scopes"]))
    return 100.0 * mine / r["busy_s"]
