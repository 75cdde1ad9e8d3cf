#!/usr/bin/env python
"""framework_lint — the single driver for every static-analysis pass
(ISSUE 13).

Registered passes (run one by name, `--fast`, or `--all`):

  ast          paddle_tpu/analysis/ast_lint.py source passes over the
               tree: jax-import fence, duplicate dict keys, unfenced
               timing around async dispatch, unlocked container
               mutation. Pure AST, jax-free, fast — run BEFORE the
               test shards.
  hlo-audit    paddle_tpu/analysis/hlo_audit.py over every capture
               named in tools/traces/audit_budgets.json: donation/
               aliasing, host-transfer budget, byte budgets vs the
               committed baseline, forbidden-op patterns (no [T,T] on
               flash captures, no AMP f32 upcasts). Also verifies the
               committed *.audit.json reports still match the
               captures they describe — a stale report is itself a
               violation. `--write-audit` refreshes them after an
               intentional perf change (then re-baseline
               audit_budgets.json by hand: budgets never auto-widen).
  spmd-audit   paddle_tpu/analysis/spmd_audit.py over every SPMD-
               policy capture (the mc_* rows from
               tools/profile_multichip.py): partition-count pin,
               replication floor (no tensor above the floor may ride
               replicated on a sharded program), collective byte
               budgets + required/forbidden collective kinds, and
               schedule safety (channel uniqueness, data-dependent
               channel order, collective-permute ring validity).
               Same freshness discipline and --write-audit flow as
               hlo-audit; the two passes split the budgets file by
               policy kind so `--all` audits every stem exactly once.
  bundle       `bundle FILE...`: schema lint of flight-recorder and
               fleet incident bundles an operator hands it
               (paddle_tpu/obs/flight_recorder.py `check_bundle`);
               not a pass over the tree, so `--all` leaves it out.

Runtime tripwires live next door and are driven elsewhere: the
recompile guard (analysis/recompile_guard.py) arms inside the trainer
/serving batcher, and the lock-order checker (analysis/lock_order.py)
instruments the known locks when the faults shard runs with
PADDLE_LOCK_CHECK=1 (tests/run_suite.sh).

Usage:
    python tools/framework_lint.py --all
    python tools/framework_lint.py --fast          # jax-free AST tier
    python tools/framework_lint.py ast hlo-audit   # specific passes
    python tools/framework_lint.py bundle B.json [...]
    python tools/framework_lint.py hlo-audit --write-audit
    python tools/framework_lint.py --list

Exit 0 = clean, 1 = violations (printed to stderr), 2 = usage error.
Everything here is pure stdlib — no jax, no device runtime.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)
TRACES_DIR = os.path.join(_REPO, "tools", "traces")


# ---- passes -------------------------------------------------------
def pass_ast(repo: str, _args) -> list:
    from paddle_tpu.analysis import ast_lint

    return ast_lint.run_passes(repo)


def _audit_pass(repo: str, args, tag: str, only=None) -> list:
    """Shared body of the capture-audit passes: run the auditor over
    every budgets entry `only` selects, then enforce committed-report
    freshness — the *.audit.json next to each capture must be exactly
    what the capture audits to today; a stale report lies about what
    the lint enforces. `--write-audit` regenerates them after an
    intentional change (then re-baseline audit_budgets.json by hand:
    budgets never auto-widen)."""
    from paddle_tpu.analysis import hlo_audit

    traces = os.path.join(repo, "tools", "traces")
    if not os.path.isdir(traces):
        traces = TRACES_DIR
    budgets = os.path.join(traces, "audit_budgets.json")
    if not os.path.exists(budgets):
        return [
            f"[{tag}] {budgets}: missing — the byte-budget "
            f"baselines are gone; the audit has nothing to enforce"
        ]
    reports = hlo_audit.audit_dir(traces, budgets, only=only)
    violations = [
        f"[{tag}] {v}" for v in hlo_audit.violations(reports)
    ]
    for stem, rep in sorted(reports.items()):
        out_path = os.path.join(traces, stem + ".audit.json")
        if getattr(args, "write_audit", False):
            with open(out_path, "w") as f:
                json.dump(rep, f, indent=2)
                f.write("\n")
            print(f"framework_lint: wrote {out_path}")
            continue
        if not os.path.exists(out_path):
            violations.append(
                f"[{tag}] {stem}: no committed audit report "
                f"({os.path.basename(out_path)}) — run "
                f"`python tools/framework_lint.py {tag} "
                f"--write-audit` and commit it"
            )
            continue
        with open(out_path) as f:
            committed = json.load(f)
        if committed != rep:
            violations.append(
                f"[{tag}] {stem}: committed audit report is "
                f"STALE (capture or auditor changed since it was "
                f"written) — regenerate with --write-audit"
            )
    return violations


def pass_hlo_audit(repo: str, args) -> list:
    from paddle_tpu.analysis import spmd_audit

    # non-SPMD stems only: the SPMD-policy captures belong to the
    # spmd-audit pass (one pass per stem, so `--all` audits every
    # stem exactly once and the two passes can't double-write a
    # report)
    return _audit_pass(
        repo, args, "hlo-audit",
        only=lambda p: not spmd_audit.is_spmd_policy(p),
    )


def pass_spmd_audit(repo: str, args) -> list:
    from paddle_tpu.analysis import spmd_audit

    return _audit_pass(
        repo, args, "spmd-audit", only=spmd_audit.is_spmd_policy
    )


def pass_bundle(_repo: str, args) -> list:
    from paddle_tpu.obs import flight_recorder

    return [
        f"[bundle] {v}"
        for path in args.bundle_files
        for v in flight_recorder.check_bundle(path)
    ]


PASSES = {
    "ast": pass_ast,
    "hlo-audit": pass_hlo_audit,
    "spmd-audit": pass_spmd_audit,
    "bundle": pass_bundle,
}
# the jax-free tier cheap enough to gate every suite run up front
FAST_PASSES = ("ast",)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="framework_lint",
        description=__doc__.splitlines()[0],
    )
    ap.add_argument("passes", nargs="*",
                    help=f"pass names ({', '.join(PASSES)}); "
                         f"after `bundle`, the bundle files")
    ap.add_argument("--all", action="store_true",
                    help="run every pass over the tree")
    ap.add_argument("--fast", action="store_true",
                    help=f"run the fast jax-free tier "
                         f"({', '.join(FAST_PASSES)})")
    ap.add_argument("--list", action="store_true",
                    help="list registered passes")
    ap.add_argument("--repo", default=_REPO,
                    help="repo root to lint (default: this checkout)")
    ap.add_argument("--write-audit", action="store_true",
                    help="(hlo-audit) regenerate the committed "
                         "*.audit.json reports")
    args = ap.parse_args(argv)

    if args.list:
        for name in PASSES:
            print(name)
        return 0
    names = list(args.passes)
    if "bundle" in names:  # what follows it are its files
        i = names.index("bundle")
        names, args.bundle_files = names[:i + 1], names[i + 1:]
        if not args.bundle_files:
            print("framework_lint: bundle needs at least one file",
                  file=sys.stderr)
            return 2
    if args.all:
        names = [n for n in PASSES if n != "bundle"]
    elif args.fast:
        names = list(FAST_PASSES)
    if not names:
        ap.print_usage(sys.stderr)
        print(
            "framework_lint: name at least one pass, or --all/--fast",
            file=sys.stderr,
        )
        return 2
    unknown = [n for n in names if n not in PASSES]
    if unknown:
        print(
            f"framework_lint: unknown pass(es) {unknown}; "
            f"registered: {list(PASSES)}",
            file=sys.stderr,
        )
        return 2

    violations = []
    for name in names:
        violations.extend(PASSES[name](args.repo, args))
    for v in violations:
        print(f"framework_lint: {v}", file=sys.stderr)
    if not violations:
        print(f"framework_lint: OK ({', '.join(names)})")
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main())
