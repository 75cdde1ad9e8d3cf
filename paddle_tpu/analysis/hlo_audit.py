"""Compiled-program auditor (ISSUE 13 tentpole, part a).

Static checks over captured HLO modules (`tools/traces/*.hlo.txt.gz`
— REAL compiled programs dumped by tools/profile_longctx.py /
tools/profile_lm.py), turning the repo's hardest-won perf
invariants into machine-checked tripwires:

- **donation/aliasing** — a train-update program that donates its
  parameter/optimizer buffers must show them in the module's
  `input_output_alias` map. A missing alias means XLA kept the input
  buffers live across the step: HBM footprint silently doubles and
  nobody notices until the first OOM at scale.
- **host transfers** — infeed/outfeed/send/recv/host-offload
  custom-calls per step against an explicit budget (default 0: the
  watchdog's "zero extra D2H per batch" pin, generalized to any
  audited program).
- **byte budgets** — total program bytes, the largest single
  materialized tensor, and per-category bytes (the attention category
  is how the flash-vs-dense byte removal was proven) against the
  committed baseline + headroom. A byte *regression* fails the lint —
  the static counterpart of the measured `fused_speedup` A/B.
- **forbidden-op patterns** — no [T,T] score materialization in a
  program captured with `attn_impl="flash"` (any instruction whose
  output carries two adjacent seq_len dims), and no large f32 upcasts
  in programs captured under an AMP policy.

Every check is driven by a per-capture policy from
`tools/traces/audit_budgets.json`; `audit_capture` returns a
machine-readable report (committed as `<stem>.audit.json` next to the
capture) and `tools/framework_lint.py` fails CI when a check fails OR
when a committed report no longer matches the capture it describes.

Pure stdlib — runs with jax blocked, like every analysis/ module.
"""

from __future__ import annotations

import json
import os

from paddle_tpu.analysis import hlo_text as _hlo

AUDIT_SCHEMA = "paddle-tpu-hlo-audit/v1"

# opcodes / custom-call targets that move data across the host
# boundary. `copy` is NOT here: device-internal copies are layout
# traffic; host copies on TPU surface as infeed/outfeed or the
# MoveToHost/MoveToDevice offload annotations.
_HOST_TRANSFER_OPCODES = (
    "infeed", "outfeed", "send", "send-done", "recv", "recv-done",
)
_HOST_OFFLOAD_TOKENS = ("movetohost", "movetodevice")

# byte-budget fields checked against the policy's `*_max` keys
_BYTE_BUDGET_FIELDS = ("total_bytes", "largest_output_bytes")

# adjacent equal dims below this are ignored by the [T,T] check —
# square weight matrices (e.g. [512,512] projections) are not score
# materializations
_TT_MIN_DIM = 1024


def _instructions(path: str):
    text = _hlo.load_text(path)
    return text, list(_hlo.iter_instructions(text.splitlines()))


def check_donation(text: str, policy: dict, report: dict) -> dict:
    """`require_donation` policies: the module's input_output_alias
    map must cover at least `min_aliased_buffers` parameter indices
    (the capture's sibling report records how many buffers the
    program was compiled to donate)."""
    need = int(
        policy.get("min_aliased_buffers")
        or report.get("donated_arg_buffers")
        or 0
    )
    aliased = _hlo.parse_input_output_alias(text)
    ok = len(aliased) >= need
    return {
        "name": "donation",
        "ok": ok,
        "aliased_buffers": len(aliased),
        "min_aliased_buffers": need,
        "detail": (
            "" if ok else
            f"only {len(aliased)} input buffer(s) in "
            f"input_output_alias, expected >= {need} — donated "
            f"params are being copied, HBM footprint doubles"
        ),
    }


def check_host_transfers(instrs, policy: dict) -> dict:
    """Count host-boundary ops against the per-step budget."""
    budget = int(policy.get("host_transfer_budget", 0))
    found = []
    for name, _out, opcode, _ops, line in instrs:
        low = line.lower()
        if opcode in _HOST_TRANSFER_OPCODES:
            found.append(f"{opcode} {name}")
        elif opcode == "custom-call" and any(
            t in low for t in _HOST_OFFLOAD_TOKENS
        ):
            found.append(f"custom-call {name}")
    ok = len(found) <= budget
    return {
        "name": "host_transfers",
        "ok": ok,
        "host_transfer_ops": len(found),
        "budget": budget,
        "ops": found[:8],
        "detail": (
            "" if ok else
            f"{len(found)} host-transfer op(s) vs budget {budget}: "
            f"{found[:4]} — an extra D2H/H2D per step landed in the "
            f"compiled program"
        ),
    }


def check_byte_budgets(attrib: dict, policy: dict) -> list:
    """total_bytes / largest_output_bytes / per-category bytes vs the
    committed `*_max` budgets. Budgets carry the baseline + headroom;
    exceeding one is a byte REGRESSION against the measured record."""
    checks = []
    for field in _BYTE_BUDGET_FIELDS:
        cap = policy.get(field + "_max")
        if cap is None:
            continue
        got = attrib[field]
        ok = got <= cap
        checks.append({
            "name": f"byte_budget.{field}",
            "ok": ok,
            "measured": got,
            "budget": cap,
            "detail": (
                "" if ok else
                f"{field}={got / 1e6:.1f} MB exceeds the committed "
                f"budget {cap / 1e6:.1f} MB — bytes regressed vs the "
                f"baseline this capture was committed with"
            ),
        })
    for cat, cap in (policy.get("category_bytes_max") or {}).items():
        got = attrib["categories"].get(cat, {}).get("bytes", 0)
        ok = got <= cap
        checks.append({
            "name": f"byte_budget.category.{cat}",
            "ok": ok,
            "measured": got,
            "budget": cap,
            "detail": (
                "" if ok else
                f"category {cat!r} bytes {got / 1e6:.1f} MB exceed "
                f"the committed budget {cap / 1e6:.1f} MB"
            ),
        })
    return checks


def check_no_tt_materialization(instrs, policy: dict,
                                report: dict) -> dict:
    """Flash-path programs must not materialize a [T,T] score tensor:
    no instruction OUTPUT may carry two adjacent dims equal to the
    capture's seq_len (>= _TT_MIN_DIM so square weights don't trip
    it). This is the static pin behind PERF round 8's 2147->268 MB
    largest-tensor verdict."""
    t = int(policy.get("seq_len") or report.get("seq_len") or 0)
    offenders = []
    if t >= _TT_MIN_DIM:
        for name, out_shape, _opcode, _ops, _line in instrs:
            for _dt, dims in _hlo.shape_dims(out_shape):
                for a, b in zip(dims, dims[1:]):
                    if a == t and b == t:
                        offenders.append(f"{name} {out_shape}")
                        break
    ok = not offenders
    return {
        "name": "no_tt_materialization",
        "ok": ok,
        "seq_len": t,
        "offenders": offenders[:6],
        "detail": (
            "" if ok else
            f"{len(offenders)} instruction(s) materialize a "
            f"[{t},{t}] tensor on an attn_impl='flash' program: "
            f"{offenders[:3]} — the O(T^2) score matrix is back"
        ),
    }


def check_no_f32_upcast(instrs, policy: dict) -> dict:
    """AMP-policy programs must not grow large f32 tensors out of
    bf16 inputs at fusion boundaries (an upcast fusion silently
    doubles the bytes AMP exists to halve). Only outputs >=
    `f32_upcast_bytes_min` count — scalar/stat upcasts (loss, BN
    statistics) are the point of mixed precision."""
    floor = int(policy.get("f32_upcast_bytes_min", 1 << 20))
    offenders = []
    for name, out_shape, _opcode, operands, _line in instrs:
        dims = _hlo.shape_dims(out_shape)
        if not dims or any(dt != "f32" for dt, _ in dims):
            continue
        if _hlo.shape_bytes(out_shape) < floor:
            continue
        if "bf16[" in operands or "f16[" in operands:
            offenders.append(f"{name} {out_shape}")
    ok = not offenders
    return {
        "name": "no_f32_upcast",
        "ok": ok,
        "floor_bytes": floor,
        "offenders": offenders[:6],
        "detail": (
            "" if ok else
            f"{len(offenders)} fusion(s) upcast bf16 operands into "
            f">= {floor / 1e6:.1f} MB f32 outputs inside an AMP "
            f"program: {offenders[:3]}"
        ),
    }


def audit_capture(hlo_path: str, policy: dict,
                  report: dict = None) -> dict:
    """Run every policy-enabled check on one capture; returns the
    audit report dict (`ok` = all checks passed). `report` is the
    capture's sibling `<stem>.report.json` (auto-loaded when not
    passed) — it carries the shape/donation context the capture
    generator knew at compile time."""
    if report is None:
        stem = hlo_path
        for suf in (".hlo.txt.gz", ".hlo.txt"):
            if stem.endswith(suf):
                stem = stem[: -len(suf)]
                break
        sibling = stem + ".report.json"
        report = {}
        if os.path.exists(sibling):
            with open(sibling) as f:
                report = json.load(f)

    text, instrs = _instructions(hlo_path)
    lines = text.splitlines()
    attrib = _hlo.analyze_hlo(hlo_path, lines=lines)
    checks = []
    if policy.get("require_donation"):
        checks.append(check_donation(text, policy, report))
    if "host_transfer_budget" in policy:
        checks.append(check_host_transfers(instrs, policy))
    checks.extend(check_byte_budgets(attrib, policy))
    if policy.get("forbid_tt_materialization"):
        checks.append(
            check_no_tt_materialization(instrs, policy, report)
        )
    if policy.get("forbid_f32_upcast"):
        checks.append(check_no_f32_upcast(instrs, policy))
    out = {
        "schema": AUDIT_SCHEMA,
        "source": os.path.basename(hlo_path),
        "attn_impl": report.get("attn_impl"),
        "seq_len": report.get("seq_len"),
        "n_instructions": attrib["n_instructions"],
        "total_bytes": attrib["total_bytes"],
        "largest_output_bytes": attrib["largest_output_bytes"],
    }
    # SPMD policies (ISSUE 15): partitioning/replication/collective/
    # schedule checks ride the SAME report + freshness machinery —
    # one <stem>.audit.json per capture, never two writers. The extra
    # keys appear only on SPMD policies so the single-device reports
    # stay byte-identical.
    from paddle_tpu.analysis import spmd_audit as _spmd

    if _spmd.is_spmd_policy(policy):
        spmd_checks, summary = _spmd.spmd_checks(
            text, policy, lines=lines
        )
        checks.extend(spmd_checks)
        out["num_partitions"] = _hlo.num_partitions(text)
        out["collectives"] = summary
    out["ok"] = all(c["ok"] for c in checks)
    out["checks"] = checks
    return out


def load_budgets(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def audit_dir(traces_dir: str, budgets_path: str = None,
              only=None) -> dict:
    """Audit every capture named in the budgets file. Returns
    {stem: report}. A budget entry whose capture file is missing is
    itself a violation (reported as a failed pseudo-check): deleting
    an audited capture must not silently drop its tripwires.
    `only` is an optional predicate on the policy dict — the
    spmd-audit pass uses it to run exactly the SPMD-policy stems."""
    budgets_path = budgets_path or os.path.join(
        traces_dir, "audit_budgets.json"
    )
    budgets = load_budgets(budgets_path)
    out = {}
    for stem, policy in sorted(budgets.items()):
        if stem.startswith("_"):  # "_comment" etc.
            continue
        if only is not None and not only(policy):
            continue
        hlo_path = os.path.join(traces_dir, stem + ".hlo.txt.gz")
        if not os.path.exists(hlo_path):
            hlo_path = os.path.join(traces_dir, stem + ".hlo.txt")
        if not os.path.exists(hlo_path):
            out[stem] = {
                "schema": AUDIT_SCHEMA,
                "source": stem,
                "ok": False,
                "checks": [{
                    "name": "capture_exists",
                    "ok": False,
                    "detail": f"{stem}: capture named in "
                              f"{os.path.basename(budgets_path)} is "
                              f"missing from {traces_dir}",
                }],
            }
            continue
        out[stem] = audit_capture(hlo_path, policy)
    return out


def violations(reports: dict) -> list:
    """Flatten failed checks into lint-style violation strings."""
    out = []
    for stem, rep in sorted(reports.items()):
        for c in rep["checks"]:
            if not c["ok"]:
                out.append(f"{stem}: [{c['name']}] {c['detail']}")
    return out
