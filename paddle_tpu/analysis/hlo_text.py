"""Compiled-HLO text parsing + op classification (pure stdlib).

The single parser behind BOTH measurement tools and the static
auditor (ISSUE 13): `tools/trace_attribution.py` uses it to attribute
a captured program's bytes to categories, and
`paddle_tpu/analysis/hlo_audit.py` uses the same instruction stream
to enforce donation/aliasing, host-transfer budgets, byte budgets and
forbidden-op patterns. One parser means the audit argues about the
exact bytes the perf record argues about — the two can never drift.

Input is the `*.hlo.txt[.gz]` capture format written by
tools/profile_longctx.py / bench.write_decode_hlo: the
`compiled.as_text()` dump of a REAL compiled program
(`is_scheduled=true`, fusions closed), NOT pre-optimization stable
HLO. Bytes are charged at fusion boundaries — exactly the tensors
that cross HBM.

No jax anywhere in this module: the audits must run in CI shards and
serving front ends with the device runtime blocked (the obs-lint
discipline, extended to analysis/).
"""

from __future__ import annotations

import gzip
import json
import os
import re
from collections import defaultdict

CATEGORIES = (
    "conv", "gemm", "attention", "bn_elementwise", "layout",
    "collective", "infeed", "other",
)

_COLLECTIVE_TOKENS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective", "send", "recv",
)
_LAYOUT_NAME_PREFIXES = (
    "copy", "transpose", "bitcast", "reshape", "convert_element_type",
    "slice-start", "slice-done", "dynamic_slice", "dynamic-update",
    "pad",
)
# attention bucketing (ISSUE 12): ops under the attention
# named_scopes (parallel/ring.py stamps dense_attention /
# flash_attention / ring/ulysses scopes into HLO metadata op_name,
# which trace events carry in long_name/tf_op) and Pallas/Mosaic
# custom-call attention kernels
_ATTENTION_TOKENS = (
    "dense_attention", "flash_attention", "ring_attention",
    "ulysses_attention", "flash_att",
)
_ATTENTION_CUSTOM_CALL_TOKENS = ("mosaic", "tpu_custom_call")


def classify(name: str, category: str, long_name: str) -> str:
    """Map one device op to a report category. `category` is XLA's own
    `hlo_category` arg (or the HLO opcode in hlo-module captures);
    `long_name` the HLO text incl. metadata (both may be '')."""
    n = name.lower()
    c = (category or "").lower()
    ln = (long_name or "").lower()
    if any(t in n or t in c for t in _COLLECTIVE_TOKENS):
        return "collective"
    if "infeed" in n or "outfeed" in n or "infeed" in c or "outfeed" in c:
        return "infeed"
    # attention BEFORE conv/gemm: the attention scopes' dots/fusions
    # must land here, and a Pallas flash kernel is a custom-call whose
    # only category hint is its target/metadata
    if any(t in n or t in ln for t in _ATTENTION_TOKENS):
        return "attention"
    if ("custom-call" in c or "custom_call" in c
            or n.startswith("custom")) and any(
        t in n or t in ln for t in _ATTENTION_CUSTOM_CALL_TOKENS
    ):
        return "attention"
    if "convolution" in c or "convolution(" in ln or n.startswith("conv_"):
        return "conv"
    if ("dot(" in ln or "dot " in ln or "gemm" in n or "gemm" in c
            or c == "dot" or n.startswith("dot")):
        return "gemm"
    # layout/data-movement BEFORE elementwise: convert_element_type is
    # a dtype/layout relayout even though XLA categorizes it
    # "non-fusion elementwise", and the async slice-start/done pairs
    # are HBM<->scratch staging copies
    if (c in ("copy", "copy-start", "copy-done", "data formatting",
              "dynamic-slice", "async-start", "async-done")
            or n.startswith(_LAYOUT_NAME_PREFIXES)):
        return "layout"
    if ("fusion" in c or "elementwise" in c or "reduce" in c
            or "scatter" in c or "select-and-scatter" in c
            or n.startswith(("fusion", "add", "multiply", "reduce",
                             "select_and_scatter", "broadcast"))):
        return "bn_elementwise"
    return "other"


_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8,
    "u64": 8, "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1,
    "f8e5m2": 1,
}
_SHAPE_RE = re.compile(
    r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]"
)
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*"      # instruction name
    r"((?:\((?:[^()]|\([^()]*\))*\))|\S+)\s+"   # output shape (or tuple;
    # tuple shapes carry /*index=N*/ comments from 6 elements up — a
    # [^=] shape matcher loses every big-carry while loop and
    # tuple-form all-to-all — and, compiled for a TPU, one level of
    # parens from the tiled layouts, `{3,2,1,0:T(2,128)(2,1)S(1)}`:
    # without it every async -start collective of a TPU module is lost)
    r"([\w\-]+)\("                               # opcode
)
# instructions that move no HBM bytes of their own: reads are charged
# at the consuming op, parameters/constants at their users, tuple
# plumbing is free
_FREE_OPCODES = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "partition-id", "replica-id", "iota",
}


def shape_bytes(text: str) -> int:
    """Total bytes of every dtype[shape] occurrence in `text` (tuples
    sum their elements; scalars count their dtype size)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def shape_dims(text: str) -> list:
    """Every dtype[shape] occurrence in `text` as (dtype, [dims])."""
    out = []
    for dt, dims in _SHAPE_RE.findall(text):
        out.append(
            (dt, [int(d) for d in dims.split(",")] if dims else [])
        )
    return out


def operand_section(rest: str) -> str:
    """`rest` starts right after the opcode's '(' — return the operand
    text up to its matching ')' (attributes/metadata excluded)."""
    depth = 1
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return rest[:i]
    return rest


def load_text(path: str) -> str:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return f.read()


def iter_instructions(lines):
    """Yield (name, out_shape, opcode, operands, line) for every
    top-level instruction — instructions inside %fused_computation
    bodies are skipped (they live in registers/scratch; only fusion
    boundaries cross HBM). Other non-entry computations (while bodies,
    reduce appliers) are yielded once — callers needing loop-trip
    semantics must handle `while` opcodes themselves."""
    in_fused = False
    depth_at_fused = 0
    brace_depth = 0
    for line in lines:
        stripped = line.strip()
        opens = line.count("{") - line.count("}")
        if not in_fused and (
            stripped.startswith("%fused_computation")
            or stripped.startswith("fused_computation")
        ) and "{" in line:
            in_fused = True
            depth_at_fused = brace_depth
        brace_depth += opens
        if in_fused:
            if brace_depth <= depth_at_fused:
                in_fused = False
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, out_shape, opcode = m.groups()
        rest = line[m.end():]
        yield name, out_shape, opcode, operand_section(rest), line


def module_header(text: str) -> str:
    """The `HloModule ...` header line (alias map, entry layout)."""
    for line in text.splitlines():
        if line.startswith("HloModule"):
            return line
    return ""


_ALIAS_ENTRY_RE = re.compile(r"\{[\d,\s]*\}:\s*\((\d+)")


def parse_input_output_alias(text: str) -> list:
    """Parameter indices appearing in the module's
    `input_output_alias` map — the donated/aliased input buffers.
    Empty list = the program aliases nothing: every parameter is a
    live extra buffer for the whole step (the donation regression the
    auditor exists to catch). The map nests braces
    (`{ {0}: (0, {}, may-alias), ... }`), so the span is found by
    brace balancing, not regex."""
    header = module_header(text)
    start = header.find("input_output_alias={")
    if start < 0:
        return []
    i = start + len("input_output_alias=")
    depth = 0
    for j in range(i, len(header)):
        if header[j] == "{":
            depth += 1
        elif header[j] == "}":
            depth -= 1
            if depth == 0:
                body = header[i + 1:j]
                return sorted({
                    int(g) for g in _ALIAS_ENTRY_RE.findall(body)
                })
    return []


# categories with a positive token/opcode signal; the fallback buckets
# (bn_elementwise / layout / other) are WEAK — a weak op whose operand
# was produced by an attention op inherits "attention" (dataflow
# closure). XLA's backward-pass fission drops metadata from some
# fusions (e.g. the [T,T] softmax-backward convert fusions in the
# dense longctx capture carry no op_name at all), and without the
# closure those score-matrix bytes silently leak into bn_elementwise.
_STRONG_CATEGORIES = ("collective", "infeed", "attention", "conv",
                      "gemm")
_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")


def analyze_hlo(path: str, top: int = 10, lines=None) -> dict:
    """Static byte attribution of one compiled HLO module (the
    `*.hlo.txt[.gz]` captures): each top-level instruction is charged
    its output + operand bytes — at fusion granularity, exactly the
    tensors that cross HBM — and bucketed with the same classify() as
    the trace path (plus the weak-op dataflow inheritance above).
    Instructions inside %fused_computation bodies are skipped (they
    live in registers/scratch); other non-entry computations (while
    bodies, reduce appliers) count once, with the while-instruction
    count reported so the caveat is visible. `lines` lets a caller
    that already loaded the capture (hlo_audit runs several checks
    over one module) skip the second read+decompress."""
    if lines is None:
        lines = load_text(path).splitlines()

    cat_bytes = defaultdict(int)
    cat_ops = defaultdict(int)
    by_name = {}
    prod_cat: dict = {}  # instruction -> category (dataflow closure)
    total = 0
    n_instr = 0
    n_while = 0
    largest_output = 0
    inherited = 0
    for name, out_shape, opcode, operands, line in iter_instructions(
        lines
    ):
        if opcode in _FREE_OPCODES:
            continue
        n_instr += 1
        if opcode == "while":
            n_while += 1
        out_bytes = shape_bytes(out_shape)
        largest_output = max(largest_output, out_bytes)
        nbytes = out_bytes + shape_bytes(operands)
        cat = classify(name, opcode, line)
        if cat not in _STRONG_CATEGORIES:
            for op_name in _OPERAND_NAME_RE.findall(operands):
                if prod_cat.get(op_name) == "attention":
                    cat = "attention"
                    inherited += 1
                    break
        prod_cat[name] = cat
        cat_bytes[cat] += nbytes
        cat_ops[cat] += 1
        total += nbytes
        rec = by_name.setdefault(
            name, {"name": name, "category": cat, "bytes": 0,
                   "count": 0},
        )
        rec["bytes"] += nbytes
        rec["count"] += 1

    if n_instr == 0:
        raise SystemExit(f"{path}: no HLO instructions found")

    categories = {}
    for cat in CATEGORIES:
        if cat_ops.get(cat, 0) == 0:
            continue
        categories[cat] = {
            "bytes": cat_bytes[cat],
            "share": round(cat_bytes[cat] / total, 4) if total else 0.0,
            "n_ops": cat_ops[cat],
        }
    top_hlos = sorted(by_name.values(), key=lambda r: -r["bytes"])[:top]
    for r in top_hlos:
        r["share_of_bytes"] = round(r["bytes"] / total, 4) if total \
            else 0.0

    report = {
        "source": os.path.basename(path),
        "capture_kind": "hlo_module",
        "total_bytes": total,
        "n_instructions": n_instr,
        # while bodies are charged ONCE; a loopy capture must fold its
        # trip count in by hand (the decode analysis multiplies by
        # max_len) — 0 means the byte table is exact
        "while_instructions": n_while,
        # the footprint pin: the biggest single tensor the program
        # materializes (dense longctx: the [B,H,T,T] scores; flash:
        # a [B,H,T,block_k] tile)
        "largest_output_bytes": largest_output,
        "attention_inherited_ops": inherited,
        "shares": {c: v["share"] for c, v in categories.items()},
        "categories": categories,
        "top_hlos": top_hlos,
    }
    stem = path
    for suf in (".hlo.txt.gz", ".hlo.txt"):
        if stem.endswith(suf):
            stem = stem[: -len(suf)]
            break
    sibling = stem + ".report.json"
    if os.path.exists(sibling):
        with open(sibling) as f:
            report["capture_report"] = json.load(f)
    return report


# ==== SPMD parsing (ISSUE 15) ======================================
# Partitioned-module structure the SPMD auditor
# (analysis/spmd_audit.py) and the runtime multi-chip gate
# (parallel/dp.py assert_collectives) argue about: `sharding={...}`
# annotations, the collective instructions with their replica groups /
# channel ids / permute pairs, and which computation each instruction
# lives in (collectives appear inside while bodies and conditional
# branch regions — the ring attention hop is a collective-permute
# inside a branch inside the ring while loop).

# canonical collective opcodes; async pairs normalize to the base kind
# and only the -start half is yielded (the -done moves no new bytes)
COLLECTIVE_KINDS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
)

_NUM_PARTITIONS_RE = re.compile(r"\bnum_partitions=(\d+)\b")
_CHANNEL_ID_RE = re.compile(r"\bchannel_id=(\d+)\b")
_GROUP_LIST_RE = re.compile(r"\{([0-9,\s]*)\}")
_IOTA_GROUPS_RE = re.compile(
    r"\[([0-9,]+)\]<=\[([0-9,]+)\]"
)
# computation definition lines: `%name (params...) -> shape {` with an
# optional leading ENTRY; fused computations match too (collectives
# never fuse today, but the walker must not silently lose one if a
# future runtime puts them there)
_COMP_DEF_RE = re.compile(r"^\s*(?:ENTRY\s+)?%([\w.\-]+)\s*\(")


def num_partitions(text: str) -> int:
    """The module header's partition count — 1 (or absent) means the
    program was NOT SPMD-partitioned; an audited sharded capture with
    num_partitions=1 silently ran single-device."""
    m = _NUM_PARTITIONS_RE.search(module_header(text))
    return int(m.group(1)) if m else 1


def _balanced_braces(text: str, start: int) -> str:
    """`text[start]` is '{' — return the body between it and its
    matching '}' (exclusive)."""
    depth = 0
    for j in range(start, len(text)):
        if text[j] == "{":
            depth += 1
        elif text[j] == "}":
            depth -= 1
            if depth == 0:
                return text[start + 1:j]
    return text[start + 1:]


def _split_top_level(body: str) -> list:
    """Split `a, b, c` at depth-0 commas (sub-braces kept intact)."""
    parts, depth, cur = [], 0, []
    for ch in body:
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append("".join(cur).strip())
            cur = []
        else:
            cur.append(ch)
    tail = "".join(cur).strip()
    if tail:
        parts.append(tail)
    return parts


def _parse_sharding_body(body: str) -> dict:
    """`body` is the text INSIDE the annotation's outer braces."""
    body = body.strip()
    if body.startswith("{"):
        # tuple sharding: one `{...}` element per tuple leaf, in order
        return {
            "kind": "tuple",
            "elements": [
                _parse_sharding_body(_balanced_braces(e, e.find("{")))
                for e in _split_top_level(body)
            ],
        }
    if body == "replicated":
        return {"kind": "replicated"}
    if body == "manual":
        return {"kind": "manual"}
    if body.startswith("maximal"):
        m = re.search(r"device=(\d+)", body)
        return {
            "kind": "maximal",
            "device": int(m.group(1)) if m else 0,
        }
    if body.startswith("devices="):
        m = re.match(r"devices=\[([0-9,]+)\]", body)
        tile = [int(d) for d in m.group(1).split(",")] if m else []
        return {
            "kind": "devices",
            "tile": tile,
            "last_tile_dim_replicate":
                "last_tile_dim_replicate" in body,
        }
    return {"kind": "other", "raw": body}


def parse_sharding(line: str):
    """The `sharding={...}` annotation on one instruction line, as a
    dict — kind 'replicated' | 'maximal' | 'devices' (with the tile
    assignment dims) | 'tuple' (per-leaf elements) | 'manual' — or
    None when the line carries no annotation."""
    i = line.find("sharding=")
    if i < 0:
        return None
    j = line.find("{", i)
    if j < 0:
        return None
    return _parse_sharding_body(_balanced_braces(line, j))


def sharding_is_replicated(sh: dict) -> bool:
    """True when the annotation pins FULL bytes on every device: plain
    replicated, maximal (one device holds the whole tensor), or a
    devices= tiling whose non-replication dims are all 1."""
    if sh is None:
        return False
    kind = sh.get("kind")
    if kind in ("replicated", "maximal"):
        return True
    if kind == "devices":
        tile = sh.get("tile") or []
        if sh.get("last_tile_dim_replicate"):
            tile = tile[:-1]
        return all(d == 1 for d in tile)
    return False


def iter_computations(lines):
    """Yield (computation_name, line) for every line, tracking which
    computation definition the walker is inside (fused bodies
    included — unlike iter_instructions, nothing is skipped)."""
    comp = ""
    for line in lines:
        m = _COMP_DEF_RE.match(line)
        if m and "->" in line and line.rstrip().endswith("{"):
            comp = m.group(1)
        yield comp, line


def iter_shardings(lines):
    """Yield (name, out_shape, sharding, computation) for every
    instruction carrying a `sharding={...}` annotation, across ALL
    computations (entry params, outputs, copies)."""
    for comp, line in iter_computations(lines):
        if "sharding=" not in line:
            continue
        m = _INSTR_RE.match(line)
        if not m:
            # parameters have no '(': `%p = f32[8]{0} parameter(0), ...`
            # — they DO match _INSTR_RE (opcode `parameter(`); anything
            # else with a sharding but no instruction form is skipped
            continue
        name, out_shape, _opcode = m.groups()
        sh = parse_sharding(line)
        if sh is not None:
            yield name, out_shape, sh, comp


def _parse_replica_groups(line: str):
    """`replica_groups={{0,1},{2,3}}` -> [[0,1],[2,3]]; the iota form
    `replica_groups=[2,4]<=[8]` expands row-major when untransposed
    (the transposed form is kept raw — no capture uses it today)."""
    i = line.find("replica_groups=")
    if i < 0:
        return []
    rest = line[i + len("replica_groups="):]
    if rest.startswith("{"):
        body = _balanced_braces(rest, 0)
        return [
            [int(x) for x in g.split(",") if x.strip()]
            for g in _GROUP_LIST_RE.findall("{" + body + "}")
        ]
    m = _IOTA_GROUPS_RE.match(rest)
    if m:
        dims = [int(d) for d in m.group(1).split(",")]
        n = 1
        for d in [int(d) for d in m.group(2).split(",")]:
            n *= d
        if len(dims) == 2 and dims[0] * dims[1] == n \
                and not rest[m.end():m.end() + 1] == "T":
            return [
                list(range(r * dims[1], (r + 1) * dims[1]))
                for r in range(dims[0])
            ]
    return []


def _parse_pairs(line: str):
    """`source_target_pairs={{0,1},{1,2}}` -> [(0,1),(1,2)]."""
    i = line.find("source_target_pairs=")
    if i < 0:
        return []
    body = _balanced_braces(line, line.find("{", i))
    out = []
    for g in _GROUP_LIST_RE.findall("{" + body + "}"):
        xs = [int(x) for x in g.split(",") if x.strip()]
        if len(xs) == 2:
            out.append((xs[0], xs[1]))
    return out


def parse_collectives(lines) -> list:
    """Every collective instruction in the module, across ALL
    computations (while bodies, conditional branches, fusion bodies),
    as dicts:

      {name, kind, opcode, out_shape, bytes, channel_id,
       replica_groups, source_target_pairs, computation, operands}

    `kind` normalizes async pairs (`all-gather-start` -> all-gather);
    only the -start half is recorded. `bytes` is the instruction's
    output bytes — for a tuple-shaped all-to-all the sum over
    elements — i.e. what one program execution moves through the
    fabric per device. `channel_id` is None for unchanneled
    (replica-mode) collectives."""
    out = []
    for comp, line in iter_computations(lines):
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, out_shape, opcode = m.groups()
        base = opcode
        for suf in ("-start", "-done"):
            if base.endswith(suf):
                base = base[: -len(suf)]
        if base not in COLLECTIVE_KINDS:
            continue
        if opcode.endswith("-done"):
            continue
        cm = _CHANNEL_ID_RE.search(line)
        rest = line[m.end():]
        out.append({
            "name": name,
            "kind": base,
            "opcode": opcode,
            "out_shape": out_shape,
            "bytes": shape_bytes(out_shape),
            "channel_id": int(cm.group(1)) if cm else None,
            "replica_groups": _parse_replica_groups(line),
            "source_target_pairs": _parse_pairs(line),
            "computation": comp,
            "operands": operand_section(rest),
        })
    return out


def collective_summary(collectives) -> dict:
    """Aggregate byte/count view of `parse_collectives` output — the
    numbers the collective byte budget is enforced against."""
    by_kind: dict = {}
    total = 0
    largest = 0
    largest_name = ""
    for c in collectives:
        k = by_kind.setdefault(c["kind"], {"count": 0, "bytes": 0})
        k["count"] += 1
        k["bytes"] += c["bytes"]
        total += c["bytes"]
        if c["bytes"] > largest:
            largest, largest_name = c["bytes"], c["name"]
    return {
        "count": len(collectives),
        "total_bytes": total,
        "largest_bytes": largest,
        "largest": largest_name,
        "by_kind": by_kind,
    }
