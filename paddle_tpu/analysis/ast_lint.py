"""Framework AST lint — registered source passes over paddle_tpu/
(ISSUE 13 tentpole, part c).

A pass registry the `tools/framework_lint.py` driver runs over the
whole tree. Each pass encodes a rule the repo learned the hard way:

- **jax_import_fence** — the module-scope jax-import allowlist,
  inverted into explicit jax-free zones: obs/ (serving front ends and
  data workers must import telemetry without the device runtime),
  analysis/ (this very lint runs in CI with jax blocked), serving/,
  data/, native/ (TCP front end, feeders, master server — all clean
  today and load-bearing that way), plus the lazily-importing package
  entry points. A top-level `import jax` in a fenced module is a
  regression that only surfaces when a front end box without jaxlib
  falls over.
- **duplicate_dict_keys** — a duplicate key in a dict literal is
  legal Python that silently keeps the LAST value; in the flag
  registry (core/flags.py `_DEFAULTS`) it is a silently-dropped
  setting. Any dict literal with a repeated constant key fails.
- **unfenced_timing** — a function that binds a jitted callable
  (`f = jax.jit(...)` / `...lower().compile()`), calls it between
  clock reads, and never fences (block_until_ready / float / asarray
  / device_get / tolist / item) measures DISPATCH, not execution —
  the async-dispatch timing bug the dispatch-floor campaign
  (ROADMAP 5d) kept re-finding in timing code. Trainer-style
  self-fencing APIs (run_step fetches the loss) are not flagged: the
  pass tracks only locally-bound jit objects.
- **raw_collective_outside_shard_map** — `lax.psum` / `ppermute` /
  `all_to_all` / `all_gather` are only meaningful over a named mesh
  axis, i.e. inside a function that flows into `jax.shard_map`.
  A raw collective in ordinary jit code either crashes on an unbound
  axis name or — under an enclosing pmap/shard_map it was never
  written for — silently reduces over the WRONG axis. The pass roots
  at every function passed to a `*shard_map` call and closes over
  same-file name references and lexical nesting; anything else that
  calls a raw collective fails. A deliberate exception carries a
  `# lint: raw-collective-ok` pragma saying why.
- **unlocked_mutation** — in a class that owns a `self._lock`,
  mutating a container attribute (one assigned `{}`/`[]`/`deque()`/
  `set()` in `__init__`) outside a `with self._lock`/`self._work`
  block races the locked readers. Methods named `*_locked` are
  exempt by the repo's held-by-contract convention; a deliberate
  lock-free site carries a `# lint: unlocked-ok` pragma on the
  statement (or the line above) saying why.

All pure stdlib/ast — no imports of the scanned code.
"""

from __future__ import annotations

import ast
import os

__all__ = ["PASSES", "run_passes", "iter_py_files"]

# ---- jax_import_fence configuration -------------------------------
JAX_FREE_DIRS = (
    "paddle_tpu/obs",
    "paddle_tpu/analysis",
    "paddle_tpu/serving",
    "paddle_tpu/data",
    "paddle_tpu/native",
    "paddle_tpu/decoding",
)
JAX_FREE_FILES = (
    # the telemetry modules every front end imports: the fence names
    # them so that deleting one fails the lint, not only a box
    "paddle_tpu/obs/metrics.py",
    "paddle_tpu/obs/timeline.py",
    "paddle_tpu/obs/tracing.py",
    "paddle_tpu/obs/flight_recorder.py",
    "paddle_tpu/obs/aggregate.py",
    "paddle_tpu/__init__.py",
    "paddle_tpu/__main__.py",
    "paddle_tpu/launch.py",
    "paddle_tpu/testing_faults.py",
    "paddle_tpu/trainer/__init__.py",
    "paddle_tpu/trainer/watchdog.py",
    "paddle_tpu/trainer/events.py",
    "paddle_tpu/core/flags.py",
    "paddle_tpu/core/stat.py",
    "paddle_tpu/core/config.py",
    "paddle_tpu/core/registry.py",
)

_CLOCK_FNS = {"time", "perf_counter", "monotonic"}
_FENCE_FNS = {
    "block_until_ready", "asarray", "float", "result", "device_get",
    "tolist", "item", "ravel",
}
_MUTATORS = {
    "append", "appendleft", "add", "pop", "popleft", "popitem",
    "update", "clear", "extend", "remove", "discard", "setdefault",
    "insert",
}
_CONTAINER_CTORS = {"dict", "list", "set", "deque", "defaultdict",
                    "OrderedDict", "Counter"}
_PRAGMA = "lint: unlocked-ok"

# ---- raw_collective_outside_shard_map configuration ---------------
_RAW_COLLECTIVES = {"psum", "ppermute", "all_to_all", "all_gather",
                    "pmean", "psum_scatter"}
_COLLECTIVE_PRAGMA = "lint: raw-collective-ok"


def iter_py_files(repo_dir: str, subpaths=("paddle_tpu",)):
    for sub in subpaths:
        path = os.path.join(repo_dir, sub)
        if os.path.isfile(path):
            yield path
            continue
        for root, dirs, files in os.walk(path):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in sorted(files):
                if f.endswith(".py"):
                    yield os.path.join(root, f)


def _parse(path: str):
    with open(path) as f:
        src = f.read()
    return ast.parse(src, path), src


def _module_scope(node):
    """Nodes reachable at import time (function bodies are lazy)."""
    for child in ast.iter_child_nodes(node):
        if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
            continue
        yield child
        yield from _module_scope(child)


def _call_name(node):
    if isinstance(node, ast.Call):
        f = node.func
        if isinstance(f, ast.Attribute):
            return f.attr
        if isinstance(f, ast.Name):
            return f.id
    return None


# ---- pass: jax_import_fence ---------------------------------------
def check_jax_import_fence(repo_dir: str) -> list:
    violations = []
    fenced = []
    for d in JAX_FREE_DIRS:
        full = os.path.join(repo_dir, d)
        if not os.path.isdir(full):
            violations.append(
                f"{d}: fenced jax-free package is missing — a "
                f"load-bearing subsystem was deleted"
            )
            continue
        fenced.extend(
            p for p in iter_py_files(repo_dir, (d,))
        )
    for f in JAX_FREE_FILES:
        full = os.path.join(repo_dir, f)
        if not os.path.exists(full):
            violations.append(
                f"{f}: fenced jax-free module is missing"
            )
            continue
        fenced.append(full)
    for path in dict.fromkeys(fenced):  # obs modules are named twice
        rel = os.path.relpath(path, repo_dir)
        tree, _src = _parse(path)
        for node in _module_scope(tree):
            mods = []
            if isinstance(node, ast.Import):
                mods = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                mods = [node.module or ""]
            for m in mods:
                if m.split(".")[0] in ("jax", "jaxlib"):
                    violations.append(
                        f"{rel}:{node.lineno}: imports {m!r} at "
                        f"module scope inside a jax-free fence — "
                        f"use a function-local import; this module "
                        f"must stay importable without the device "
                        f"runtime"
                    )
    return violations


# ---- pass: duplicate_dict_keys ------------------------------------
def check_duplicate_dict_keys(repo_dir: str) -> list:
    violations = []
    for path in iter_py_files(repo_dir):
        rel = os.path.relpath(path, repo_dir)
        tree, _src = _parse(path)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Dict):
                continue
            seen = set()
            for k in node.keys:
                if not isinstance(k, ast.Constant):
                    continue
                try:
                    key = k.value
                    if key in seen:
                        violations.append(
                            f"{rel}:{k.lineno}: duplicate key "
                            f"{key!r} in dict literal — Python "
                            f"silently keeps the LAST value; the "
                            f"first registration is dead (flag "
                            f"registry shadowing)"
                        )
                    seen.add(key)
                except TypeError:
                    continue
    return violations


# ---- pass: unfenced_timing ----------------------------------------
def _is_jit_binding(node):
    """`x = jax.jit(...)` / `x = jit(...)` / `x = <...>.compile()`"""
    if not (isinstance(node, ast.Assign)
            and isinstance(node.value, ast.Call)):
        return None
    name = _call_name(node.value)
    if name in ("jit", "compile"):
        return [
            t.id for t in node.targets if isinstance(t, ast.Name)
        ]
    return None


def check_unfenced_timing(repo_dir: str) -> list:
    violations = []
    subpaths = ("paddle_tpu", "tools")
    for path in iter_py_files(repo_dir, subpaths):
        if os.sep + "traces" + os.sep in path:
            continue
        rel = os.path.relpath(path, repo_dir)
        tree, _src = _parse(path)
        for fn in [n for n in ast.walk(tree)
                   if isinstance(n, (ast.FunctionDef,
                                     ast.AsyncFunctionDef))]:
            jitted = set()
            for n in ast.walk(fn):
                names = _is_jit_binding(n)
                if names:
                    jitted.update(names)
            if not jitted:
                continue
            has_clock = False
            has_fence = False
            calls_jitted = False
            for n in ast.walk(fn):
                nm = _call_name(n)
                if nm in _CLOCK_FNS:
                    has_clock = True
                if nm in _FENCE_FNS:
                    has_fence = True
                if (isinstance(n, ast.Call)
                        and isinstance(n.func, ast.Name)
                        and n.func.id in jitted):
                    calls_jitted = True
            if has_clock and calls_jitted and not has_fence:
                violations.append(
                    f"{rel}:{fn.lineno}: {fn.name}() times a jitted "
                    f"callable ({sorted(jitted)}) with no fence "
                    f"(block_until_ready/float/asarray/...) — the "
                    f"clock measures async DISPATCH, not execution"
                )
    return violations


# ---- pass: unlocked_mutation --------------------------------------
def _container_attrs(cls) -> set:
    """Attributes assigned a container literal/ctor in __init__ —
    the state the class's lock exists to guard."""
    out = set()
    for meth in cls.body:
        if not (isinstance(meth, ast.FunctionDef)
                and meth.name == "__init__"):
            continue
        for n in ast.walk(meth):
            if not isinstance(n, ast.Assign):
                continue
            is_container = isinstance(
                n.value, (ast.Dict, ast.List, ast.Set, ast.DictComp,
                          ast.ListComp, ast.SetComp)
            ) or _call_name(n.value) in _CONTAINER_CTORS
            if not is_container:
                continue
            for t in n.targets:
                if (isinstance(t, ast.Attribute)
                        and isinstance(t.value, ast.Name)
                        and t.value.id == "self"
                        and t.attr.startswith("_")):
                    out.add(t.attr)
    return out


class _LockedMutationVisitor(ast.NodeVisitor):
    def __init__(self, attrs):
        self.attrs = attrs
        self.depth = 0
        self.hits = []

    def _is_lock_item(self, item):
        e = item.context_expr
        return (
            isinstance(e, ast.Attribute)
            and e.attr in ("_lock", "_work")
            and isinstance(e.value, ast.Name)
            and e.value.id == "self"
        )

    def visit_With(self, node):
        locked = any(self._is_lock_item(i) for i in node.items)
        if locked:
            self.depth += 1
        self.generic_visit(node)
        if locked:
            self.depth -= 1

    def visit_Assign(self, node):
        if self.depth == 0:
            for t in node.targets:
                if (isinstance(t, ast.Subscript)
                        and isinstance(t.value, ast.Attribute)
                        and isinstance(t.value.value, ast.Name)
                        and t.value.value.id == "self"
                        and t.value.attr in self.attrs):
                    self.hits.append(
                        (node.lineno, t.value.attr, "[...]=")
                    )
        self.generic_visit(node)

    def visit_Delete(self, node):
        if self.depth == 0:
            for t in node.targets:
                if (isinstance(t, ast.Subscript)
                        and isinstance(t.value, ast.Attribute)
                        and isinstance(t.value.value, ast.Name)
                        and t.value.value.id == "self"
                        and t.value.attr in self.attrs):
                    self.hits.append(
                        (node.lineno, t.value.attr, "del")
                    )
        self.generic_visit(node)

    def visit_Call(self, node):
        f = node.func
        if (self.depth == 0
                and isinstance(f, ast.Attribute)
                and f.attr in _MUTATORS
                and isinstance(f.value, ast.Attribute)
                and f.value.attr in self.attrs
                and isinstance(f.value.value, ast.Name)
                and f.value.value.id == "self"):
            self.hits.append((node.lineno, f.value.attr, f.attr))
        self.generic_visit(node)


def check_unlocked_mutation(repo_dir: str) -> list:
    violations = []
    for path in iter_py_files(repo_dir):
        rel = os.path.relpath(path, repo_dir)
        tree, src = _parse(path)
        lines = src.splitlines()

        def suppressed(lineno):
            for ln in (lineno, lineno - 1):
                if 1 <= ln <= len(lines) and _PRAGMA in lines[ln - 1]:
                    return True
            return False

        for cls in [n for n in ast.walk(tree)
                    if isinstance(n, ast.ClassDef)]:
            has_lock = any(
                isinstance(n, ast.Assign) and any(
                    isinstance(t, ast.Attribute)
                    and t.attr == "_lock"
                    and isinstance(t.value, ast.Name)
                    and t.value.id == "self"
                    for t in n.targets
                )
                for n in ast.walk(cls)
            )
            if not has_lock:
                continue
            attrs = _container_attrs(cls)
            if not attrs:
                continue
            for meth in cls.body:
                if not isinstance(meth, ast.FunctionDef):
                    continue
                if (meth.name == "__init__"
                        or meth.name.endswith("_locked")):
                    continue
                v = _LockedMutationVisitor(attrs)
                v.visit(meth)
                for ln, attr, kind in v.hits:
                    if suppressed(ln):
                        continue
                    violations.append(
                        f"{rel}:{ln}: {cls.name}.{meth.name}() "
                        f"mutates self.{attr} ({kind}) outside "
                        f"`with self._lock` — races the locked "
                        f"readers; hold the lock, use a *_locked "
                        f"helper, or justify with `# {_PRAGMA}`"
                    )
    return violations


# ---- pass: raw_collective_outside_shard_map -----------------------
_FN_NODES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)


def _index_functions(tree):
    """(fn_node -> enclosing fn_node | None) for every def/lambda."""
    parent = {}

    def walk(node, enclosing):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, _FN_NODES):
                parent[child] = enclosing
                walk(child, child)
            else:
                walk(child, enclosing)

    walk(tree, None)
    return parent


def _is_raw_collective(node):
    """`lax.psum(...)` / `jax.lax.psum(...)` / bare `psum(...)` after
    `from jax.lax import psum`. Bare names are only trusted when the
    attribute chain is absent — a method named .psum on some other
    object still counts (no framework object has one; erring loud)."""
    if not isinstance(node, ast.Call):
        return None
    f = node.func
    if isinstance(f, ast.Attribute) and f.attr in _RAW_COLLECTIVES:
        v = f.value
        if (isinstance(v, ast.Name) and v.id == "lax") or (
            isinstance(v, ast.Attribute) and v.attr == "lax"
        ):
            return f.attr
    if isinstance(f, ast.Name) and f.id in _RAW_COLLECTIVES:
        return f.id
    return None


def _shard_map_roots(tree):
    """Function nodes / names handed to a `*shard_map(...)` call:
    direct `shard_map(f, ...)` args, inline lambdas, and
    `partial(f, ...)` wrappers."""
    root_nodes, root_names = set(), set()

    def claim(arg):
        if isinstance(arg, ast.Lambda):
            root_nodes.add(arg)
        elif isinstance(arg, ast.Name):
            root_names.add(arg.id)
        elif (isinstance(arg, ast.Call)
              and _call_name(arg) == "partial" and arg.args):
            claim(arg.args[0])

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node) or ""
        if not name.endswith("shard_map"):
            continue
        for arg in node.args:
            claim(arg)
        for kw in node.keywords:
            if kw.arg == "f":
                claim(kw.value)
    return root_nodes, root_names


def check_raw_collective_outside_shard_map(repo_dir: str) -> list:
    violations = []
    for path in iter_py_files(repo_dir):
        rel = os.path.relpath(path, repo_dir)
        tree, src = _parse(path)
        lines = src.splitlines()

        def suppressed(lineno):
            for ln in (lineno, lineno - 1):
                if (1 <= ln <= len(lines)
                        and _COLLECTIVE_PRAGMA in lines[ln - 1]):
                    return True
            return False

        # any raw collective in the file at all? (cheap early-out)
        hits = [
            (n, _is_raw_collective(n)) for n in ast.walk(tree)
            if _is_raw_collective(n)
        ]
        if not hits:
            continue

        parent = _index_functions(tree)
        root_nodes, root_names = _shard_map_roots(tree)
        by_name = {}
        for fn in parent:
            if not isinstance(fn, ast.Lambda):
                by_name.setdefault(fn.name, []).append(fn)

        covered = set(root_nodes)
        for nm in root_names:
            covered.update(by_name.get(nm, []))
        # fixpoint over two edge kinds: (a) lexical nesting — a def
        # inside a covered function runs under the same shard_map
        # (lax.cond/fori_loop branch callbacks); (b) same-file name
        # REFERENCE from a covered body — `local` calling (or merely
        # passing along) `_ring_body` extends the covered region.
        changed = True
        while changed:
            changed = False
            for fn, enc in parent.items():
                if fn not in covered and enc in covered:
                    covered.add(fn)
                    changed = True
            for fn in list(covered):
                for n in ast.walk(fn):
                    if not isinstance(n, ast.Name):
                        continue
                    for target in by_name.get(n.id, ()):
                        if target not in covered:
                            covered.add(target)
                            changed = True

        def enclosing(node):
            """Innermost fn the call sits in (parents map has only
            fn->fn edges, so walk the tree for the chain)."""
            chain = []

            def down(cur, stack):
                for child in ast.iter_child_nodes(cur):
                    if child is node:
                        chain.extend(stack)
                        return True
                    nxt = stack + [child] if isinstance(
                        child, _FN_NODES
                    ) else stack
                    if down(child, nxt):
                        return True
                return False

            down(tree, [])
            return chain[-1] if chain else None

        for call, kind in hits:
            if suppressed(call.lineno):
                continue
            fn = enclosing(call)
            if fn is not None and fn in covered:
                continue
            where = (
                "module scope" if fn is None else
                (fn.name if not isinstance(fn, ast.Lambda)
                 else f"<lambda>:{fn.lineno}") + "()"
            )
            violations.append(
                f"{rel}:{call.lineno}: raw lax.{kind} in {where} "
                f"which never flows into shard_map — the axis name "
                f"is unbound (or bound to the WRONG mesh axis under "
                f"someone else's pmap); wrap the caller in "
                f"jax.shard_map or justify with "
                f"`# {_COLLECTIVE_PRAGMA}`"
            )
    return violations


PASSES = {
    "jax_import_fence": check_jax_import_fence,
    "duplicate_dict_keys": check_duplicate_dict_keys,
    "unfenced_timing": check_unfenced_timing,
    "unlocked_mutation": check_unlocked_mutation,
    "raw_collective_outside_shard_map":
        check_raw_collective_outside_shard_map,
}


def run_passes(repo_dir: str, names=None) -> list:
    violations = []
    for name in (names or PASSES):
        violations.extend(
            f"[{name}] {v}" for v in PASSES[name](repo_dir)
        )
    return violations
