"""tools/framework_lint.py — the static-analysis driver (ISSUE 13).

Pins: the driver runs green on THIS tree with jax blocked (the passes
are pure stdlib), every AST pass actually bites on a seeded
violation, and run_suite.sh really wires the driver in (fast tier
before the shards, HLO audit after, lock checking on the faults
shard).
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from paddle_tpu.analysis import ast_lint  # noqa: E402


def _run(args, **kw):
    return subprocess.run(
        [sys.executable, "tools/framework_lint.py", *args],
        cwd=REPO, capture_output=True, text=True, timeout=300, **kw,
    )


class TestDriver:
    def test_all_green_on_tree_with_jax_blocked(self):
        """The acceptance pin: `framework_lint.py --all` passes on
        the committed tree, in a process where importing jax dies —
        every pass over the tree (AST, hlo-audit, spmd-audit) is
        jax-free."""
        code = (
            "import sys\n"
            "sys.modules['jax'] = None\n"
            "sys.argv = ['framework_lint', '--all']\n"
            "sys.path.insert(0, 'tools')\n"
            "import framework_lint\n"
            "rc = framework_lint.main(['--all'])\n"
            "assert rc == 0, rc\n"
            "print('LINT-OK')\n"
        )
        r = subprocess.run(
            [sys.executable, "-c", code], cwd=REPO,
            capture_output=True, text=True, timeout=300,
        )
        assert r.returncode == 0, r.stdout + r.stderr
        assert "LINT-OK" in r.stdout

    def test_fast_tier_green(self):
        r = _run(["--fast"])
        assert r.returncode == 0, r.stdout + r.stderr
        assert "OK" in r.stdout

    def test_list_and_usage(self):
        r = _run(["--list"])
        assert r.returncode == 0
        assert r.stdout.split() == [
            "ast", "hlo-audit", "spmd-audit", "bundle"
        ]
        r = _run([])
        assert r.returncode == 2
        r = _run(["no-such-pass"])
        assert r.returncode == 2

    def test_violation_exits_1(self, tmp_path):
        """A seeded violation in a scratch repo fails the driver (the
        lint bites through the CLI, not only via the library)."""
        self._scaffold(tmp_path)
        (tmp_path / "paddle_tpu" / "obs" / "bad.py").write_text(
            "import jax\n"
        )
        r = _run(["ast", "--repo", str(tmp_path)])
        assert r.returncode == 1
        assert "jax" in r.stderr

    def _scaffold(self, tmp_path):
        """Minimal tree satisfying the fence-existence checks."""
        for d in ast_lint.JAX_FREE_DIRS:
            (tmp_path / d).mkdir(parents=True, exist_ok=True)
        for f in ast_lint.JAX_FREE_FILES:
            p = tmp_path / f
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text("x = 1\n")


class TestAstPasses:
    def _scaffold(self, tmp_path):
        TestDriver._scaffold(self, tmp_path)

    def test_tree_is_clean(self):
        assert ast_lint.run_passes(REPO) == []

    def test_jax_import_fence_bites(self, tmp_path):
        self._scaffold(tmp_path)
        (tmp_path / "paddle_tpu" / "serving" / "bad.py").write_text(
            "from jaxlib import xla_client\n"
        )
        v = ast_lint.check_jax_import_fence(str(tmp_path))
        assert len(v) == 1 and "bad.py:1" in v[0]
        # module scope includes try/if blocks: whatever runs at
        # import time; a function's own import is lazy and fine
        (tmp_path / "paddle_tpu" / "serving" / "bad.py").write_text(
            "try:\n    import jax.numpy as jnp\nexcept ImportError:\n"
            "    jnp = None\n"
            "def ok():\n    import jax\n"
        )
        v = ast_lint.check_jax_import_fence(str(tmp_path))
        assert len(v) == 1 and "bad.py:2" in v[0]

    def test_jax_import_fence_flags_deleted_zone(self, tmp_path):
        self._scaffold(tmp_path)
        import shutil

        shutil.rmtree(tmp_path / "paddle_tpu" / "obs")
        v = ast_lint.check_jax_import_fence(str(tmp_path))
        assert any("paddle_tpu/obs" in x and "missing" in x for x in v)
        # one module of it deleted is named too (the package's
        # required modules are in JAX_FREE_FILES)
        self._scaffold(tmp_path)
        os.remove(tmp_path / "paddle_tpu" / "obs" / "tracing.py")
        v = ast_lint.check_jax_import_fence(str(tmp_path))
        assert len(v) == 1 and "missing" in v[0]
        assert "paddle_tpu/obs/tracing.py" in v[0]

    def test_function_local_jax_import_ok(self, tmp_path):
        self._scaffold(tmp_path)
        (tmp_path / "paddle_tpu" / "obs" / "lazy.py").write_text(
            "def f():\n    import jax\n    return jax\n"
        )
        assert ast_lint.check_jax_import_fence(str(tmp_path)) == []

    def test_duplicate_dict_keys_bites(self, tmp_path):
        self._scaffold(tmp_path)
        (tmp_path / "paddle_tpu" / "flags2.py").write_text(
            "_DEFAULTS = {\n"
            "    'seed': 0,\n"
            "    'log_period': 100,\n"
            "    'seed': 1,\n"
            "}\n"
        )
        v = ast_lint.check_duplicate_dict_keys(str(tmp_path))
        assert len(v) == 1 and "'seed'" in v[0]

    def test_unfenced_timing_bites(self, tmp_path):
        self._scaffold(tmp_path)
        (tmp_path / "paddle_tpu" / "badtiming.py").write_text(
            "import time\n"
            "def measure(jax, x):\n"
            "    f = jax.jit(lambda v: v + 1)\n"
            "    t0 = time.perf_counter()\n"
            "    f(x)\n"
            "    return time.perf_counter() - t0\n"
        )
        v = ast_lint.check_unfenced_timing(str(tmp_path))
        assert len(v) == 1 and "measure" in v[0]

    def test_fenced_timing_clean(self, tmp_path):
        self._scaffold(tmp_path)
        (tmp_path / "paddle_tpu" / "goodtiming.py").write_text(
            "import time\n"
            "def measure(jax, x):\n"
            "    f = jax.jit(lambda v: v + 1)\n"
            "    t0 = time.perf_counter()\n"
            "    jax.block_until_ready(f(x))\n"
            "    return time.perf_counter() - t0\n"
        )
        assert ast_lint.check_unfenced_timing(str(tmp_path)) == []

    def test_raw_collective_outside_shard_map_bites(self, tmp_path):
        self._scaffold(tmp_path)
        (tmp_path / "paddle_tpu" / "badcoll.py").write_text(
            "from jax import lax\n"
            "from paddle_tpu.core.mesh import shard_map\n"
            "def merge_grads(g):\n"
            "    return lax.psum(g, 'data')\n"
            "def ring_root(x):\n"
            "    return lax.ppermute(x, 'seq', [(0, 1), (1, 0)])\n"
            "def use(mesh, x):\n"
            "    return shard_map(ring_root, mesh=mesh,\n"
            "                     in_specs=(), out_specs=())(x)\n"
            "def excused(g):\n"
            "    # lint: raw-collective-ok — pmap-era bridge\n"
            "    return lax.psum(g, 'batch')\n"
        )
        v = ast_lint.check_raw_collective_outside_shard_map(
            str(tmp_path)
        )
        assert len(v) == 1, v
        assert "merge_grads" in v[0] and "lax.psum" in v[0]

    def test_raw_collective_nesting_and_reference_closure(
        self, tmp_path
    ):
        """The covered region closes over same-file name references
        (root -> helper) and lexical nesting (fori_loop callbacks) —
        the shapes ring.py actually uses."""
        self._scaffold(tmp_path)
        (tmp_path / "paddle_tpu" / "ringlike.py").write_text(
            "from jax import lax\n"
            "from paddle_tpu.core.mesh import shard_map\n"
            "def _body(axis, x):\n"
            "    def step(i, c):\n"
            "        def rotate(kv):\n"
            "            return lax.ppermute(kv, axis, [(0, 1)])\n"
            "        return lax.cond(i < 3, rotate, lambda k: k, c)\n"
            "    return lax.fori_loop(0, 4, step, x)\n"
            "def attn(mesh, axis, x):\n"
            "    def local(x):\n"
            "        return _body(axis, x)\n"
            "    return shard_map(lambda a: local(a), mesh=mesh,\n"
            "                     in_specs=(), out_specs=())(x)\n"
        )
        assert ast_lint.check_raw_collective_outside_shard_map(
            str(tmp_path)
        ) == []

    def test_unlocked_mutation_bites_and_pragma(self, tmp_path):
        self._scaffold(tmp_path)
        (tmp_path / "paddle_tpu" / "racy.py").write_text(
            "import threading\n"
            "class R:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._d = {}\n"
            "    def bad(self, k, v):\n"
            "        self._d[k] = v\n"
            "    def good(self, k, v):\n"
            "        with self._lock:\n"
            "            self._d[k] = v\n"
            "    def justified(self, k):\n"
            "        # lint: unlocked-ok — test pragma\n"
            "        self._d.pop(k, None)\n"
            "    def _helper_locked(self, k, v):\n"
            "        self._d[k] = v\n"
        )
        v = ast_lint.check_unlocked_mutation(str(tmp_path))
        assert len(v) == 1, v
        assert "R.bad()" in v[0] and "_d" in v[0]


class TestSuiteWiring:
    def test_run_suite_wires_framework_lint(self):
        """CI satellite pin: the fast tier gates the shards, the HLO
        audit runs after them, and the faults shard instruments the
        known locks."""
        sh = open(
            os.path.join(REPO, "tests", "run_suite.sh")
        ).read()
        import framework_lint

        # the fast tier is the AST passes alone, and the script runs
        # no pass that the driver no longer has
        assert framework_lint.FAST_PASSES == ("ast",)
        assert "bench" not in sh
        assert "framework_lint.py --fast" in sh
        assert "framework_lint.py hlo-audit" in sh
        assert "framework_lint.py spmd-audit" in sh
        assert "PADDLE_LOCK_CHECK=1" in sh
        # ordering: fast gate before the shard loop, audits after
        assert sh.index("framework_lint.py --fast") < sh.index(
            "for ((i = 0"
        )
        assert sh.index("framework_lint.py hlo-audit") > sh.index(
            "-m faults"
        )
        assert sh.index("framework_lint.py spmd-audit") > sh.index(
            "framework_lint.py hlo-audit"
        )

    def test_committed_audit_reports_exist(self):
        budgets = json.load(open(os.path.join(
            REPO, "tools", "traces", "audit_budgets.json"
        )))
        stems = [s for s in budgets if not s.startswith("_")]
        # 4 single-device stems (ISSUE 13) + 5 SPMD mc_* stems
        # (ISSUE 15)
        assert len(stems) >= 9
        for stem in stems:
            assert os.path.exists(os.path.join(
                REPO, "tools", "traces", stem + ".audit.json"
            )), f"{stem}.audit.json missing"

    def test_capture_without_audit_report_fails_the_audit_pass(
        self, tmp_path, capsys
    ):
        """A capture the budgets name with no sibling audit.json is a
        violation of the audit pass that owns it; writing the report
        clears it."""
        import shutil

        import framework_lint

        src = os.path.join(REPO, "tools", "traces")
        traces = tmp_path / "tools" / "traces"
        traces.mkdir(parents=True)
        stem = "mc_sparse_lookup"
        for ext in (".hlo.txt.gz", ".report.json"):
            shutil.copy(os.path.join(src, stem + ext), str(traces))
        budgets = json.load(open(
            os.path.join(src, "audit_budgets.json")))
        (traces / "audit_budgets.json").write_text(
            json.dumps({stem: budgets[stem]}))
        argv = ["spmd-audit", "--repo", str(tmp_path)]
        assert framework_lint.main(argv) == 1
        err = capsys.readouterr().err
        assert f"{stem}: no committed audit report" in err
        assert framework_lint.main(argv + ["--write-audit"]) == 0
        assert framework_lint.main(argv) == 0
