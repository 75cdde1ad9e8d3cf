"""`python -m paddle_tpu`: every subcommand the entry point keeps
builds its parser and prints its help in a process where importing
jax dies, and leaves no trace of jax behind. The entry point is
inside the jax import fence (analysis/ast_lint.py JAX_FREE_FILES);
this is the run-time half: a name left dangling in `main()` fails
every case here, whatever subcommand it belonged to.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SUBCOMMANDS = (
    "train", "dump_config", "merge_model", "infer", "master", "serve",
    "metrics", "fleetz", "make_diagram", "launch", "version",
)

_DRIVE = """
import contextlib, io, json, sys
from paddle_tpu.__main__ import main
out = {}
for argv in json.loads(sys.argv[1]):
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf), \\
                contextlib.redirect_stderr(buf):
            main(argv)
        code = "returned"
    except SystemExit as e:
        code = e.code
    out[argv[0]] = {
        "code": code, "said": buf.getvalue(),
        "jax": sorted(m for m in sys.modules
                      if m.split(".")[0] in ("jax", "jaxlib")),
    }
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def parsed(tmp_path_factory):
    """One child process with jax blocked parses every case."""
    tmp = tmp_path_factory.mktemp("nojax")
    for mod in ("jax", "jaxlib"):
        (tmp / f"{mod}.py").write_text(
            "raise ImportError('jax blocked for this test')\n")
    cases = [[name, "--help"] for name in SUBCOMMANDS] + [["bench"]]
    r = subprocess.run(
        [sys.executable, "-c", _DRIVE, json.dumps(cases)],
        env=dict(os.environ,
                 PYTHONPATH=str(tmp) + os.pathsep + REPO),
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr
    return json.loads(r.stdout.splitlines()[-1])


@pytest.mark.parametrize("name", SUBCOMMANDS)
def test_subcommand_parses_with_jax_blocked(parsed, name):
    got = parsed[name]
    assert got["code"] == 0, got
    assert f"usage: paddle {name}" in got["said"]
    assert got["jax"] == []


def test_bench_subcommand_is_refused(parsed):
    got = parsed["bench"]
    assert got["code"] == 2, got
    assert "invalid choice: 'bench'" in got["said"]
    # and what the parser offers instead is the eleven, no more
    assert f"(choose from {', '.join(SUBCOMMANDS)})" in got["said"]
