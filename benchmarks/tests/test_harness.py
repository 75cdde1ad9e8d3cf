"""The harness is driven by data, says what the contract fixes, refuses to
measure without a chip, and reduces a trace rightly."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmarks import harness, trace_reduce

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark()


def test_named_files_exist_and_names_are_allowed(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and os.path.isfile(
            os.path.join(ROOT, c["file"]))
        assert json.load(open(os.path.join(ROOT, c["file"])))["reduced"] \
            == c["reduced"]
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["name"] == f'{w["config"]}.{w["traffic"]}'
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        cell = harness.Cell(w["name"])
        assert cell.kind.run and cell.model.program_conf
        assert cell.limits, f"{w['name']} compares nothing"
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in bench["per_layer"]:
        reader, _ = harness.Cell(m["workloads"][0]).layer_metric(m["name"])
        assert callable(reader.read)
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])


def test_each_layer_metric_moves_a_metric_its_cells_report(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    layers = {}
    for m in bench["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in cells
            assert cell in moved.get("workloads", cells), (m["name"], cell)
        layers.setdefault(m["layer"], []).append(m["name"])
    for w in bench["workloads"]:
        cell = harness.Cell(w["name"])
        assert len(cell.metrics("end_to_end")) >= 2
        assert cell.metrics("per_layer")
        assert any("mfu" in m["name"].split(".")
                   for m in cell.metrics("per_layer"))


def test_new_cell_kind_and_reader_are_found_as_new_files(tmp_path):
    """A later PR adds files and entries and edits no file that is there."""
    root = tmp_path / "repo"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = harness.load_benchmark()
    bench["workloads"].append({"name": "resnet50.echo", "config": "resnet50",
                               "traffic": "echo", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "calls.echo", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "entry points",
        "moves": "setup_s", "workloads": ["resnet50.echo"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    b = root / "benchmarks"
    (b / "workloads" / "resnet50.echo.json").write_text(json.dumps(
        {"kind": "echo", "rate_metric": "setup_s", "traffic": {"n": 3},
         "limits": {"x": 1}}))
    (b / "kinds" / "echo.py").write_text(
        "def run(ctx):\n    return {'counters': {'calls': "
        "[ctx.cell.traffic['n']]}}\n")
    (b / "layer_metrics" / "calls.echo.json").write_text(
        json.dumps({"reader": "last_counter", "counter": "calls"}))
    (b / "layer_metrics" / "last_counter.py").write_text(
        "def read(run, args):\n    return run['counters'][args['counter']]"
        "[-1]\n")
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from benchmarks import harness\n"
        "assert harness.ROOT == %r, harness.ROOT\n"
        "cell = harness.Cell('resnet50.echo')\n"
        "class Ctx: pass\n"
        "ctx = Ctx(); ctx.cell = cell\n"
        "run = cell.kind.run(ctx)\n"
        "(m,) = cell.metrics('per_layer')\n"
        "reader, data = cell.layer_metric(m['name'])\n"
        "print(reader.read(run, data))\n" % (str(root), str(root)))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=str(root))
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "3"


def test_last_line_has_the_contracts_keys():
    line = json.loads(harness.last_line(
        True, 4, 0, {"setup_s": {"value": 1.5, "unit": "s"}},
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
         "memory_peak_bytes": 7}, [["loss1_gap", 1e-4, 1e-3]],
        {"device_ops": [], "idle_gaps": []}))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "breakdown", "compared"]
    line = json.loads(harness.last_line(False, 0, 0, {}, {}, []))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]


def test_timed_entry_refuses_to_run_without_a_tpu(bench):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", bench["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT)
    assert out.returncode != 0
    assert "not a TPU" in out.stderr
    assert '"correct"' not in out.stdout


def test_unknown_device_kind_is_an_error():
    assert harness.peak_of("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(SystemExit):
        harness.peak_of("TPU v99")


def test_trace_reduce_on_a_hand_built_trace():
    ops = {"/device:TPU:0": [("conv", 0, 40), ("fusion", 30, 50),
                             ("conv", 70, 90), ("late", 120, 130)],
           "/device:TPU:1": [("conv", 0, 20)]}
    spans = {"feeder": [(50, 68)], "run_step": [(68, 100)],
             "reader": [(90, 100)]}
    r = trace_reduce.reduce_events(ops, spans, (0, 100),
                                   ["reader", "feeder", "run_step"])
    assert r["window"] == 100
    assert r["busy"] == (70 + 20) / 2            # overlap counted once
    assert r["idle_share"] == pytest.approx(0.30)  # the fullest chip's
    assert r["device_ops"][0] == ("conv", 60) and \
        r["device_ops"][1] == ("fusion", 20)
    assert r["idle_gaps"] == [("feeder", 20), ("reader", 10)]
    assert trace_reduce.reduce_events({}, {}, (0, 1), []) is None
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]


def test_readers_read_the_run_and_return_nothing_where_nothing_is():
    cell = harness.Cell("resnet50.train_bs256")
    run = {"flops": 3e12, "window_s": 4.0, "chips": 1,
           "peak": {"bf16_flops": 1e12},
           "spans": {"reader": 0.5, "feeder": 1.5, "run_step": 2.0},
           "trace": {"busy_s": 1.5, "window_s": 4.0, "idle_share": 0.625}}
    got = {}
    for m in cell.metrics("per_layer"):
        reader, data = cell.layer_metric(m["name"])
        got[m["name"]] = reader.read(run, data)
        bare = dict(run, trace=None, spans={}, flops=0)
        assert reader.read(bare, data) is None, m["name"]
    assert got == {"input_wait_share.images": 50.0,
                   "mfu.images": pytest.approx(200.0),   # 3e12/1.5 s/1e12
                   "device_idle_share.images": 62.5}


def test_analytic_flops_equal_their_closed_forms():
    res = harness.Cell("resnet50.train_bs256")
    per_image = res.model.train_flops_per_row(res.config, res.traffic)
    # 3 (forward and backward) x 2 per multiply-add x 3.86 GMAC: the
    # paper's "3.8e9 multiply-adds" for v1, stride on the first 1x1, which
    # is the program's graph. bench.py's 24.6e9 is the v1.5 graph's count.
    assert per_image == 6 * 3857973248


SEQ_TRAFFIC = {
    "batch": 512, "pool": 2048,
    "lengths": {"src": [16, 32], "trg": [16, 32]},
    "slots": [
        {"name": "src", "type": "ids_seq", "vocab": 30000, "min_id": 2,
         "len": "src"},
        {"name": "trg_in", "type": "ids_seq", "vocab": 30000, "min_id": 2,
         "len": "trg"},
        {"name": "trg_out", "type": "ids_seq", "vocab": 30000, "min_id": 2,
         "len": "trg"}],
    "count": {"unit": "tokens", "length_group": "trg"}}


def test_traffic_gives_every_seed_the_same_sizes():
    """Sequence traffic (no cell sends it yet; a later cell brings it as a
    data file): every seed the same multiset of lengths in another order,
    one padded shape, every epoch the same rows."""
    from benchmarks import traffic

    t = SEQ_TRAFFIC
    a, b = traffic.Pool(t, 3), traffic.Pool(t, 2 ** 31 + 11)
    for g in a.lengths:
        assert sorted(a.lengths[g]) == sorted(b.lengths[g])
        assert (a.lengths[g] != b.lengths[g]).any()
    rows = next(a.batches(1))
    assert len(set(rows.tolist())) == t["batch"]
    cols = a.arrays(rows)
    assert cols["src"].shape == (512, 32) and cols["trg_in"].shape == (512, 32)
    assert a.count(rows, t["count"]) == int(cols["trg_in_lens"].sum())
    epoch = [r for r, _ in zip(a.batches(1), range(a.per_epoch))]
    assert sorted(i for r in epoch for i in r.tolist()) == list(range(2048))
    again = traffic.Pool(t, 3)
    assert (again.arrays(rows)["src"] == cols["src"]).all()
    img = harness.Cell("resnet50.train_bs256").traffic
    assert img["batch"] == 256 and img["pool"] == 512
    assert img["slots"][0]["dim"] == 224 * 224 * 3


def test_sequence_slots_feed_and_adam_follows_its_closed_form():
    """What the next cells bring as data and no cell uses yet: sequence
    slots through the program's DataFeeder, and the reference's Adam."""
    import jax.numpy as jnp

    from benchmarks import traffic
    from benchmarks.kinds import train as T
    from benchmarks.reference import train as ref_train

    small = dict(SEQ_TRAFFIC, batch=4, pool=8)
    pool = traffic.Pool(small, 5)
    rows = next(pool.batches(0))
    fed = T.feeder_for(small["slots"])([pool.sample(i) for i in rows])
    assert set(fed) >= {"src", "trg_in", "trg_out"}
    opt = {"method": "adam", "learning_rate": 0.01, "beta1": 0.9,
           "beta2": 0.999, "epsilon": 1e-8}
    out = ref_train.first_steps(
        lambda p, b: jnp.sum(p["w"] * b["x"]), opt,
        {"w": jnp.ones((3,), jnp.float32)},
        [{"x": jnp.array([1.0, -2.0, 4.0])}] * 3)
    # a constant gradient: Adam moves every weight by lr a step, and the
    # first gradient read back from its state is the gradient
    assert out["delta"]["w"] == pytest.approx(0.03 * 3 ** 0.5, rel=1e-4)
    assert out["grad1"]["w"] == pytest.approx(21 ** 0.5, rel=1e-6)
    state = {"m": 0.1 * jnp.array([1.0, -2.0, 4.0])}
    g = ref_train.first_gradient_from_state(opt, state)
    assert jnp.allclose(g, jnp.array([1.0, -2.0, 4.0]))


def test_entry_refuses_where_only_the_benchmark_is(tmp_path, bench):
    """In a directory that holds only BENCHMARK.json and `paths` there is
    no program to measure: another exit code than 0, and no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         bench["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, cwd=str(tmp_path))
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
