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
        if cell.workload["kind"] == "train":
            # the kind has one rate, named for no unit: the cell's file
            # says what a unit is
            assert cell.workload["rate_metric"] == "train_units_per_s"
            assert NAME.match(cell.traffic["count"]["unit"])
            assert "train_units_per_s" in {
                m["name"] for m in cell.metrics("end_to_end")}
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
        if cell.workload["kind"] == "train":
            assert {m["moves"] for m in cell.metrics("per_layer")} == {
                "train_units_per_s"}


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


# A training cell counted in tokens, as a later PR brings one: new files and
# entries appended to lists. The program is a graph of the program's own
# layers at toy sizes; the reference beside it imports nothing of it.
TOKEN_CELL = "toy_tokens.train_len4to8"
TOKEN_FILES = {
    "configs/toy_tokens.json": json.dumps({
        "name": "toy_tokens", "model": "toy_tokens", "vocab": 50,
        "emb_dim": 16, "num_classes": 4, "param_dtype": "float32",
        "matmul_precision": "float32", "reduced": [],
        "optimizer": {"method": "adam", "learning_rate": 0.01, "beta1": 0.9,
                      "beta2": 0.999, "epsilon": 1e-8}}),
    f"workloads/{TOKEN_CELL}.json": json.dumps({
        "kind": "train", "rate_metric": "train_units_per_s", "mesh": None,
        "traffic": {
            "batch": 8, "pool": 16, "lengths": {"words": [4, 8]},
            "slots": [{"name": "words", "type": "ids_seq", "vocab": 50,
                       "len": "words"},
                      {"name": "label", "type": "ids", "vocab": 4}],
            "count": {"unit": "tokens", "length_group": "words"}},
        "limits": {"loss1_gap": 1e-4, "grad1_gap": 1e-3,
                   "delta_gap": 1e-3}}),
    "layer_metrics/mfu.tokens.json": json.dumps({"reader": "mfu"}),
    "models/toy_tokens.py": """
from benchmarks.reference import toy_tokens as reference


def program_conf(cfg):
    from paddle_tpu import dsl

    with dsl.model() as g:
        ids = dsl.data("words", (1,), is_seq=True, is_ids=True)
        lbl = dsl.data("label", (1,), is_ids=True)
        h = dsl.embedding(ids, size=cfg["emb_dim"], vocab_size=cfg["vocab"],
                          name="emb")
        out = dsl.fc(dsl.seq_pool(h, pool_type="average"),
                     size=cfg["num_classes"], name="output")
        dsl.classification_cost(out, lbl)
        g.conf.output_layer_names.append("output")
    return g.conf


def reference_batch(cols):
    return cols


def train_flops_per_row(cfg, traffic):      # a row is a token here
    return 6.0 * cfg["emb_dim"] * cfg["num_classes"]
""",
    "reference/toy_tokens.py": """
import jax
import jax.numpy as jnp


def param_spec(cfg):
    v, e, c = cfg["vocab"], cfg["emb_dim"], cfg["num_classes"]
    return {"_emb.w0": ((v, e), ("normal", 1.0)),
            "_output.w0": ((e, c), ("normal", e ** -0.5)),
            "_output.wbias": ((c,), ("const", 0.0))}


def loss(cfg, p, b, mode="f32"):
    lens = b["words_lens"]
    real = jnp.arange(b["words"].shape[1])[None, :] < lens[:, None]
    pooled = jnp.sum(p["_emb.w0"][b["words"]] * real[..., None],
                     axis=1) / lens[:, None]
    logits = jnp.dot(pooled, p["_output.w0"],
                     precision="highest") + p["_output.wbias"]
    picked = jnp.take_along_axis(logits, b["label"][:, None], axis=1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=1) - picked)
"""}


def _tree(top):
    out = {}
    for d, _dirs, files in os.walk(top):
        if "__pycache__" in d:
            continue
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, top)] = fh.read()
    return out


def test_a_token_counted_training_cell_is_new_files_and_appended_entries(
        tmp_path):
    """`train_units_per_s` is the kind's one rate: a cell counted in tokens
    joins it by appending its name to the metric's `workloads`, brings its
    own files, and edits none that is there. Measured here on the CPU, so
    the rate is held to its arithmetic alone."""
    from benchmarks import traffic

    root = tmp_path / "repo"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = harness.load_benchmark()
    bench = json.loads(json.dumps(before))
    bench["configs"].append({
        "name": "toy_tokens", "source": "this test", "reduced": [],
        "file": "benchmarks/configs/toy_tokens.json", "why": "test"})
    bench["workloads"].append({
        "name": TOKEN_CELL, "config": "toy_tokens", "chips": 1,
        "traffic": "train_len4to8", "why": "test"})
    bench["per_layer"].append({
        "name": "mfu.tokens", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "step program and model graph",
        "moves": "train_units_per_s", "workloads": [TOKEN_CELL]})
    rate = {m["name"]: m for m in bench["end_to_end"]}["train_units_per_s"]
    rate["workloads"].append(TOKEN_CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    for rel, text in TOKEN_FILES.items():
        (root / "benchmarks" / rel).write_text(text)

    code = (
        "import json, sys; sys.path[:0] = [%r, %r]\n"
        "import jax\n"
        "from benchmarks import harness, run\n"
        "assert harness.ROOT == %r, harness.ROOT\n"
        "cell = harness.Cell(%r)\n"
        "res = run.measure(cell, 2 ** 31 + 5, 1.0, False,\n"
        "                  jax.devices()[:1], peak={'bf16_flops': 1e12})\n"
        "print(json.dumps(res))\n" % (str(root), ROOT, str(root), TOKEN_CELL))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=str(root),
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(x) for x in out.stdout.splitlines()
             if x.startswith("{")]
    log = {k: v for line in lines[:-1] for k, v in line.items()}
    res = lines[-1]
    assert res["correct"], (res["compared"], res["problems"])
    assert set(res["metrics"]) == {"train_units_per_s", "setup_s"}
    assert res["metrics"]["train_units_per_s"]["unit"] == "units/s"
    # an earlier line of the log says what a unit is in this cell
    assert log["counts"] == "tokens" and log["cell"] == TOKEN_CELL
    # the rate: the real tokens of the steps that ended, over the window
    cell = harness.Cell(TOKEN_CELL, root=str(root))
    pool = traffic.Pool(cell.traffic, 2 ** 31 + 5)
    steps = log["steps_in_window"]
    tokens = sum(pool.count(rows, cell.traffic["count"])
                 for rows, _ in zip(pool.batches(1), range(steps)))
    assert steps == res["attempted"] > 0 and log["work"] == tokens
    assert tokens != steps * cell.traffic["batch"]      # tokens, not rows
    assert res["metrics"]["train_units_per_s"]["value"] == pytest.approx(
        tokens / log["window_s"], rel=1e-12)

    # the listing of the temporary tree against the original: every file
    # that was there is there unchanged, and the cell's own are new
    was, now = _tree(os.path.join(ROOT, "benchmarks")), _tree(
        root / "benchmarks")
    assert {f for f in was if now.get(f) != was[f]} == set()
    assert set(now) - set(was) == set(TOKEN_FILES)
    # and in BENCHMARK.json every list only grew at its end
    after = json.loads((root / "BENCHMARK.json").read_text())
    grown = json.loads(json.dumps(after))
    {m["name"]: m for m in grown["end_to_end"]}[
        "train_units_per_s"]["workloads"].pop()
    for key, value in before.items():
        if isinstance(value, list) and key not in ("command", "paths"):
            assert grown[key][:len(value)] == value, key
            assert len(after[key]) - len(value) == {
                "configs": 1, "workloads": 1, "per_layer": 1}.get(key, 0)
        else:
            assert after[key] == value, key


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
    """Held over the metrics it names: a later PR appends others, whose
    readers need only read nothing where nothing is."""
    cell = harness.Cell("resnet50.train_bs256")
    run = {"flops": 3e12, "window_s": 4.0, "chips": 1,
           "peak": {"bf16_flops": 1e12},
           "spans": {"reader": 0.5, "feeder": 1.5},
           "trace": {"busy_s": 1.5, "window_s": 4.0, "idle_share": 0.625}}
    got = {}
    for m in cell.metrics("per_layer"):
        reader, data = cell.layer_metric(m["name"])
        got[m["name"]] = reader.read(run, data)
        bare = dict(run, trace=None, spans={}, flops=0)
        assert reader.read(bare, data) is None, m["name"]
    want = {"input_wait_share.images": 50.0,
            "mfu.images": pytest.approx(200.0),   # 3e12/1.5 s/1e12
            "device_idle_share.images": 62.5}
    assert {n: got[n] for n in want} == want
    # the others read the trace's file, and this run has none
    assert not any(v is not None for n, v in got.items() if n not in want)


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
