"""The cell `mellum2_12b_ep4.train_seq8192`: its files loaded as the harness
finds them and driven at a tiny size on the CPU through `run.measure`, a
sound run held to `correct` true and the fp8 control and a planted fault to
`correct` false; the analytic counts held to ISSUE 28's arithmetic at the
published widths. Widths shrink here and nowhere else."""

import copy
import sys

import jax
import pytest

from benchmarks import compare, harness, run as R, traffic
from benchmarks.kinds import train as T
from benchmarks.tests import tiny
from benchmarks.tests.test_correct import Broken, _half_batch, _state_unchanged

CELL = "mellum2_12b_ep4.train_seq8192"
SEED = 2 ** 31 + 28


def tiny_cell():
    """Hidden 64, 4 heads on 2 KV heads of 16, experts 2-5 of 8 held, top-2,
    window 8, T 32, the 4 layers of the published pattern, 2 rows a step."""
    cell = harness.Cell(CELL)
    cell.config = copy.deepcopy(cell.config)
    cell.workload = copy.deepcopy(cell.workload)
    cell.traffic = cell.workload["traffic"]
    cell.config.update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, moe_intermediate_size=32, num_experts=4,
        router_experts=8, experts_held_first=2, num_experts_per_tok=2,
        vocab_size=96, sliding_window=8, head_chunk_rows=16,
        # float32 on the CPU: the reference's own precision, so that a
        # sound run reads rounding and a fault reads as itself
        matmul_precision="float32")
    cell.config["rope_parameters"]["full_attention"][
        "original_max_position_embeddings"] = 16
    cell.traffic.update(pool=8, lengths={"seq": [32, 32]})
    for slot in cell.traffic["slots"]:
        slot["vocab"] = 96
    return cell


def measure(cell):
    return R.measure(cell, SEED, 0.5, False, jax.devices()[:1],
                     peak=tiny.PEAK)


def test_the_cells_files_are_found_and_say_what_the_issue_says():
    cell = harness.Cell(CELL)
    cfg = cell.config
    assert cell.chips == 1 and cell.workload["kind"] == "train"
    assert cell.traffic["count"] == {"unit": "tokens", "length_group": "seq"}
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["sliding_window"]) == (2304, 32, 4, 128, 896, 8, 1024)
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["router_experts"], cfg["vocab_size"]) == (4, 16, 64, 24576)
    assert cfg["layer_types"] == ["sliding_attention"] * 3 + ["full_attention"]
    spec = cell.model.reference.param_spec(cfg)
    count = 0
    for shape, _ in spec.values():
        n = 1
        for s in shape:
            n *= s
        count += n
    assert count == cfg["deployment"]["parameters_held"]["all"] == 595153152
    # the metrics the cell reports, each with a reader and a data file
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert "moe_gmm_roofline.tokens" in names and len(names) == 12
    for name in names:
        reader, data = cell.layer_metric(name)
        assert callable(reader.read)


def test_analytic_counts_are_the_issues_arithmetic():
    cell = harness.Cell(CELL)
    ref = cell.model.reference
    parts = ref.forward_flops_per_token(cell.config, 8192)
    # per token forward: projections 42.5M a layer; scores 15.7M a window
    # layer and 67.1M the full one; held experts 24.8M a layer; head 113.2M
    assert parts["projections"] / 4 == pytest.approx(42.5e6, rel=2e-3)
    assert parts["experts"] / 4 == pytest.approx(24.8e6, rel=2e-3)
    assert parts["head"] == pytest.approx(113.2e6, rel=2e-3)
    window = 4 * 32 * 128 * ref.attended_keys(8192, 1024) / 8192
    full = 4 * 32 * 128 * ref.attended_keys(8192, None) / 8192
    assert window == pytest.approx(15.7e6, rel=5e-3)
    assert full == pytest.approx(67.1e6, rel=5e-3)
    assert parts["attention"] == pytest.approx(3 * window + full)
    per_token = cell.model.train_flops_per_row(cell.config, cell.traffic)
    assert per_token == pytest.approx(1.49e9, rel=1e-2)


def test_the_kernels_costs_are_the_models_work_and_read_nothing_of_the_program(
        monkeypatch):
    """What a roofline share is a share of: mask-exact pairs, 2 matmuls
    forward and 5 backward; 3 products a projection; no recomputation,
    though the configuration asks for it; and no module of the program."""
    cell = harness.Cell(CELL)
    assert cell.config["recompute"] == "block"
    for name in [m for m in sys.modules if m.startswith("paddle_tpu")]:
        monkeypatch.setitem(sys.modules, name, None)   # an import raises
    monkeypatch.setitem(sys.modules, "paddle_tpu", None)
    parts = cell.model.reference.forward_flops_per_token(cell.config, 8192)
    attn = cell.model.window_attention_cost(cell.config, cell.traffic)
    assert attn["flops"] == pytest.approx(
        16384 * parts["attention"] / 2 * 7)
    assert attn["flops"] == pytest.approx(6.55e12, rel=5e-3)
    # q, o and their gradients 134 MB each, k, v and theirs 16.8 MB
    assert attn["bytes"] == pytest.approx(
        4 * 6 * (16384 * 32 * 128 * 2 + 16384 * 4 * 128 * 2))
    gmm = cell.model.moe_gmm_cost(cell.config, cell.traffic)
    assert gmm["flops"] == pytest.approx(16384 * 3 * parts["experts"])
    assert gmm["flops"] == pytest.approx(4.87e12, rel=5e-3)
    rows = 16384 * 8 * 16 / 64
    a_call = (rows * (2304 + 896) + 16 * 2304 * 896) * 2
    assert gmm["bytes"] == pytest.approx(4 * 3 * 3 * a_call)


def test_sound_run_is_correct():
    res = measure(tiny_cell())
    assert res["correct"], (res["compared"], res["problems"])
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"train_units_per_s", "setup_s"}


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch])
def test_training_fault_is_not_correct(fault, monkeypatch):
    build = T.build_trainer

    def broken(*args, **kw):
        trainer = build(*args, **kw)
        trainer.step_fn = Broken(trainer.step_fn, fault)
        return trainer

    monkeypatch.setattr(T, "build_trainer", broken)
    res = measure(tiny_cell())
    assert not res["correct"]
    assert [n for n, v, lim in res["compared"] if not v <= lim]


def test_fp8_control_is_not_correct():
    cell = tiny_cell()
    pool = traffic.Pool(cell.traffic, SEED)
    ref = T.reference_readings(cell, SEED, pool)
    low = T.reference_readings(cell, SEED, pool, mode="fp8")
    ok, rows = compare.judge(compare.training_numbers(low, ref), cell.limits)
    assert not ok, rows
    ok, rows = compare.judge(compare.training_numbers(ref, ref), cell.limits)
    assert ok, rows
