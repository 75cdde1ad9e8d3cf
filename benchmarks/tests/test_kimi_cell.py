"""The cell `kimi_vl_a3b_ep8.train_seq8192`: its files loaded as the harness
finds them and driven at a tiny size on the CPU through `run.measure`, a
sound run held to `correct` true and the fp8 control and a planted fault to
`correct` false; the cut, the parameters held and the analytic counts held
to ISSUE 33's arithmetic at the published widths. Widths shrink here and
nowhere else."""

import copy
import sys

import jax
import pytest

from benchmarks import compare, harness, run as R, traffic
from benchmarks.kinds import train as T
from benchmarks.tests import tiny
from benchmarks.tests.test_correct import Broken, _half_batch

KIMI_CELL = "kimi_vl_a3b_ep8.train_seq8192"
KIMI_SEED = 2 ** 31 + 33
PR33 = ["mfu.mla_tokens", "device_idle_share.mla_tokens",
        "loop_input_wait_share.mla_tokens",
        "idle_input_wait_share.mla_tokens", "idle_dispatch_share.mla_tokens",
        "idle_other_share.mla_tokens", "feed_worker_share.mla_tokens",
        "hbm_pass_busy_share.mla_tokens", "moe_busy_share.mla_tokens",
        "attention_busy_share.mla_tokens", "gated_mlp_busy_share.mla_tokens",
        "mla_attention_roofline.mla_tokens", "moe_gmm_roofline.mla_tokens"]


def tiny_kimi_cell():
    """Hidden 64, 4 heads of 16 + 8 keys and 16 values up from a latent of
    32, a dense layer of 96 and two expert layers: experts 2-5 of 8 held,
    the top 2 by sigmoid plus bias, beside 2 shared experts of 24; T 32, 2
    rows a step."""
    cell = harness.Cell(KIMI_CELL)
    cell.config = copy.deepcopy(cell.config)
    cell.workload = copy.deepcopy(cell.workload)
    cell.traffic = cell.workload["traffic"]
    cell.config.update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, intermediate_size=96, moe_intermediate_size=24,
        num_hidden_layers=3, n_routed_experts=4, router_experts=8,
        experts_held_first=2, num_experts_per_tok=2, vocab_size=96,
        head_chunk_rows=16,
        # float32 on the CPU: the reference's own precision, so that a
        # sound run reads rounding and a fault reads as itself
        matmul_precision="float32")
    cell.traffic.update(pool=8, lengths={"seq": [32, 32]})
    for slot in cell.traffic["slots"]:
        slot["vocab"] = 96
    return cell


def _measure(cell):
    return R.measure(cell, KIMI_SEED, 0.5, False, jax.devices()[:1],
                     peak=tiny.PEAK)


def _count(shape):
    n = 1
    for s in shape:
        n *= s
    return n


def test_kimi_cells_files_are_found_and_say_what_the_issue_says():
    cell = harness.Cell(KIMI_CELL)
    cfg = cell.config
    assert cell.chips == 1 and cell.workload["kind"] == "train"
    assert cell.entry["config"] + "." + cell.entry["traffic"] == KIMI_CELL
    assert cell.traffic["count"] == {"unit": "tokens", "length_group": "seq"}
    assert (cell.traffic["batch"], cell.traffic["pool"],
            cell.traffic["lengths"]) == (2, 32, {"seq": [8192, 8192]})
    assert [s["vocab"] for s in cell.traffic["slots"]] == [20480, 20480]
    mellum = harness.Cell("mellum2_12b_ep4.train_seq8192").workload
    assert (cell.workload["spans"], cell.workload["trace_seconds"]) == (
        mellum["spans"], mellum["trace_seconds"])
    # every published width, unchanged
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["n_shared_experts"],
            cfg["num_experts_per_tok"], cfg["router_experts"],
            cfg["routed_scaling_factor"], cfg["rope_theta"],
            cfg["scoring_func"], cfg["topk_method"],
            cfg["first_k_dense_replace"], cfg["rms_norm_eps"]) == (
        2048, 16, 128, 64, 128, 512, 11264, 1408, 2, 6, 64, 2.446, 800000,
        "sigmoid", "noaux_tc", 1, 1e-5)
    # the table of the cut
    assert cfg["reduced"] == cell.bench["configs"][-1]["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["experts_held_first"]) == (5, 8, 20480, 0)
    assert (cfg["published"]["num_hidden_layers"],
            cfg["published"]["n_routed_experts"],
            cfg["published"]["vocab_size"]) == (27, 64, 163840)
    assert cfg["deployment"]["chips_sharing_a_layer"] == 8
    # the parameters held, summed from the reference's shapes
    held = cfg["deployment"]["parameters_held"]
    spec = cell.model.reference.param_spec(cfg)
    by = {k: _count(s) for k, (s, _) in spec.items()}

    def under(prefix):
        return sum(n for k, n in by.items() if k.startswith(prefix))

    assert under("_l1_attn.") == held["attention"] == 13763072
    assert under("_l0_") == held["dense_layer_0"] == 82973184
    assert by["_l1_moe.w_gate"] * 3 // 8 == held["one_expert"] == 8650752
    assert under("_l1_shared.") == held["shared_experts"] == 17301504
    assert (by["_l1_moe.router"], by["_l1_moe.e_score_correction_bias"]) == (
        held["router"], held["router_bias"]) == (131072, 64)
    for i in (1, 2, 3, 4):
        assert under(f"_l{i}_") == held["an_expert_layer"] == 100405824
    assert by["_emb.w0"] + by["_head.w0"] == held["embedding_and_head"]
    assert sum(by.values()) == held["all"] == 568484608
    # the metrics the cell reports, each with a reader and a data file
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert names == PR33
    for name in names:
        reader, data = cell.layer_metric(name)
        assert callable(reader.read)
    assert [m["name"] for m in cell.metrics("end_to_end")] == [
        "train_units_per_s", "setup_s"]
    for name in ("mla_attention_roofline", "moe_gmm_roofline"):
        _, data = cell.layer_metric(name + ".mla_tokens")
        assert data["cell"] == KIMI_CELL and data["hbm_bytes_per_s"] == 819e9
        assert "819 GB/s" in data["hbm_source"]
        assert callable(getattr(cell.model, data["cost"]))


def test_kimi_analytic_counts_are_the_issues_arithmetic():
    cell = harness.Cell(KIMI_CELL)
    ref = cell.model.reference
    parts = ref.forward_flops_per_token(cell.config, 8192)
    total = sum(parts.values())
    # forward 761 MFLOP a token, 2.28 GFLOP to train
    assert total == pytest.approx(761e6, rel=1e-3)
    assert cell.model.train_flops_per_row(
        cell.config, cell.traffic) == pytest.approx(2.28e9, rel=2e-3)
    shares = {k: round(100 * v / total, 1) for k, v in parts.items()}
    assert shares == {"attention": 27.6, "projections": 18.1, "dense": 18.2,
                      "shared": 18.2, "experts": 6.8, "router": 0.1,
                      "head": 11.0}
    assert parts["attention"] == 5 * 16 * 640 * 8193 / 2
    assert parts["experts"] == 4 * (6 * 8 / 64) * 3 * 2 * 2048 * 1408


def test_kimi_kernels_costs_are_the_models_work_and_read_nothing_of_the_program(
        monkeypatch):
    """What a roofline share is a share of: mask-exact pairs at the
    MODEL's widths (192 and 128, whatever a kernel pads to), 640 operations
    a pair forward and 1,664 backward; 3 products a projection on the
    expected slots; no recomputation, though the configuration asks for
    it; and no module of the program."""
    cell = harness.Cell(KIMI_CELL)
    assert cell.config["recompute"] == "block"
    for name in [m for m in sys.modules if m.startswith("paddle_tpu")]:
        monkeypatch.setitem(sys.modules, name, None)   # an import raises
    monkeypatch.setitem(sys.modules, "paddle_tpu", None)
    attn = cell.model.mla_attention_cost(cell.config, cell.traffic)
    pairs = 5 * 2 * 16 * (8192 * 8193 // 2)
    assert attn["flops"] == pairs * (640 + 1664)
    parts = cell.model.reference.forward_flops_per_token(cell.config, 8192)
    assert attn["flops"] == pytest.approx(
        16384 * parts["attention"] * (640 + 1664) / 640)
    # q and k (and dq, dk) 100.7 MB each, v and o (and do, dv) 67.1 MB
    wide, narrow = 16384 * 16 * 192 * 2, 16384 * 16 * 128 * 2
    assert attn["bytes"] == 5 * 6 * (wide + narrow)
    gmm = cell.model.moe_gmm_cost(cell.config, cell.traffic)
    rows = 16384 * 6 * 8 / 64
    assert rows == 12288
    assert gmm["flops"] == pytest.approx(16384 * 3 * parts["experts"])
    assert gmm["flops"] == 4 * 3 * 3 * 2 * rows * 2048 * 1408
    a_call = (rows * (2048 + 1408) + 8 * 2048 * 1408) * 2
    assert gmm["bytes"] == pytest.approx(4 * 3 * 3 * a_call)


def test_kimi_sound_run_is_correct():
    res = _measure(tiny_kimi_cell())
    assert res["correct"], (res["compared"], res["problems"])
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"train_units_per_s", "setup_s"}
    # the bias is a leaf of both, compared with a gap of 0
    assert res["notes"]["grad1_leaf"] != "_l1_moe.e_score_correction_bias"


def test_kimi_half_batch_is_not_correct(monkeypatch):
    build = T.build_trainer

    def broken(*args, **kw):
        trainer = build(*args, **kw)
        trainer.step_fn = Broken(trainer.step_fn, _half_batch)
        return trainer

    monkeypatch.setattr(T, "build_trainer", broken)
    res = _measure(tiny_kimi_cell())
    assert not res["correct"]
    assert [n for n, v, lim in res["compared"] if not v <= lim]


def test_kimi_fp8_control_is_not_correct():
    cell = tiny_kimi_cell()
    pool = traffic.Pool(cell.traffic, KIMI_SEED)
    ref = T.reference_readings(cell, KIMI_SEED, pool)
    low = T.reference_readings(cell, KIMI_SEED, pool, mode="fp8")
    ok, rows = compare.judge(compare.training_numbers(low, ref), cell.limits)
    assert not ok, rows
    ok, rows = compare.judge(compare.training_numbers(ref, ref), cell.limits)
    assert ok, rows
