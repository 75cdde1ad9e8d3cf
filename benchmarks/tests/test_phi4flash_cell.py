"""The cell `phi4_mini_flash_pp8.train_seq8192`: its files loaded as the
harness finds them and driven at a tiny size on the CPU through
`run.measure`, a sound run held to `correct` true and the fp8 control and a
planted fault to `correct` false; the cut, the parameters held and the
analytic counts held to ISSUE 35's arithmetic at the published widths, and
the two kernels' counts at a size small enough to count by hand. Widths
shrink here and nowhere else."""

import copy
import sys

import jax
import pytest

from benchmarks import compare, harness, run as R, traffic
from benchmarks.kinds import train as T
from benchmarks.tests import tiny
from benchmarks.tests.test_correct import Broken, _half_batch

PHI_CELL = "phi4_mini_flash_pp8.train_seq8192"
PHI_SEED = 2 ** 31 + 35
PR35 = ["mfu.ssm_tokens", "device_idle_share.ssm_tokens",
        "loop_input_wait_share.ssm_tokens",
        "idle_input_wait_share.ssm_tokens", "idle_dispatch_share.ssm_tokens",
        "idle_other_share.ssm_tokens", "feed_worker_share.ssm_tokens",
        "hbm_pass_busy_share.ssm_tokens", "mamba_busy_share.ssm_tokens",
        "attention_busy_share.ssm_tokens", "gated_mlp_busy_share.ssm_tokens",
        "selective_scan_roofline.ssm_tokens",
        "diff_attention_roofline.ssm_tokens"]


def tiny_phi_cell():
    """Hidden 64, 8 query heads on 4 key heads of 8 (2 value pairs of 16), a
    window of 8, feed-forward 96, Mamba of 128 channels, 4 states, rank 4;
    published layers 14-17 of 32; T 32, 2 rows a step."""
    cell = harness.Cell(PHI_CELL)
    cell.config = copy.deepcopy(cell.config)
    cell.workload = copy.deepcopy(cell.workload)
    cell.traffic = cell.workload["traffic"]
    cell.config.update(
        hidden_size=64, num_attention_heads=8, num_key_value_heads=4,
        intermediate_size=96, sliding_window=8, vocab_size=96,
        mamba_d_state=4, mamba_dt_rank=4, head_chunk_rows=16,
        # a tied matrix of std 0.04 over 64-wide rows gives logits of std
        # 0.3; at this width 0.2 gives the cell's 2
        init_std=dict(cell.config["init_std"], embedding=0.2,
                      projection=0.1, mlp=0.1),
        # float32 on the CPU: the reference's own precision, so that a
        # sound run reads rounding and a fault reads as itself
        matmul_precision="float32")
    cell.traffic.update(pool=8, lengths={"seq": [32, 32]})
    for slot in cell.traffic["slots"]:
        slot["vocab"] = 96
    return cell


def _measure(cell):
    return R.measure(cell, PHI_SEED, 0.5, False, jax.devices()[:1],
                     peak=tiny.PEAK)


def _count(shape):
    n = 1
    for s in shape:
        n *= s
    return n


def test_phi_cells_files_are_found_and_say_what_the_issue_says():
    cell = harness.Cell(PHI_CELL)
    cfg = cell.config
    assert cell.chips == 1 and cell.workload["kind"] == "train"
    assert cell.entry["config"] + "." + cell.entry["traffic"] == PHI_CELL
    assert cell.traffic["count"] == {"unit": "tokens", "length_group": "seq"}
    assert (cell.traffic["batch"], cell.traffic["pool"],
            cell.traffic["lengths"]) == (2, 32, {"seq": [8192, 8192]})
    assert [s["vocab"] for s in cell.traffic["slots"]] == [25088, 25088]
    # the other two decoder cells' traffic with another vocabulary
    kimi = harness.Cell("kimi_vl_a3b_ep8.train_seq8192").workload
    mine = copy.deepcopy(cell.workload)
    for slot in mine["traffic"]["slots"]:
        slot["vocab"] = 20480
    assert {k: v for k, v in mine.items() if k != "limits"} == {
        k: v for k, v in kimi.items() if k != "limits"}
    # every published key of the source, unchanged but for the two reduced
    source = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
              "intermediate_size": 10240, "layer_norm_eps": 1e-05,
              "max_position_embeddings": 262144, "mb_per_layer": 2,
              "model_type": "phi4flash", "num_attention_heads": 40,
              "num_hidden_layers": 32, "num_key_value_heads": 20,
              "resid_pdrop": 0, "sliding_window": 512,
              "tie_word_embeddings": True, "mlp_bias": False,
              "lm_head_bias": False, "vocab_size": 200064}
    assert {k for k, v in source.items() if cfg[k] != v} == {
        "num_hidden_layers", "vocab_size"}
    entry = {c["name"]: c for c in cell.bench["configs"]}[cfg["name"]]
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "vocab_size"]
    assert entry["source"] == cfg["source"]
    assert (cfg["num_hidden_layers"], cfg["vocab_size"], cfg["first_layer"],
            cfg["published_layers"]) == (4, 25088, 14, 32)
    assert (cfg["published"]["num_hidden_layers"],
            cfg["published"]["vocab_size"]) == (32, 200064)
    assert 8 * 25088 == 8 * 128 * 196 == 200704 >= 200064
    assert (cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_expand"],
            cfg["mamba_dt_rank"]) == (16, 4, 2, 160) and -(-2560 // 16) == 160
    assert cfg["deployment"]["pipeline_stages"] == 8
    assert "nothing is exported" in cfg["deployment"]["held_here"][
        "memory_and_keys"]
    assert len(cfg["assumed"]) >= 5
    # the stage: (Mamba, window, the memory Mamba, full)
    ref = cell.model.reference
    assert ref.layers_held(cfg) == [14, 15, 16, 17]
    assert [ref.kind_of(cfg, l) for l in ref.layers_held(cfg)] == [
        "mamba", "window", "mamba", "full"]
    # the parameters held, summed from the reference's shapes
    held = cfg["deployment"]["parameters_held"]
    spec = ref.param_spec(cfg)
    by = {k: _count(s) for k, (s, _) in spec.items()}

    def under(prefix):
        return sum(n for k, n in by.items() if k.startswith(prefix))

    assert under("_l14_mamba.") == held["a_mamba_mixer"] == 41241600
    assert under("_l15_attn.") == held["an_attention_mixer"] == 19668864
    assert under("_l14_mlp.") == held["an_mlp"] == 78643200
    assert (under("_l14_norm") == under("_l17_norm")
            == held["a_layers_two_norms"] == 10240)
    for l in (14, 16):
        assert under(f"_l{l}_") == held["a_mamba_layer"] == 119895040
    for l in (15, 17):
        assert under(f"_l{l}_") == held["an_attention_layer"] == 98322304
    assert by["_emb.w0"] == held["embedding_and_head"] == 25088 * 2560
    assert "_head.w0" not in by                    # ONE leaf: the tie
    assert under("_final_norm.") == held["final_norm"] == 5120
    assert sum(by.values()) == held["all"] == 500665088
    # the metrics the cell reports, each with a reader and a data file
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert names == PR35
    for name in names:
        reader, data = cell.layer_metric(name)
        assert callable(reader.read)
    assert [m["name"] for m in cell.metrics("end_to_end")] == [
        "train_units_per_s", "setup_s"]
    for name, scope in (("selective_scan_roofline", "ssm.scan"),
                        ("diff_attention_roofline", "attn.core")):
        _, data = cell.layer_metric(name + ".ssm_tokens")
        assert data["cell"] == PHI_CELL and data["hbm_bytes_per_s"] == 819e9
        assert (data["scope"], data["op"]) == (scope, "custom-call")
        assert "819 GB/s" in data["hbm_source"]
        assert callable(getattr(cell.model, data["cost"]))


def test_phi_analytic_counts_are_the_issues_arithmetic():
    cell = harness.Cell(PHI_CELL)
    ref = cell.model.reference
    parts = ref.forward_flops_per_token(cell.config, 8192)
    step = {k: 3 * 16384 * v / 1e12 for k, v in parts.items()}
    # ISSUE 35: a step is 53 TFLOP: MLPs 31, mixer projections 12, head 6.3,
    # the scan 0.07; the attention pairs 3.5 at 3 x forward (the kernels'
    # own count, with the backward's fifth product, is 3.85)
    assert step["mlp"] == pytest.approx(30.9, rel=2e-3)
    assert step["projections"] == pytest.approx(11.95, rel=2e-3)
    assert step["head"] == pytest.approx(6.31, rel=2e-3)
    assert step["scan"] == pytest.approx(0.0725, rel=2e-3)
    assert step["attention"] == pytest.approx(3.47, rel=2e-3)
    assert 16384 * cell.model.train_flops_per_row(
        cell.config, cell.traffic) == pytest.approx(52.7e12, rel=2e-3)
    assert parts["scan"] == 2 * 9 * 5120 * 16
    window = 512 * 513 // 2 + (8192 - 512) * 512
    assert ref.attended_keys(8192, 512) == window
    assert ref.attended_keys(8192) == 8192 * 8193 // 2
    assert ref.attended_keys(300, 512) == 300 * 301 // 2
    assert parts["attention"] == 40 * 384 * (window + 8192 * 8193 // 2) / 8192


def test_phi_kernels_costs_count_by_hand_and_read_nothing_of_the_program(
        monkeypatch):
    """What a roofline share is a share of, at a size to count by hand: 1
    row of 4 positions, 8 query heads on 4 key heads of 8, a window of 2;
    128 channels of 4 states. No recomputation, though the configuration
    asks for it; and no module of the program."""
    cell = tiny_phi_cell()
    cfg, traffic_ = cell.config, cell.traffic
    assert cfg["recompute"] == "block"
    traffic_.update(batch=1, lengths={"seq": [4, 4]})
    cfg.update(sliding_window=2)
    for name in [m for m in sys.modules if m.startswith("paddle_tpu")]:
        monkeypatch.setitem(sys.modules, name, None)   # an import raises
    monkeypatch.setitem(sys.modules, "paddle_tpu", None)
    scan = cell.model.selective_scan_cost(cfg, traffic_)
    # 2 Mamba layers x 4 positions x 128 channels x 4 states, 9 + 18
    assert scan["flops"] == 2 * 4 * 128 * 4 * 27
    # a position: x, s bf16 and dt f32 a channel, B and C bf16 a state
    fwd = 128 * (2 + 4 + 2) + 2 * 4 * 2
    bwd = fwd + 128 * (2 + 4) + 2 * 4 * 2
    assert (fwd, bwd) == (1040, 1824)
    assert scan["bytes"] == 2 * 4 * (fwd + bwd)
    attn = cell.model.diff_attention_cost(cfg, traffic_)
    # pairs a head: the window layer 1 + 2 + 2 + 2, the full layer 1 + 2 + 3
    # + 4; a pair 2 x 8 + 2 x 16 forward, 3 x 2 x 8 + 2 x 2 x 16 backward
    assert attn["flops"] == 8 * (7 + 10) * (48 + 112)
    q, k, v, o = 4 * 8 * 8 * 2, 4 * 4 * 8 * 2, 4 * 2 * 16 * 2, 4 * 8 * 16 * 2
    assert attn["bytes"] == 2 * 3 * (q + k + v + o)
    # at the cell's size: 3.85 TFLOP and 2.0 GB, 72.5 GFLOP and 3.7 GB
    cell = harness.Cell(PHI_CELL)
    attn = cell.model.diff_attention_cost(cell.config, cell.traffic)
    scan = cell.model.selective_scan_cost(cell.config, cell.traffic)
    pairs = 2 * 40 * (8192 * 8193 // 2 + 512 * 513 // 2 + 7680 * 512)
    assert attn["flops"] == pairs * (384 + 896)
    assert attn["bytes"] == 2 * 3 * 16384 * 2 * (40 * 64 + 20 * 64 + 10 * 128
                                                 + 40 * 128)
    assert scan["flops"] == 2 * 16384 * 5120 * 16 * 27
    assert scan["bytes"] == 2 * 16384 * (2 * 41024 + 5120 * 6 + 64)


def test_phi_sound_run_is_correct():
    res = _measure(tiny_phi_cell())
    assert res["correct"], (res["compared"], res["problems"])
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"train_units_per_s", "setup_s"}
    assert [row[0] for row in res["compared"]] == [
        "grad1_median_gap", "grad1_total_gap", "delta_median_gap",
        "delta_total_gap"]


def test_phi_half_batch_is_not_correct(monkeypatch):
    build = T.build_trainer

    def broken(*args, **kw):
        trainer = build(*args, **kw)
        trainer.step_fn = Broken(trainer.step_fn, _half_batch)
        return trainer

    monkeypatch.setattr(T, "build_trainer", broken)
    res = _measure(tiny_phi_cell())
    assert not res["correct"]
    assert [n for n, v, lim in res["compared"] if not v <= lim]


def test_phi_fp8_control_is_not_correct():
    cell = tiny_phi_cell()
    pool = traffic.Pool(cell.traffic, PHI_SEED)
    ref = T.reference_readings(cell, PHI_SEED, pool)
    low = T.reference_readings(cell, PHI_SEED, pool, mode="fp8")
    ok, rows = compare.judge(compare.training_numbers(low, ref), cell.limits)
    assert not ok, rows
    ok, rows = compare.judge(compare.training_numbers(ref, ref), cell.limits)
    assert ok, rows
