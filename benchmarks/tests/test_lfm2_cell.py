"""The cell `lfm2_8b_a1b_ep4.train_seq8192`: its files loaded as the harness
finds them and driven at a tiny size on the CPU through `run.measure`, a
sound run held to `correct` true and the fp8 control and a planted fault to
`correct` false; the cut, the parameters held and the analytic counts held
to ISSUE 41's arithmetic at the published widths. Its entries are held
wherever later entries leave them. Widths shrink here and nowhere else."""

import copy
import json
import os
import sys

import jax
import pytest

from benchmarks import compare, harness, run as R, traffic
from benchmarks.kinds import train as T
from benchmarks.tests import tiny
from benchmarks.tests.test_correct import Broken, _half_batch

LFM2_CELL = "lfm2_8b_a1b_ep4.train_seq8192"
LFM2_SEED = 2 ** 31 + 41
PR41 = [f"{name}.conv_tokens" for name in (
    "mfu", "device_idle_share", "loop_input_wait_share",
    "idle_input_wait_share", "idle_dispatch_share", "idle_other_share",
    "feed_worker_share", "hbm_pass_busy_share", "moe_busy_share",
    "attention_busy_share", "gated_mlp_busy_share", "moe_gmm_roofline",
    "setup_before_build_s", "setup_build_s", "setup_step_trace_s",
    "setup_step_load_s", "short_conv_busy_share", "conv_mix_busy_share",
    "qk_norm_busy_share", "qk_norm_attention_roofline")]


def tiny_lfm2_cell():
    """Hidden 64, 4 query heads on 2 KV heads of 16; published layers 0
    (conv over a dense block of 96), 2 (attention) and 3 (conv), the last
    two over experts 8-15 of 32 (24 wide, top 4, sigmoid plus a selection
    bias); the published list of layer kinds whole; T 32, 2 rows a step."""
    cell = harness.Cell(LFM2_CELL)
    cell.config = copy.deepcopy(cell.config)
    cell.workload = copy.deepcopy(cell.workload)
    cell.traffic = cell.workload["traffic"]
    cell.config.update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        num_hidden_layers=3, layers_held=[0, 2, 3], intermediate_size=96,
        moe_intermediate_size=24, num_experts=8, router_experts=32,
        experts_held_first=8, num_experts_per_tok=4, vocab_size=96,
        head_chunk_rows=16,
        # float32 on the CPU: the reference's own precision, so that a
        # sound run reads rounding and a fault reads as itself
        matmul_precision="float32")
    cell.traffic.update(pool=8, lengths={"seq": [32, 32]})
    for slot in cell.traffic["slots"]:
        slot["vocab"] = 96
    return cell


def _measure(cell):
    return R.measure(cell, LFM2_SEED, 0.5, False, jax.devices()[:1],
                     peak=tiny.PEAK)


def _count(shape):
    n = 1
    for s in shape:
        n *= s
    return n


def test_lfm2_cells_files_are_found_and_say_what_the_issue_says():
    cell = harness.Cell(LFM2_CELL)
    cfg = cell.config
    assert cell.chips == 1 and cell.workload["kind"] == "train"
    assert cell.entry["config"] + "." + cell.entry["traffic"] == LFM2_CELL
    assert cell.traffic["count"] == {"unit": "tokens", "length_group": "seq"}
    assert (cell.traffic["batch"], cell.traffic["pool"],
            cell.traffic["lengths"]) == (2, 32, {"seq": [8192, 8192]})
    assert [(s["vocab"], s["min_id"]) for s in cell.traffic["slots"]] == [
        (16384, 0), (16384, 0)]
    # the other four decoder cells' traffic with another vocabulary
    kimi = harness.Cell("kimi_vl_a3b_ep8.train_seq8192").workload
    mine = copy.deepcopy(cell.workload)
    for slot in mine["traffic"]["slots"]:
        slot["vocab"] = 20480
    assert {k: v for k, v in mine.items() if k != "limits"} == {
        k: v for k, v in kimi.items() if k != "limits"}
    # every number of the published config, but the three cut
    source = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
              "intermediate_size": 7168, "max_position_embeddings": 128000,
              "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
              "norm_eps": 1e-05, "norm_topk_prob": True,
              "num_attention_heads": 32, "num_dense_layers": 2,
              "num_experts": 32, "num_experts_per_tok": 4,
              "num_hidden_layers": 24, "num_key_value_heads": 8,
              "rope_theta": 1000000, "routed_scaling_factor": 1,
              "use_expert_bias": True, "vocab_size": 65536}
    assert {k for k, v in source.items() if cfg[k] != v} == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    period = ["full_attention", "conv", "conv", "conv"]
    assert cfg["layer_types"] == (["conv", "conv"] + 4 * period
                                  + 2 * ["full_attention", "conv", "conv"])
    assert cfg["layer_types"].count("conv") == 18
    entry = {c["name"]: c for c in cell.bench["configs"]}[cfg["name"]]
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json")
    assert (cfg["num_hidden_layers"], cfg["layers_held"], cfg["num_experts"],
            cfg["router_experts"], cfg["experts_held_first"],
            cfg["vocab_size"], cfg["tie_word_embeddings"]) == (
        5, [0, 2, 3, 4, 5], 8, 32, 0, 16384, True)
    assert (cfg["published"]["num_hidden_layers"],
            cfg["published"]["num_experts"],
            cfg["published"]["vocab_size"]) == (24, 32, 65536)
    assert 4 * 16384 == 65536 and 4 * 8 == 32
    dep = cfg["deployment"]
    assert (dep["chips_sharing_a_layer"], dep["pipeline_stages"]) == (4, 5)
    assert "a quarter" in dep["held_here"]["expert_load"]
    assert "no code stands in" in dep["how"]
    assumed = " ".join(cfg["assumed"])
    for said in ("tied", "intermediate_size 7168", "1e-6",
                 "BEFORE the rotary"):
        assert said in assumed, said
    ref = cell.model.reference
    assert [(l, ref.is_conv(cfg, l), ref.is_dense(cfg, l))
            for l in ref.layers_held(cfg)] == [
        (0, True, True), (2, False, False), (3, True, False),
        (4, True, False), (5, True, False)]
    # the parameters held, summed from the reference's shapes
    held = dep["parameters_held"]
    by = {k: _count(s) for k, (s, _) in ref.param_spec(cfg).items()}

    def under(prefix):
        return sum(n for k, n in by.items() if k.startswith(prefix))

    assert under("_l0_conv.") == held["a_conv_mixer"] == (
        2048 * 6144 + 2048 * 3 + 2048 * 2048)
    assert under("_l2_attn.") == held["attention"] == 10485888
    assert (by["_l2_attn.q_norm"] + by["_l2_attn.k_norm"]
            == held["of_it_qk_norms"] == 2 * 64)
    assert under("_l0_mlp.") == held["dense_block_layer_0"] == 3 * 2048 * 7168
    assert by["_l2_moe.router"] == held["router"] == 2048 * 32
    assert by["_l2_moe.e_score_correction_bias"] == held["router_bias"] == 32
    assert by["_l2_moe.w_up"] == 8 * 2048 * 1792
    assert held["one_expert"] == 3 * 2048 * 1792
    assert under("_l0_") == held["layer_0"] == 60827648
    assert under("_l2_") == held["the_attention_expert_layer"] == 98635936
    for l in (3, 4, 5):
        assert under(f"_l{l}_") == held["a_conv_expert_layer"] == 104933408
    assert "_head.w0" not in by                              # tied
    assert by["_emb.w0"] == held["embedding_tied_to_the_head"] == 16384 * 2048
    assert under("_final_norm.") == held["final_norm"] == 2048
    assert sum(by.values()) == held["all"] == 507820288
    # 16 bytes a parameter for the program, 20 for the reference trainer
    assert 16 * held["all"] / 1e9 == pytest.approx(8.13, abs=0.01)
    assert 20 * held["all"] / 1e9 == pytest.approx(10.16, abs=0.01)
    # the metrics the cell reports, each with a reader and a data file
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert names == PR41
    for name in PR41:
        reader, data = cell.layer_metric(name)
        assert callable(reader.read)
        # nothing to read: nothing read, and no raise
        assert reader.read({"trace": None, "spans": {}, "flops": 0,
                            "window_s": 0.0}, data) is None
    assert [m["name"] for m in cell.metrics("end_to_end")] == [
        "train_units_per_s", "setup_s"]
    for name, scope in (("qk_norm_attention_roofline", "attn.core"),
                        ("moe_gmm_roofline", "moe.gmm")):
        _, data = cell.layer_metric(name + ".conv_tokens")
        assert data["cell"] == LFM2_CELL and data["hbm_bytes_per_s"] == 819e9
        assert (data["scope"], data["op"]) == (scope, "custom-call")
        assert "819 GB/s" in data["hbm_source"]
        assert callable(getattr(cell.model, data["cost"]))
    for name, holds in (("conv_mix_busy_share", "conv.mix"),
                        ("qk_norm_busy_share", "attn.qk_norm")):
        reader, data = cell.layer_metric(name + ".conv_tokens")
        assert reader.__name__.endswith("inner_scope_busy_share")
        assert data == {"reader": "inner_scope_busy_share", "holds": holds}
    _, data = cell.layer_metric("short_conv_busy_share.conv_tokens")
    assert data == {"reader": "scope_busy_share", "scopes": ["short_conv:"]}


def test_lfm2_entries_stand_as_one_run_and_the_benchmark_had_the_rest():
    """One configuration, one cell on one chip and twenty metrics in one
    run, wherever later entries leave them; the cell's name among the rate's
    cells; every metric a data file on a reader the benchmark had."""
    bench = harness.load_benchmark()
    assert [c["name"] for c in bench["configs"]].count("lfm2_8b_a1b_ep4") == 1
    (mine,) = [w for w in bench["workloads"] if w["name"] == LFM2_CELL]
    assert mine == {"name": LFM2_CELL, "config": "lfm2_8b_a1b_ep4",
                    "traffic": "train_seq8192", "chips": 1,
                    "why": mine["why"]}
    assert len(mine["why"]) <= 200 and "a quarter" in mine["why"]
    assert [w["config"] for w in bench["workloads"]].count(
        "lfm2_8b_a1b_ep4") == 1              # ONE cell
    per = bench["per_layer"]
    names = [m["name"] for m in per]
    at = names.index(PR41[0])
    assert names[at: at + len(PR41)] == PR41
    rate, setup = bench["end_to_end"]
    assert LFM2_CELL in rate["workloads"] and "workloads" not in setup
    layers = {"mfu": "step program and model graph",
              "device_idle_share": "device", "idle_input_wait_share": "device",
              "idle_dispatch_share": "device", "idle_other_share": "device",
              "loop_input_wait_share": "entry points",
              "feed_worker_share": "entry points",
              "setup_before_build_s": "entry points",
              "setup_build_s": "entry points",
              "moe_gmm_roofline": "kernels",
              "qk_norm_attention_roofline": "kernels"}
    for m in per[at: at + len(PR41)]:
        assert m["workloads"] == [LFM2_CELL]
        kind = m["name"].split(".")[0]
        assert m["layer"] == layers.get(kind, "step program and model graph")
        if kind.startswith("setup_"):
            assert (m["unit"], m["better"], m["moves"], m["source"]) == (
                "s", "lower", "setup_s", "program_counter")
        else:
            assert (m["unit"], m["moves"]) == ("%", "train_units_per_s")
            assert m["better"] == ("higher" if kind == "mfu"
                                   or "roofline" in kind else "lower")
    here = os.path.join(harness.ROOT, "benchmarks", "layer_metrics")
    readers = {json.load(open(os.path.join(here, n + ".json")))["reader"]
               for n in PR41}
    assert readers == {
        "mfu", "device_idle_share", "program_span_share", "scope_busy_share",
        "kernel_roofline", "program_setup_seconds", "inner_scope_busy_share"}


def test_lfm2_analytic_counts_are_the_issues_arithmetic():
    cell = harness.Cell(LFM2_CELL)
    ref = cell.model.reference
    parts = ref.forward_flops_per_token(cell.config, 8192)
    total = sum(parts.values())
    step = {k: 3 * 16384 * v / 1e12 for k, v in parts.items()}
    # a step at 3 x forward: 21.3 TFLOP; the conv blocks' projections 6.6 of
    # it, layer 0's dense block 4.33, the experts held 4.33, the head 3.30,
    # attention's pairs 1.65 (the kernel's own count, with the backward's
    # fifth product, is 1.92), its projections 1.03, the routers 0.026
    assert 3 * 16384 * total == pytest.approx(21.26e12, rel=2e-3)
    assert step["conv_projections"] == pytest.approx(6.597, rel=2e-3)
    assert step["dense"] == step["experts"] == pytest.approx(4.330, rel=2e-3)
    assert step["head"] == pytest.approx(3.299, rel=2e-3)
    assert step["attention"] == pytest.approx(1.650, rel=2e-3)
    assert step["attn_projections"] == pytest.approx(1.031, rel=2e-3)
    assert step["router"] == pytest.approx(0.0258, rel=2e-3)
    shares = {k: round(100 * v / total, 1) for k, v in parts.items()}
    assert shares == {"conv_projections": 31.0, "dense": 20.4,
                      "experts": 20.4, "head": 15.5, "attention": 7.8,
                      "attn_projections": 4.8, "router": 0.1}
    assert 16384 * cell.model.train_flops_per_row(
        cell.config, cell.traffic) == 3 * 16384 * total
    assert ref.attended_keys(8192) == 8192 * 8193 // 2 == 33558528
    assert parts["attention"] == 32 * 4 * 64 * 33558528 / 8192
    # the four conv layers' w_in [2048, 6144] and w_out [2048, 2048]
    assert parts["conv_projections"] == 4 * 2 * 2048 * (6144 + 2048)
    assert parts["attn_projections"] == 2 * 2048 * (2 * 2048 + 2 * 512)
    # a top-4 of 32 over 8 held: one expected slot a token and layer
    assert parts["experts"] == 4 * (4 * 8 / 32) * 3 * 2 * 2048 * 1792
    assert parts["head"] == 2 * 2048 * 16384


def test_lfm2_kernels_costs_are_the_models_work_and_read_nothing_of_program(
        monkeypatch):
    """What a roofline share is a share of: causal pairs for 32 heads of 64,
    256 operations a pair forward and 640 backward; 3 products a projection
    on the expected slots; no recomputation, though the configuration asks
    for it; the norms of q and k nowhere; and no module of the program."""
    cell = harness.Cell(LFM2_CELL)
    assert cell.config["recompute"] == "block"
    for name in [m for m in sys.modules if m.startswith("paddle_tpu")]:
        monkeypatch.setitem(sys.modules, name, None)   # an import raises
    monkeypatch.setitem(sys.modules, "paddle_tpu", None)
    attn = cell.model.qk_norm_attention_cost(cell.config, cell.traffic)
    pairs = 2 * 32 * 33558528
    assert attn["flops"] == pairs * (256 + 640)
    assert attn["flops"] == pytest.approx(1.92e12, rel=5e-3)
    q, kv = 16384 * 32 * 64 * 2, 16384 * 8 * 64 * 2
    assert attn["bytes"] == 6 * (q + kv)
    gmm = cell.model.moe_gmm_cost(cell.config, cell.traffic)
    rows = 16384 * 4 * 8 / 32
    assert rows == 16384
    parts = cell.model.reference.forward_flops_per_token(cell.config, 8192)
    assert gmm["flops"] == pytest.approx(16384 * 3 * parts["experts"])
    assert gmm["flops"] == 4 * 3 * 3 * 2 * rows * 2048 * 1792
    assert gmm["flops"] == pytest.approx(4.33e12, rel=5e-3)
    a_call = (rows * (2048 + 1792) + 8 * 2048 * 1792) * 2
    assert gmm["bytes"] == 4 * 3 * 3 * a_call
    # the matrix unit binds: 22 ms of it against 6.5 ms of HBM time
    assert gmm["flops"] / 197e12 > gmm["bytes"] / 819e9
    # a count by hand at the tiny size: 1 row of 4 positions; pairs a head 1
    # + 2 + 3 + 4 on the one attention layer of 4 heads; a pair 2 x 2 x 16
    # forward and 5 x 2 x 16 backward
    cell = tiny_lfm2_cell()
    cell.traffic.update(batch=1, lengths={"seq": [4, 4]})
    attn = cell.model.qk_norm_attention_cost(cell.config, cell.traffic)
    assert attn["flops"] == 4 * 10 * (64 + 160)
    assert attn["bytes"] == 6 * 4 * 16 * 2 * (4 + 2)


def test_lfm2_sound_run_is_correct():
    res = _measure(tiny_lfm2_cell())
    assert res["correct"], (res["compared"], res["problems"])
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"train_units_per_s", "setup_s"}
    assert [row[0] for row in res["compared"]] == [
        "grad1_median_gap", "grad1_total_gap", "delta_median_gap",
        "delta_total_gap"]


def test_lfm2_half_batch_is_not_correct(monkeypatch):
    build = T.build_trainer

    def broken(*args, **kw):
        trainer = build(*args, **kw)
        trainer.step_fn = Broken(trainer.step_fn, _half_batch)
        return trainer

    monkeypatch.setattr(T, "build_trainer", broken)
    res = _measure(tiny_lfm2_cell())
    assert not res["correct"]
    assert [n for n, v, lim in res["compared"] if not v <= lim]


def test_lfm2_fp8_control_is_not_correct():
    cell = tiny_lfm2_cell()
    pool = traffic.Pool(cell.traffic, LFM2_SEED)
    ref = T.reference_readings(cell, LFM2_SEED, pool)
    low = T.reference_readings(cell, LFM2_SEED, pool, mode="fp8")
    ok, rows = compare.judge(compare.training_numbers(low, ref), cell.limits)
    assert not ok, rows
    ok, rows = compare.judge(compare.training_numbers(ref, ref), cell.limits)
    assert ok, rows
