"""The cell `laguna_xs2_ep16.train_seq8192`: its files loaded as the harness
finds them and driven at a tiny size on the CPU through `run.measure`, a
sound run held to `correct` true and the fp8 control and a planted fault to
`correct` false; the cut, the parameters held and the analytic counts held
to ISSUE 39's arithmetic at the published widths; the new reader on plain
data. Widths shrink here and nowhere else."""

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

LAGUNA_CELL = "laguna_xs2_ep16.train_seq8192"
LAGUNA_SEED = 2 ** 31 + 39
PR39 = [f"{name}.gated_tokens" for name in (
    "mfu", "device_idle_share", "loop_input_wait_share",
    "idle_input_wait_share", "idle_dispatch_share", "idle_other_share",
    "feed_worker_share", "hbm_pass_busy_share", "moe_busy_share",
    "attention_busy_share", "gated_mlp_busy_share",
    "gated_attention_roofline", "moe_gmm_roofline",
    "attention_gate_busy_share", "moe_route_busy_share",
    "setup_before_build_s", "setup_build_s", "setup_step_trace_s",
    "setup_step_load_s")]


def tiny_laguna_cell():
    """Hidden 64 on 2 KV heads of 16; layer 0 full attention with 4 heads
    (rotary on 8 of a head's 16 dims, YaRN) over a dense block of 96, layer
    1 a window of 8 with 8 heads (whole-width rotary), layer 2 full with 4:
    both over experts 8-15 of 32 (24 wide, top 4, scaled 2.5) beside a
    shared one of 24; T 32, 2 rows a step."""
    cell = harness.Cell(LAGUNA_CELL)
    cell.config = copy.deepcopy(cell.config)
    cell.workload = copy.deepcopy(cell.workload)
    cell.traffic = cell.workload["traffic"]
    cell.config.update(
        hidden_size=64, head_dim=16, num_key_value_heads=2,
        num_hidden_layers=3, num_attention_heads_per_layer=[4, 8, 4],
        layer_types=["full_attention", "sliding_attention",
                     "full_attention"],
        mlp_layer_types=["dense", "sparse", "sparse"], sliding_window=8,
        intermediate_size=96, moe_intermediate_size=24,
        shared_expert_intermediate_size=24, num_experts=8,
        router_experts=32, experts_held_first=8, num_experts_per_tok=4,
        vocab_size=96, head_chunk_rows=16,
        # float32 on the CPU: the reference's own precision, so that a
        # sound run reads rounding and a fault reads as itself
        matmul_precision="float32")
    # positions past the original length at T 32, so that YaRN's blend is
    # in what is compared
    cell.config["rope_parameters"]["full_attention"].update(
        original_max_position_embeddings=16)
    cell.traffic.update(pool=8, lengths={"seq": [32, 32]})
    for slot in cell.traffic["slots"]:
        slot["vocab"] = 96
    return cell


def _measure(cell):
    return R.measure(cell, LAGUNA_SEED, 0.5, False, jax.devices()[:1],
                     peak=tiny.PEAK)


def _count(shape):
    n = 1
    for s in shape:
        n *= s
    return n


def test_laguna_cells_files_are_found_and_say_what_the_issue_says():
    cell = harness.Cell(LAGUNA_CELL)
    cfg = cell.config
    assert cell.chips == 1 and cell.workload["kind"] == "train"
    assert cell.entry["config"] + "." + cell.entry["traffic"] == LAGUNA_CELL
    assert cell.traffic["count"] == {"unit": "tokens", "length_group": "seq"}
    assert (cell.traffic["batch"], cell.traffic["pool"],
            cell.traffic["lengths"]) == (2, 32, {"seq": [8192, 8192]})
    assert [s["vocab"] for s in cell.traffic["slots"]] == [12544, 12544]
    # the other three decoder cells' traffic with another vocabulary
    kimi = harness.Cell("kimi_vl_a3b_ep8.train_seq8192").workload
    mine = copy.deepcopy(cell.workload)
    for slot in mine["traffic"]["slots"]:
        slot["vocab"] = 20480
    assert {k: v for k, v in mine.items() if k != "limits"} == {
        k: v for k, v in kimi.items() if k != "limits"}
    # every width as published, and every published key but the three cut
    assert (cfg["hidden_size"], cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["sliding_window"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"], cfg["router_experts"],
            cfg["num_experts_per_tok"], cfg["moe_routed_scaling_factor"]) == (
        2048, 8, 128, 512, 8192, 512, 512, 256, 8, 2.5)
    source = {"model_type": "laguna", "vocab_size": 100352,
              "hidden_size": 2048, "intermediate_size": 8192,
              "num_hidden_layers": 40, "num_attention_heads": 48,
              "num_key_value_heads": 8, "head_dim": 128,
              "max_position_embeddings": 262144, "attention_bias": False,
              "rms_norm_eps": 1e-06, "num_experts": 256,
              "num_experts_per_tok": 8, "moe_intermediate_size": 512,
              "shared_expert_intermediate_size": 512,
              "tie_word_embeddings": False, "gating": True,
              "sliding_window": 512,
              "moe_apply_router_weight_on_input": False,
              "partial_rotary_factor": 0.5, "moe_routed_scaling_factor": 2.5}
    assert {k for k, v in source.items() if cfg[k] != v} == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    # the published lists whole; the first five entries are the layers held
    period = ["full_attention"] + 3 * ["sliding_attention"]
    assert cfg["layer_types"] == 10 * period
    assert cfg["num_attention_heads_per_layer"] == 10 * [48, 64, 64, 64]
    assert cfg["mlp_layer_types"] == ["dense"] + 39 * ["sparse"]
    assert cfg["rope_parameters"] == {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096}
    entry = {c["name"]: c for c in cell.bench["configs"]}[cfg["name"]]
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json")
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"],
            cfg["experts_held_first"]) == (5, 16, 12544, 0)
    assert (cfg["published"]["num_hidden_layers"],
            cfg["published"]["num_experts"],
            cfg["published"]["vocab_size"]) == (40, 256, 100352)
    assert 8 * 12544 == 100352
    dep = cfg["deployment"]
    assert (dep["chips_sharing_a_layer"], dep["pipeline_stages"]) == (16, 8)
    assert "a sixteenth" in dep["held_here"]["expert_load"]
    assert "no code stands in" in dep["how"]
    assert len(cfg["assumed"]) >= 8
    ref = cell.model.reference
    assert [(ref.heads_of(cfg, i), ref.window_of(cfg, i),
             ref.is_dense(cfg, i)) for i in range(ref.n_layers(cfg))] == [
        (48, None, True), (64, 512, False), (64, 512, False),
        (64, 512, False), (48, None, False)]
    # the parameters held, summed from the reference's shapes
    held = dep["parameters_held"]
    spec = ref.param_spec(cfg)
    by = {k: _count(s) for k, (s, _) in spec.items()}

    def under(prefix):
        return sum(n for k, n in by.items() if k.startswith(prefix))

    assert under("_l0_attn.") == held["full_attention_48_heads"] == 29458432
    assert under("_l1_attn.") == held["window_attention_64_heads"] == 37879808
    assert (by["_l0_attn.wg"], by["_l1_attn.wg"]) == (
        held["of_it_gate_48"], held["of_it_gate_64"]) == (2048 * 48, 2048 * 64)
    assert under("_l0_mlp.") == held["dense_block_layer_0"] == 3 * 2048 * 8192
    assert under("_l1_shared.") == held["shared_expert"] == 3 * 2048 * 512
    assert by["_l1_moe.router"] == held["router"] == 2048 * 256
    assert by["_l1_moe.w_up"] == 16 * 2048 * 512
    assert held["one_expert"] == 3 * 2048 * 512
    assert under("_l0_") == held["layer_0"] == 79794176
    for i in (1, 2, 3):
        assert under(f"_l{i}_") == held["a_window_expert_layer"] == 91885568
    assert under("_l4_") == held["the_full_expert_layer"] == 83464192
    assert (by["_emb.w0"] + by["_head.w0"] == held["embedding_and_head"]
            == 2 * 12544 * 2048)
    assert under("_final_norm.") == held["final_norm"] == 2048
    assert sum(by.values()) == held["all"] == 490297344
    # 16 bytes a parameter for the program, 20 for the reference trainer
    assert 16 * held["all"] / 1e9 == pytest.approx(7.84, abs=0.01)
    assert 20 * held["all"] / 1e9 == pytest.approx(9.81, abs=0.01)
    # the metrics the cell reports, each with a reader and a data file
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert names[:len(PR39)] == PR39
    for name in PR39:
        reader, data = cell.layer_metric(name)
        assert callable(reader.read)
        # nothing to read: nothing read, and no raise
        assert reader.read({"trace": None, "spans": {}, "flops": 0,
                            "window_s": 0.0}, data) is None
    assert [m["name"] for m in cell.metrics("end_to_end")] == [
        "train_units_per_s", "setup_s"]
    for name, scope in (("gated_attention_roofline", "attn.core"),
                        ("moe_gmm_roofline", "moe.gmm")):
        _, data = cell.layer_metric(name + ".gated_tokens")
        assert data["cell"] == LAGUNA_CELL and data["hbm_bytes_per_s"] == 819e9
        assert (data["scope"], data["op"]) == (scope, "custom-call")
        assert "819 GB/s" in data["hbm_source"]
        assert callable(getattr(cell.model, data["cost"]))
    for name, holds in (("attention_gate_busy_share", "attn.gate"),
                        ("moe_route_busy_share", "moe.route")):
        reader, data = cell.layer_metric(name + ".gated_tokens")
        assert reader.__name__.endswith("inner_scope_busy_share")
        assert data == {"reader": "inner_scope_busy_share", "holds": holds}


def test_laguna_entries_stand_as_one_run_and_the_benchmark_had_the_rest():
    """One configuration, one cell on one chip and nineteen metrics in one
    run, wherever later entries leave them; the cell's name among the rate's
    cells; one new reader, the other eighteen metrics data files on readers
    the benchmark had."""
    bench = harness.load_benchmark()
    assert [c["name"] for c in bench["configs"]].count("laguna_xs2_ep16") == 1
    (mine,) = [w for w in bench["workloads"] if w["name"] == LAGUNA_CELL]
    assert mine == {"name": LAGUNA_CELL, "config": "laguna_xs2_ep16",
                    "traffic": "train_seq8192", "chips": 1,
                    "why": mine["why"]}
    assert len(mine["why"]) <= 200 and "sixteenth" in mine["why"]
    assert [w["config"] for w in bench["workloads"]].count(
        "laguna_xs2_ep16") == 1              # ONE cell
    per = bench["per_layer"]
    names = [m["name"] for m in per]
    at = names.index(PR39[0])
    assert names[at: at + len(PR39)] == PR39
    rate, setup = bench["end_to_end"]
    assert LAGUNA_CELL in rate["workloads"] and "workloads" not in setup
    for m in per[at: at + len(PR39)]:
        assert m["workloads"] == [LAGUNA_CELL]
        if m["name"].startswith("setup_"):
            assert (m["unit"], m["better"], m["moves"], m["source"]) == (
                "s", "lower", "setup_s", "program_counter")
        else:
            assert (m["unit"], m["moves"]) == ("%", "train_units_per_s")
            assert m["better"] == ("higher" if m["name"].startswith("mfu.")
                                   or "roofline" in m["name"] else "lower")
    by = {m["name"]: m for m in per}
    for name in ("attention_gate_busy_share", "moe_route_busy_share"):
        assert by[name + ".gated_tokens"]["layer"] == (
            "step program and model graph")
    for name in ("gated_attention_roofline", "moe_gmm_roofline"):
        assert by[name + ".gated_tokens"]["layer"] == "kernels"
    here = os.path.join(harness.ROOT, "benchmarks", "layer_metrics")
    readers = {json.load(open(os.path.join(here, n + ".json")))["reader"]
               for n in PR39}
    assert readers == {
        "mfu", "device_idle_share", "program_span_share", "scope_busy_share",
        "kernel_roofline", "program_setup_seconds", "inner_scope_busy_share"}


def test_laguna_analytic_counts_are_the_issues_arithmetic():
    cell = harness.Cell(LAGUNA_CELL)
    ref = cell.model.reference
    parts = ref.forward_flops_per_token(cell.config, 8192)
    total = sum(parts.values())
    step = {k: 3 * 16384 * v / 1e12 for k, v in parts.items()}
    # a step at 3 x forward: 38.8 TFLOP; attention's pairs 12.3 of it (the
    # kernels' own count, with the backward's fifth product, is 14.3:
    # ISSUE 39's 40.9 takes that one), projections 17.0, layer 0's dense
    # block 4.9, the head 2.5, shared experts 1.24, routed experts held 0.62
    assert 3 * 16384 * total == pytest.approx(38.79e12, rel=2e-3)
    assert step["attention"] == pytest.approx(12.29, rel=2e-3)
    assert step["projections"] == pytest.approx(16.96, rel=2e-3)
    assert step["dense"] == pytest.approx(4.95, rel=2e-3)
    assert step["head"] == pytest.approx(2.53, rel=2e-3)
    assert step["shared"] == 2 * step["experts"] == pytest.approx(
        1.237, rel=2e-3)
    assert step["router"] == pytest.approx(0.206, rel=2e-3)
    shares = {k: round(100 * v / total, 1) for k, v in parts.items()}
    assert shares == {"projections": 43.7, "attention": 31.7, "dense": 12.8,
                      "shared": 3.2, "experts": 1.6, "router": 0.5,
                      "head": 6.5}
    assert 16384 * cell.model.train_flops_per_row(
        cell.config, cell.traffic) == 3 * 16384 * total
    window = 512 * 513 // 2 + (8192 - 512) * 512
    assert window == 4063488 and ref.attended_keys(8192, 512) == window
    assert ref.attended_keys(8192) == 8192 * 8193 // 2 == 33558528
    assert ref.attended_keys(300, 512) == 300 * 301 // 2
    assert parts["attention"] == 512 * (
        2 * 48 * 33558528 + 3 * 64 * window) / 8192
    assert parts["experts"] == 4 * (8 * 16 / 256) * 3 * 2 * 2048 * 512
    # the gate's projection is in the projections: 2 x 2048 x heads a layer
    assert parts["projections"] == 2 * 2048 * (
        2 * (2 * 48 * 128 + 2 * 8 * 128 + 48)
        + 3 * (2 * 64 * 128 + 2 * 8 * 128 + 64))


def test_laguna_kernels_costs_are_the_models_work_and_read_nothing_of_the_program(
        monkeypatch):
    """What a roofline share is a share of: mask-exact pairs for each
    layer's OWN head count, 512 operations a pair forward and 1,280
    backward; 3 products a projection on the expected slots; no
    recomputation, though the configuration asks for it; the gate's product
    nowhere; and no module of the program."""
    cell = harness.Cell(LAGUNA_CELL)
    assert cell.config["recompute"] == "block"
    for name in [m for m in sys.modules if m.startswith("paddle_tpu")]:
        monkeypatch.setitem(sys.modules, name, None)   # an import raises
    monkeypatch.setitem(sys.modules, "paddle_tpu", None)
    attn = cell.model.gated_attention_cost(cell.config, cell.traffic)
    pairs = 2 * (2 * 48 * 33558528 + 3 * 64 * 4063488)
    assert attn["flops"] == pairs * (512 + 1280)
    assert attn["flops"] == pytest.approx(14.3e12, rel=5e-3)
    q48, q64, kv = (16384 * 48 * 128 * 2, 16384 * 64 * 128 * 2,
                    16384 * 8 * 128 * 2)
    assert attn["bytes"] == 6 * (2 * q48 + 3 * q64 + 5 * kv)
    gmm = cell.model.moe_gmm_cost(cell.config, cell.traffic)
    rows = 16384 * 8 * 16 / 256
    assert rows == 8192
    parts = cell.model.reference.forward_flops_per_token(cell.config, 8192)
    assert gmm["flops"] == pytest.approx(16384 * 3 * parts["experts"])
    assert gmm["flops"] == 4 * 3 * 3 * 2 * rows * 2048 * 512
    assert gmm["flops"] == pytest.approx(0.62e12, rel=5e-3)
    a_call = (rows * (2048 + 512) + 16 * 2048 * 512) * 2
    assert gmm["bytes"] == 4 * 3 * 3 * a_call
    assert gmm["bytes"] == pytest.approx(2.7e9, rel=2e-2)
    # the bytes bind: 3.3 ms of HBM time against 3.1 ms of the matrix unit's
    assert gmm["bytes"] / 819e9 > gmm["flops"] / 197e12
    # a count by hand at the tiny size: 1 row of 4 positions, a window of 2
    cell = tiny_laguna_cell()
    cell.traffic.update(batch=1, lengths={"seq": [4, 4]})
    cell.config.update(sliding_window=2)
    attn = cell.model.gated_attention_cost(cell.config, cell.traffic)
    # pairs a head: full 1 + 2 + 3 + 4, window 1 + 2 + 2 + 2; heads 4, 8, 4;
    # a pair 2 x 2 x 16 forward and 5 x 2 x 16 backward
    assert attn["flops"] == (4 * 10 + 8 * 7 + 4 * 10) * (64 + 160)
    assert attn["bytes"] == 6 * 4 * 16 * 2 * ((4 + 2) + (8 + 2) + (4 + 2))


def test_inner_scope_reader_takes_the_scope_wherever_it_stands_in_the_path():
    from benchmarks.layer_metrics import inner_scope_busy_share as I

    ops = {"/device:TPU:0": [("fusion.1", 0, 10), ("fusion.2", 10, 30),
                             ("dot.3", 30, 60), ("fusion.4", 55, 70),
                             ("copy.5", 90, 100)],
           "/device:TPU:1": [("fusion.1", 0, 5)]}
    # {operation: (scope path, HLO line)}, as kernel_roofline._scopes gives
    paths = {"/device:TPU:0": {
        "fusion.1": ("jit(step)/checkpoint/gqa_attention:l0_attn/attn.gate/"
                     "mul", "%fusion.1 = fusion()"),
        "fusion.2": ("jit(step)/transpose(jvp(checkpoint))/gqa_attention:"
                     "l0_attn/attn.gate/dot_general", "%fusion.2 = fusion()"),
        "dot.3": ("jit(step)/checkpoint/gqa_attention:l0_attn/dot_general",
                  "%dot.3 = convolution()"),
        "fusion.4": ("jit(step)/rematted_computation/moe:l1_moe/moe.route/"
                     "sort", "%fusion.4 = fusion()")}}
    # the fullest device's busy time: 0-70 and 90-100; under attn.gate 0-30
    assert I.busy_under(ops, paths, (0, 100), "attn.gate") == (80, 30)
    assert I.busy_under(ops, paths, (0, 100), "moe.route") == (80, 15)
    # the window clips, and a name nobody carries reads 0 of the busy time
    assert I.busy_under(ops, paths, (20, 95), "attn.gate") == (55, 10)
    assert I.busy_under(ops, paths, (0, 100), "ssm.scan") == (80, 0)
    assert I.busy_under({}, paths, (0, 100), "attn.gate") is None
    # no trace, or a trace without the scope (the parent's): nothing
    assert I.read({"trace": None}, {"holds": "attn.gate"}) is None
    assert I.read({}, {"holds": "attn.gate"}) is None


def test_laguna_sound_run_is_correct():
    res = _measure(tiny_laguna_cell())
    assert res["correct"], (res["compared"], res["problems"])
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"train_units_per_s", "setup_s"}
    assert [row[0] for row in res["compared"]] == [
        "grad1_median_gap", "grad1_total_gap", "delta_median_gap",
        "delta_total_gap"]


def test_laguna_half_batch_is_not_correct(monkeypatch):
    build = T.build_trainer

    def broken(*args, **kw):
        trainer = build(*args, **kw)
        trainer.step_fn = Broken(trainer.step_fn, _half_batch)
        return trainer

    monkeypatch.setattr(T, "build_trainer", broken)
    res = _measure(tiny_laguna_cell())
    assert not res["correct"]
    assert [n for n, v, lim in res["compared"] if not v <= lim]


def test_laguna_fp8_control_is_not_correct():
    cell = tiny_laguna_cell()
    pool = traffic.Pool(cell.traffic, LAGUNA_SEED)
    ref = T.reference_readings(cell, LAGUNA_SEED, pool)
    low = T.reference_readings(cell, LAGUNA_SEED, pool, mode="fp8")
    ok, rows = compare.judge(compare.training_numbers(low, ref), cell.limits)
    assert not ok, rows
    ok, rows = compare.judge(compare.training_numbers(ref, ref), cell.limits)
    assert ok, rows
