"""SPMD partitioning & collective-schedule auditor (ISSUE 15) against
the COMMITTED mc_* captures plus seeded violations.

The acceptance contract mirrors test_hlo_audit: every audit family is
proven to BITE on a violating module — a replicated table above the
floor, a channel order contradicting data flow, a duplicate channel,
a split permute ring — not just pass on the clean committed captures.
All jax-free (pure text fixtures + committed artifacts).
"""

import gzip
import json
import os

import pytest

from paddle_tpu.analysis import hlo_audit, hlo_text, spmd_audit

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACES = os.path.join(REPO, "tools", "traces")
BUDGETS = os.path.join(TRACES, "audit_budgets.json")

MC_STEMS = (
    "mc_longctx_ring_t32768",
    "mc_longctx_ulysses_t32768",
    "mc_dp_train",
    "mc_sparse_lookup",
    "mc_sparse_update",
    "mc_sparse_shard_step",
)


def _budgets():
    with open(BUDGETS) as f:
        return json.load(f)


# ---- seeded fixtures ----------------------------------------------
# A well-formed 8-partition module: sharded params, one ring permute
# (ch 1) feeding one all-reduce (ch 2) — channel order agrees with
# data flow, the ring is a single 8-cycle.
GOOD = """\
HloModule seeded_good, is_scheduled=true, num_partitions=8

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(f32[] %a, f32[] %b)
}

ENTRY %main (p0: f32[1024,64]) -> f32[128,64] {
  %p0 = f32[1024,64]{1,0} parameter(0), sharding={devices=[8,1]<=[8]}
  %slice = f32[128,64]{1,0} slice(f32[1024,64]{1,0} %p0), slice={[0:128], [0:64]}
  %cp = f32[128,64]{1,0} collective-permute(f32[128,64]{1,0} %slice), channel_id=1, source_target_pairs={{0,1},{1,2},{2,3},{3,4},{4,5},{5,6},{6,7},{7,0}}
  ROOT %ar = f32[128,64]{1,0} all-reduce(f32[128,64]{1,0} %cp), channel_id=2, replica_groups={{0,1,2,3,4,5,6,7}}, use_global_device_ids=true, to_apply=%add
}
"""

# the same program with the big parameter REPLICATED: 1024*64*4 =
# 262144 bytes on every chip
REPLICATED = GOOD.replace(
    "sharding={devices=[8,1]<=[8]}", "sharding={replicated}"
).replace("seeded_good", "seeded_replicated")

# channel numbers inverted: the all-reduce (ch 1) consumes the
# permute (ch 2) — data flow forces permute first, channels promise
# the opposite
BAD_ORDER = (
    GOOD.replace("channel_id=1, source_target_pairs",
                 "channel_id=9, source_target_pairs")
    .replace("channel_id=2, replica_groups",
             "channel_id=1, replica_groups")
    .replace("seeded_good", "seeded_order")
)

# two collectives on one rendezvous channel
DUP_CHANNEL = GOOD.replace(
    "channel_id=2, replica_groups", "channel_id=1, replica_groups"
).replace("seeded_good", "seeded_dup")

# the ring split into two disjoint 4-cycles: same pair count, same
# bytes, deadlocks the ring reduction
SPLIT_RING = GOOD.replace(
    "{{0,1},{1,2},{2,3},{3,4},{4,5},{5,6},{6,7},{7,0}}",
    "{{0,1},{1,2},{2,3},{3,0},{4,5},{5,6},{6,7},{7,4}}",
).replace("seeded_good", "seeded_split")

# an open chain: rank 0 sends, rank 7 receives, the ring never closes
OPEN_CHAIN = GOOD.replace(
    "{{0,1},{1,2},{2,3},{3,4},{4,5},{5,6},{6,7},{7,0}}",
    "{{0,1},{1,2},{2,3},{3,4},{4,5},{5,6},{6,7}}",
).replace("seeded_good", "seeded_open")

POLICY = {
    "num_partitions": 8,
    "replication_floor_bytes": 200000,
    "require_collectives": ["collective-permute", "all-reduce"],
    "require_single_ring": True,
}


def _checks(text, policy=POLICY):
    checks, _ = spmd_audit.spmd_checks(text, policy)
    return {c["name"]: c for c in checks}


class TestSeededViolations:
    def test_good_module_passes_every_family(self):
        by = _checks(GOOD)
        assert all(c["ok"] for c in by.values()), [
            c for c in by.values() if not c["ok"]
        ]
        assert by["spmd.schedule.permute_ring"]["permutes"] == 1

    def test_replicated_tensor_above_floor_bites(self):
        by = _checks(REPLICATED)
        rep = by["spmd.replication"]
        assert not rep["ok"]
        assert "262144" in rep["offenders"][0]
        assert "EVERY device" in rep["detail"]
        # raising the floor above the tensor admits it
        by2 = _checks(
            REPLICATED, {**POLICY, "replication_floor_bytes": 300000}
        )
        assert by2["spmd.replication"]["ok"]
        # ... as does naming it in allow_replicated
        by3 = _checks(
            REPLICATED, {**POLICY, "allow_replicated": ["p0"]}
        )
        assert by3["spmd.replication"]["ok"]

    def test_channel_order_against_dataflow_bites(self):
        by = _checks(BAD_ORDER)
        order = by["spmd.schedule.channel_order"]
        assert not order["ok"]
        assert "deadlock" in order["detail"]
        # GOOD has the same dependency with channels agreeing
        assert _checks(GOOD)["spmd.schedule.channel_order"]["ok"]

    def test_duplicate_channel_bites(self):
        by = _checks(DUP_CHANNEL)
        uniq = by["spmd.schedule.channel_unique"]
        assert not uniq["ok"]
        assert "channel 1" in uniq["detail"]

    def test_split_ring_bites(self):
        ring = _checks(SPLIT_RING)["spmd.schedule.permute_ring"]
        assert not ring["ok"]
        assert "2 disjoint cycle(s)" in ring["detail"]

    def test_open_chain_bites(self):
        ring = _checks(OPEN_CHAIN)["spmd.schedule.permute_ring"]
        assert not ring["ok"]
        assert "open chain" in ring["detail"]

    def test_split_ring_legal_without_single_ring_pin(self):
        """A split ring is a valid partial permutation — only the
        `require_single_ring` policy elevates it to a violation (dp
        captures legally permute within subgroups)."""
        p = {k: v for k, v in POLICY.items()
             if k != "require_single_ring"}
        assert _checks(SPLIT_RING, p)["spmd.schedule.permute_ring"][
            "ok"
        ]

    def test_wrong_partition_count_bites(self):
        by = _checks(GOOD, {**POLICY, "num_partitions": 16})
        part = by["spmd.partitioning"]
        assert not part["ok"]
        assert part["num_partitions"] == 8
        assert "vacuous" in part["detail"]

    def test_require_and_forbid_kinds_bite(self):
        by = _checks(GOOD, {**POLICY,
                            "require_collectives": ["all-to-all"]})
        assert not by["spmd.require.all-to-all"]["ok"]
        by2 = _checks(
            GOOD,
            {**POLICY, "forbid_collectives": ["collective-permute"]},
        )
        forbid = by2["spmd.forbid.collective-permute"]
        assert not forbid["ok"] and forbid["count"] == 1

    def test_collective_byte_budget_bites(self):
        # GOOD moves 2 * 128*64*4 = 65536 collective bytes
        by = _checks(
            GOOD, {**POLICY, "collective_total_bytes_max": 40000}
        )
        tot = by["spmd.collective_total_bytes"]
        assert not tot["ok"] and tot["measured"] == 65536
        by2 = _checks(
            GOOD, {**POLICY, "largest_collective_bytes_max": 10000}
        )
        assert not by2["spmd.collective_largest_bytes"]["ok"]


class TestCommittedCaptures:
    def test_policy_split_covers_every_stem_once(self):
        """Every mc_* stem is an SPMD policy; no non-mc stem is —
        the hlo-audit/spmd-audit pass split audits each stem exactly
        once."""
        budgets = {
            k: v for k, v in _budgets().items()
            if not k.startswith("_")
        }
        spmd = {k for k, v in budgets.items()
                if spmd_audit.is_spmd_policy(v)}
        assert spmd == set(MC_STEMS)

    @pytest.mark.parametrize("stem", MC_STEMS)
    def test_committed_capture_passes_and_is_fresh(self, stem):
        rep = hlo_audit.audit_capture(
            os.path.join(TRACES, stem + ".hlo.txt.gz"),
            _budgets()[stem],
        )
        assert rep["ok"], [c for c in rep["checks"] if not c["ok"]]
        assert rep["num_partitions"] == 8
        assert rep["collectives"]["count"] >= 1
        names = {c["name"] for c in rep["checks"]}
        # every family present on every SPMD capture
        assert {"spmd.partitioning", "spmd.replication",
                "spmd.schedule.channel_unique",
                "spmd.schedule.channel_order",
                "spmd.schedule.permute_ring"} <= names
        with open(os.path.join(TRACES, stem + ".audit.json")) as f:
            assert json.load(f) == rep, f"{stem}.audit.json is stale"

    def test_ring_capture_proves_the_ring(self):
        rep = json.load(
            open(os.path.join(
                TRACES, "mc_longctx_ring_t32768.audit.json"
            ))
        )
        by = {c["name"]: c for c in rep["checks"]}
        assert by["spmd.schedule.permute_ring"]["permutes"] >= 2
        assert by["spmd.schedule.permute_ring"]["require_single_ring"]
        assert rep["collectives"]["by_kind"][
            "collective-permute"]["count"] >= 2

    def test_ulysses_capture_proves_the_all_to_all(self):
        rep = json.load(
            open(os.path.join(
                TRACES, "mc_longctx_ulysses_t32768.audit.json"
            ))
        )
        assert rep["collectives"]["by_kind"][
            "all-to-all"]["count"] >= 2

    def test_sparse_captures_never_gather_the_table(self):
        for stem in ("mc_sparse_lookup", "mc_sparse_update",
                     "mc_sparse_shard_step"):
            by_kind = json.load(
                open(os.path.join(TRACES, stem + ".audit.json"))
            )["collectives"]["by_kind"]
            assert "all-gather" not in by_kind

    def test_seeded_all_gather_fails_sparse_shard_policy(self):
        """ISSUE 20 satellite: the new all-gather-forbidden policy
        BITES. Take the good seeded module, swap its all-reduce for
        an all-gather (the repartition that would pull every hot
        cache onto every chip), and audit under the committed
        mc_sparse_shard_step policy: spmd.forbid.all-gather must
        fail, and the required all-reduce goes missing too."""
        gathered = GOOD.replace(
            "ROOT %ar = f32[128,64]{1,0} all-reduce("
            "f32[128,64]{1,0} %cp), channel_id=2, "
            "replica_groups={{0,1,2,3,4,5,6,7}}, "
            "use_global_device_ids=true, to_apply=%add",
            "ROOT %ag = f32[1024,64]{1,0} all-gather("
            "f32[128,64]{1,0} %cp), channel_id=2, "
            "replica_groups={{0,1,2,3,4,5,6,7}}, "
            "use_global_device_ids=true, dimensions={0}",
        ).replace("seeded_good", "seeded_gathered")
        assert "all-gather" in gathered  # the mutation took
        policy = dict(_budgets()["mc_sparse_shard_step"])
        by = _checks(gathered, policy)
        assert not by["spmd.forbid.all-gather"]["ok"]
        assert by["spmd.forbid.all-gather"]["count"] == 1
        assert not by["spmd.require.all-reduce"]["ok"]
        # the committed capture passes the SAME policy object
        rep = hlo_audit.audit_capture(
            os.path.join(TRACES, "mc_sparse_shard_step.hlo.txt.gz"),
            policy,
        )
        assert rep["ok"], [c for c in rep["checks"] if not c["ok"]]

    def test_tightened_budget_fails_the_committed_capture(self):
        """The exact mechanism by which a future replication/byte
        regression fails CI, run against the real ring capture."""
        policy = dict(_budgets()["mc_longctx_ring_t32768"])
        policy["replication_floor_bytes"] = 1 << 20  # below params
        rep = hlo_audit.audit_capture(
            os.path.join(
                TRACES, "mc_longctx_ring_t32768.hlo.txt.gz"
            ),
            policy,
        )
        by = {c["name"]: c for c in rep["checks"]}
        assert not by["spmd.replication"]["ok"]
        assert by["spmd.replication"]["offenders"]


class TestHloTextSpmdParsing:
    """hlo_text edge cases the SPMD parser added (satellite 3)."""

    def test_tuple_shape_with_index_comments_parses(self):
        """Tuple shapes carry /*index=N*/ comments from 6 elements up
        — the instruction matcher must not lose them (the nmt decode
        capture's big while carries were invisible before ISSUE 15)."""
        line = (
            "  %t = (f32[16]{0}, f32[16]{0}, f32[16]{0}, f32[16]{0}, "
            "f32[16]{0}, /*index=5*/f32[16]{0}) tuple(%a, %b, %c, "
            "%d, %e, %f)"
        )
        got = list(hlo_text.iter_instructions([line]))
        assert len(got) == 1
        name, out_shape, opcode, _ops, _l = got[0]
        assert name == "t" and opcode == "tuple"
        assert hlo_text.shape_bytes(out_shape) == 6 * 16 * 4

    def test_tuple_sharding_round_trip(self):
        line = (
            "  %t = (f32[256,8]{1,0}, f32[1024,64]{1,0}) "
            "tuple(%x, %y), sharding={{devices=[8,1]<=[8]}, "
            "{replicated}}"
        )
        sh = hlo_text.parse_sharding(line)
        assert sh["kind"] == "tuple" and len(sh["elements"]) == 2
        assert not hlo_text.sharding_is_replicated(sh["elements"][0])
        assert hlo_text.sharding_is_replicated(sh["elements"][1])
        # element-wise pairing in the replication check: only the
        # REPLICATED leaf's bytes count against the floor
        check = spmd_audit.check_replication(
            [line], {"replication_floor_bytes": 100000}
        )
        assert not check["ok"]
        assert len(check["offenders"]) == 1
        assert "t[1]" in check["offenders"][0]

    def test_trivial_tile_is_replicated(self):
        """devices=[1,1]<=[1] tiles nothing — semantically
        replicated."""
        assert hlo_text.sharding_is_replicated(
            hlo_text.parse_sharding("sharding={devices=[1,1]<=[1]}")
        )
        assert hlo_text.sharding_is_replicated(
            hlo_text.parse_sharding(
                "sharding={maximal device=3}"
            )
        )
        assert not hlo_text.sharding_is_replicated(
            hlo_text.parse_sharding(
                "sharding={devices=[8,1]<=[8]}"
            )
        )

    def test_collectives_in_nested_bodies_are_attributed(self):
        text = """\
HloModule nested, num_partitions=8

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(f32[] %a, f32[] %b)
}

%body (carry: (s32[], f32[64])) -> (s32[], f32[64]) {
  %carry = (s32[], f32[64]{0}) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[64]{0}) %carry), index=0
  %x = f32[64]{0} get-tuple-element((s32[], f32[64]{0}) %carry), index=1
  %ar.0 = f32[64]{0} all-reduce(f32[64]{0} %x), channel_id=3, replica_groups={{0,1,2,3,4,5,6,7}}, use_global_device_ids=true, to_apply=%add
  ROOT %out = (s32[], f32[64]{0}) tuple(s32[] %i, f32[64]{0} %ar.0)
}

%cond (carry: (s32[], f32[64])) -> pred[] {
  %carry = (s32[], f32[64]{0}) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[64]{0}) %carry), index=0
  %k = s32[] constant(4)
  ROOT %lt = pred[] compare(s32[] %i, s32[] %k), direction=LT
}

ENTRY %main (p: (s32[], f32[64])) -> (s32[], f32[64]) {
  %p = (s32[], f32[64]{0}) parameter(0)
  ROOT %w = (s32[], f32[64]{0}) while((s32[], f32[64]{0}) %p), condition=%cond, body=%body
}
"""
        colls = hlo_text.parse_collectives(text.splitlines())
        assert len(colls) == 1
        c = colls[0]
        assert c["kind"] == "all-reduce"
        assert c["computation"] == "body"
        assert c["channel_id"] == 3
        assert c["bytes"] == 64 * 4
        assert c["replica_groups"] == [[0, 1, 2, 3, 4, 5, 6, 7]]

    def test_async_pairs_count_once(self):
        lines = [
            "ENTRY %main (p0: f32[64]) -> f32[64] {",
            "  %p0 = f32[64]{0} parameter(0)",
            "  %s = f32[64]{0} all-reduce-start(f32[64]{0} %p0), "
            "channel_id=1, replica_groups={{0,1,2,3,4,5,6,7}}, "
            "use_global_device_ids=true, to_apply=%add",
            "  ROOT %d = f32[64]{0} all-reduce-done(f32[64]{0} %s)",
            "}",
        ]
        colls = hlo_text.parse_collectives(lines)
        assert len(colls) == 1
        assert colls[0]["kind"] == "all-reduce"

    def test_tpu_tiled_layouts_in_a_tuple_shape(self):
        """Compiled for a TPU, layouts carry tilings with parens of
        their own — `T(2,128)(2,1)S(1)` — inside the tuple shape of
        every async -start op. Lines as the v5e compiler printed them
        for ring attention (PR 21): the parser used to see none."""
        tiled = "bf16[2,2,2,8]{3,2,1,0:T(2,128)(2,1)S(1)}"
        lines = [
            "ENTRY %main (p0: bf16[2,2,2,8]) -> bf16[2,2,2,8] {",
            f"  %p0 = {tiled} parameter(0)",
            f"  %collective-permute-start = ({tiled}, {tiled}, "
            "u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start("
            "%p0), channel_id=1, "
            "source_target_pairs={{0,1},{1,2},{2,3},{3,0}}",
            f"  ROOT %collective-permute-done = {tiled} "
            "collective-permute-done(%collective-permute-start)",
            "}",
        ]
        colls = hlo_text.parse_collectives(lines)
        assert [c["kind"] for c in colls] == ["collective-permute"]
        assert colls[0]["source_target_pairs"] == [
            (0, 1), (1, 2), (2, 3), (3, 0)
        ]

    def test_nested_tuple_alias_map(self):
        """input_output_alias with nested tuple indices on both
        sides."""
        text = (
            "HloModule x, input_output_alias={ {0}: (0, {0}, "
            "may-alias), {1, 2}: (1, {}, may-alias), {3}: (4, {1, 0},"
            " may-alias) }, entry_computation_layout={()->f32[]}"
        )
        assert hlo_text.parse_input_output_alias(text) == [0, 1, 4]

    def test_iota_replica_groups_expand(self):
        line = (
            "  %ar = f32[64]{0} all-reduce(f32[64]{0} %x), "
            "channel_id=1, replica_groups=[2,4]<=[8], "
            "use_global_device_ids=true, to_apply=%add"
        )
        colls = hlo_text.parse_collectives(
            ["ENTRY %main (p: f32[]) -> f32[] {", line, "}"]
        )
        assert colls[0]["replica_groups"] == [
            [0, 1, 2, 3], [4, 5, 6, 7]
        ]


class TestLintPassWiring:
    def test_spmd_audit_pass_green_on_committed_tree(self):
        import subprocess
        import sys

        r = subprocess.run(
            [sys.executable, "tools/framework_lint.py", "spmd-audit"],
            capture_output=True, text=True, cwd=REPO, timeout=120,
        )
        assert r.returncode == 0, r.stderr
        assert "OK (spmd-audit)" in r.stdout

    def test_stale_spmd_report_is_a_violation(self, tmp_path):
        """Freshness discipline: a committed mc_* audit report that
        no longer matches its capture fails the pass."""
        import shutil
        import subprocess
        import sys

        repo2 = tmp_path / "repo"
        (repo2 / "tools").mkdir(parents=True)
        shutil.copytree(TRACES, str(repo2 / "tools" / "traces"))
        stale = repo2 / "tools" / "traces" / \
            "mc_sparse_lookup.audit.json"
        rep = json.loads(stale.read_text())
        rep["collectives"]["count"] += 1
        stale.write_text(json.dumps(rep, indent=2) + "\n")
        r = subprocess.run(
            [sys.executable, os.path.join(
                REPO, "tools", "framework_lint.py"
            ), "spmd-audit", "--repo", str(repo2)],
            capture_output=True, text=True, timeout=120,
        )
        assert r.returncode == 1
        assert "STALE" in r.stderr
        assert "mc_sparse_lookup" in r.stderr

    def test_seeded_violation_fails_the_pass(self, tmp_path):
        """End-to-end BITE: a traces dir whose capture replicates
        above the floor fails `framework_lint spmd-audit`."""
        import subprocess
        import sys

        repo2 = tmp_path / "repo"
        traces = repo2 / "tools" / "traces"
        traces.mkdir(parents=True)
        with gzip.open(
            str(traces / "seeded.hlo.txt.gz"), "wt"
        ) as f:
            f.write(REPLICATED)
        (traces / "audit_budgets.json").write_text(json.dumps({
            "seeded": {
                "num_partitions": 8,
                "replication_floor_bytes": 200000,
            }
        }))
        r = subprocess.run(
            [sys.executable, os.path.join(
                REPO, "tools", "framework_lint.py"
            ), "spmd-audit", "--repo", str(repo2), "--write-audit"],
            capture_output=True, text=True, timeout=120,
        )
        # --write-audit writes the report but the violation still
        # fails the pass
        assert r.returncode == 1
        assert "spmd.replication" in r.stderr
