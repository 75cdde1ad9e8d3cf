"""The readers of the program's own spans and scopes, on hand-built traces:
an idle gap is split exactly among the spans that overlap it, the shares
add up, a scope is read off an operation's `op_name`, and the metadata of an
`.xplane.pb` is read off its bytes."""

import json
import os

import pytest

from benchmarks import harness, program_spans, trace_reduce
from benchmarks.layer_metrics import program_span_share, scope_busy_share

NEW = ["loop_input_wait_share.images", "idle_input_wait_share.images",
       "idle_dispatch_share.images", "idle_other_share.images",
       "hbm_pass_busy_share.images", "feed_worker_share.images"]

# one device, busy 10..40 and 70..90 of a window 0..100: idle 0..10, 40..70
# and 90..100. Two steps of the training thread; the second is cut by the
# window. The worker's spans lie on a thread of their own, across the steps.
OPS = {"/device:TPU:0": [("conv", 10, 40), ("bn", 70, 90)],
       "/device:TPU:1": [("conv", 10, 20)]}
SPANS = {
    "train.step": [(0, 64), (66, 120)],
    "train.input_wait.feeder": [(2, 8), (40, 58)],
    "train.dispatch": [(8, 12), (58, 62)],
    "train.h2d": [(9, 11), (59, 61)],
    "train.fetch": [(12, 40), (62, 63)],
    "train.handlers": [(63, 64)],
    "feed_ahead.reader": [(-5, 1), (50, 52)],
    "feed_ahead.feeder": [(1, 50), (52, 110)],
}


def _split():
    return program_spans.split(OPS, {k: trace_reduce.union(v)
                                     for k, v in SPANS.items()}, (0, 100))


def test_a_gap_two_spans_share_is_split_by_overlap():
    r = _split()
    # the gap 40..70: feeder 18, dispatch 2 + h2d 2 inside it, fetch 1,
    # handlers 1, the root alone 63..64 is the handlers', 64..66 no span,
    # 66..70 the root's own time; the worker, busy all through, takes none
    longest, cover = r["gaps"][0]
    assert longest == 30
    assert cover == {"train.input_wait.feeder": 18, "train.dispatch": 2,
                     "train.h2d": 2, "train.fetch": 1, "train.handlers": 1,
                     "uncovered": 2, "train.step": 4}
    assert sum(cover.values()) == longest
    # the breakdown's name for the gap (trace_reduce.attribute) is the span
    # that holds most of that split; the root, which wraps 28 of the 30,
    # holds its own 4, and the worker's spans are not in the cell's list
    merged = {k: trace_reduce.union(v) for k, v in SPANS.items()}
    names = harness.Cell("resnet50.train_bs256").workload["spans"]
    assert tuple(names) == program_spans.SPANS
    assert trace_reduce.attribute((40, 70), merged, names) == \
        "train.input_wait.feeder"
    assert trace_reduce.attribute((66, 70), merged, names) == "train.step"
    assert trace_reduce.attribute((64, 66), merged, names) == "uncovered"


def test_where_spans_nest_the_innermost_takes_what_it_covers():
    r = _split()
    idle = r["idle_by_span"]
    # 0..10: the root alone 2, feeder 6, dispatch 8..10 of which h2d has
    # 9..10
    assert idle["train.h2d"] == 1 + 2
    assert idle["train.dispatch"] == 1 + 2
    assert idle["train.input_wait.feeder"] == 6 + 18
    assert idle["train.step"] == 2 + 4 + 10    # 0..2, 66..70 and 90..100
    assert idle["uncovered"] == 2
    assert sum(idle.values()) == r["window"] - r["busy"] == 50
    assert not set(idle) & set(program_spans.WORKER_SPANS)
    # a span's own time is clipped to the window, nested or not, on the
    # training thread or the worker's
    assert r["span_time"]["train.step"] == 64 + 34
    assert r["span_time"]["train.fetch"] == 28 + 1
    assert r["span_time"]["feed_ahead.reader"] == 1 + 2
    assert r["span_time"]["feed_ahead.feeder"] == 49 + 48


def _shares(monkeypatch):
    r = _split()
    ns = {"window_s": r["window"], "busy_s": r["busy"],
          "span_s": r["span_time"], "idle_s": r["idle_by_span"]}
    monkeypatch.setattr(program_spans, "of_run", lambda run: ns)
    cell = harness.Cell("resnet50.train_bs256")
    out = {}
    for name in NEW[:4] + NEW[5:]:
        reader, data = cell.layer_metric(name)
        assert reader is program_span_share
        out[name] = reader.read({"trace": {}}, data)
    return r, out


def test_the_four_shares_and_the_busy_share_add_to_100(monkeypatch):
    r, got = _shares(monkeypatch)
    # the worker's share is of the window, beside the others and no part
    # of what adds to 100
    assert got.pop("feed_worker_share.images") == 100.0
    assert got == {"loop_input_wait_share.images": 24.0,
                   "idle_input_wait_share.images": 24.0,
                   "idle_dispatch_share.images": 7.0,
                   "idle_other_share.images": 19.0}
    busy_share = 100.0 * r["busy"] / r["window"]
    idle = [v for k, v in got.items() if k.startswith("idle_")]
    assert busy_share + sum(idle) == pytest.approx(100.0, abs=1e-9)


def test_a_program_without_the_spans_reads_nothing(monkeypatch):
    cell = harness.Cell("resnet50.train_bs256")
    for name in NEW:
        reader, data = cell.layer_metric(name)
        assert reader.read({"trace": None, "trace_file": "x"},
                           data) is None                       # no trace
        assert reader.read({"trace": {"busy_s": 1}}, data) is None  # no file
    assert program_spans.split({}, {}, (0, 1)) is None
    # a program that feeds on the training thread has no worker to read: a
    # share of the window is nothing there, never 0
    r = _split()
    for name in program_spans.WORKER_SPANS:
        del r["span_time"][name]
    monkeypatch.setattr(program_spans, "of_run", lambda run: {
        "window_s": r["window"], "span_s": r["span_time"],
        "idle_s": r["idle_by_span"]})
    reader, data = cell.layer_metric("feed_worker_share.images")
    assert reader.read({"trace": {}}, data) is None
    reader, data = cell.layer_metric("loop_input_wait_share.images")
    assert reader.read({"trace": {}}, data) == 24.0


def test_a_scope_is_read_off_an_operations_op_name():
    of = program_spans.scope_of
    assert of("jit(step)/jvp(batch_norm:bn2a)/jit(relu)/max") == \
        "batch_norm:bn2a"
    assert of("jit(step)/transpose(jvp(exconv:res2a))/"
              "conv_general_dilated:") == "exconv:res2a"
    assert of("jit(step)/optimizer/mul") == "optimizer"
    assert of("jit(step)/watchdog/select_n") == "watchdog"
    assert of("jit(step)/jit(main)/reduce_sum") == ""
    _, data = harness.Cell("resnet50.train_bs256").layer_metric(NEW[4])
    inside = [s for s in ("batch_norm:bn2a", "addto:res2a", "pool:pool1",
                          "optimizer", "watchdog")
              if program_spans.in_classes(s, data["scopes"])]
    assert len(inside) == 5
    for s in ("exconv:res2a", "fc:output", "optimizer_state:x", ""):
        assert not program_spans.in_classes(s, data["scopes"])


def test_scope_share_of_the_busy_time(monkeypatch):
    by = {"exconv:conv1": 6.0, "batch_norm:bn1": 1.0, "addto:a": 1.0,
          "optimizer": 0.5, "watchdog": 0.5, "": 1.0}
    monkeypatch.setattr(program_spans, "busy_of_run", lambda run: {
        "busy_s": 10.0, "scoped_s": 9.0, "by_scope_s": by})
    _, data = harness.Cell("resnet50.train_bs256").layer_metric(NEW[4])
    assert scope_busy_share.read({"trace": {}}, data) == 30.0
    monkeypatch.setattr(program_spans, "busy_of_run", lambda run: {
        "busy_s": 10.0, "scoped_s": 0.0, "by_scope_s": {"": 10.0}})
    assert scope_busy_share.read({"trace": {}}, data) is None


def test_busy_time_by_scope_on_a_hand_built_trace():
    ops = {"/device:TPU:0": [("%f.1 bf16[8]", 0, 40), ("%f.2 f32[8]", 30, 60),
                             ("%f.3 f32[8]", 80, 130), ("%copy f32[8]", 60, 70)],
           "/device:TPU:1": [("%f.1 bf16[8]", 0, 10)]}
    scopes = {"/device:TPU:0": {
        "%f.1 bf16[8]": "jit(step)/jvp(exconv:c1)/conv",
        "%f.2 f32[8]": "jit(step)/transpose(jvp(batch_norm:b1))/mul",
        "%f.3 f32[8]": "jit(step)/optimizer/mul"}}
    r = program_spans.busy_by_scope(ops, scopes, (0, 100))
    assert r["busy"] == 70 + 20                # the fullest chip, clipped
    assert r["by_scope"] == {"exconv:c1": 40, "batch_norm:b1": 30,
                             "optimizer": 20, "": 10}
    assert program_spans.busy_by_scope({}, {}, (0, 1)) is None


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def test_scopes_are_read_off_an_xplanes_bytes(tmp_path):
    """A hand-written XSpace: one device plane whose two operations carry
    `tf_op`, one as a string and one as a reference, and a host plane."""
    stat_meta = [_field(5, _field(1, 7) + _field(2, _field(1, 7) + _field(
        2, b"tf_op"))),
        _field(5, _field(1, 8) + _field(2, _field(1, 8) + _field(
            2, b"jit(step)/optimizer/mul"))),
        _field(5, _field(1, 9) + _field(2, _field(1, 9) + _field(
            2, b"flops")))]
    conv = _field(1, 1) + _field(2, b"%fusion.1 = bf16[8]") + _field(
        5, _field(1, 9) + _field(3, 12345)) + _field(
        5, _field(1, 7) + _field(5, b"jit(step)/jvp(exconv:c1)/conv"))
    upd = _field(1, 2) + _field(2, b"%fusion.2 = f32[8]") + _field(
        5, _field(1, 7) + _field(7, 8))
    bare = _field(1, 3) + _field(2, b"%copy.3 = f32[8]")
    events = [_field(4, _field(1, i) + _field(2, m))
              for i, m in ((1, conv), (2, upd), (3, bare))]
    # the plane's lines are passed over whole; so are fixed-width fields
    line = _field(3, _field(2, b"XLA Ops"))
    fixed = b"\x99\x06" + b"\0" * 8 + b"\x9d\x06" + b"\0" * 4   # 99: 64, 32
    device = _field(1, _field(2, b"/device:TPU:0") + line + fixed
                    + b"".join(stat_meta) + b"".join(events))
    host = _field(1, _field(2, b"/host:CPU") + b"".join(events))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(device + host)
    assert program_spans.scopes_of_file(str(path)) == {"/device:TPU:0": {
        "%fusion.1 = bf16[8]": "jit(step)/jvp(exconv:c1)/conv",
        "%fusion.2 = f32[8]": "jit(step)/optimizer/mul"}}


def test_every_new_metric_resolves_to_a_reader_and_a_data_file():
    bench = harness.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-len(NEW):] == NEW
    for name in NEW:
        m = entries[name]
        assert m["workloads"] == ["resnet50.train_bs256"]
        assert (m["unit"], m["better"], m["moves"]) == (
            "%", "lower", "train_units_per_s")
        reader, data = harness.Cell(m["workloads"][0]).layer_metric(name)
        assert callable(reader.read)
        assert os.path.isfile(os.path.join(
            harness.ROOT, "benchmarks", "layer_metrics", name + ".json"))
        assert json.dumps(data)
    assert {entries[n]["layer"] for n in NEW} == {
        "entry points", "device", "step program and model graph"}
