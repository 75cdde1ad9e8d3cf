"""The benchmark's cheap tests in tier-1: `benchmarks/tests/test_harness.py`
(files and names, the last line's keys, the no-TPU refusal, `trace_reduce`,
the readers, a cell added as new files) and `test_program_spans.py` (the
readers of the program's spans and scopes). They run here as they stand
there, but for one: `test_harness.py` holds the cell's per-layer metrics to
the three of PR 24 by an exact comparison, and no PR but a `benchmark` PR may
edit that file, so the same test is given here over the metrics it names."""

import pytest

from benchmarks import harness
from benchmarks.tests.test_harness import *  # noqa: F401,F403
from benchmarks.tests.test_program_spans import *  # noqa: F401,F403
from benchmarks.tests.test_program_spans import NEW


def test_readers_read_the_run_and_return_nothing_where_nothing_is(
        monkeypatch, tmp_path):
    from benchmarks import program_spans

    monkeypatch.setattr(program_spans, "TRACE_DIR", str(tmp_path))
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
    # the new readers read the trace's file, and this run has none
    assert [got.pop(n) for n in NEW] == [None] * len(NEW)
    assert got == {"input_wait_share.images": 50.0,
                   "mfu.images": pytest.approx(200.0),   # 3e12/1.5 s/1e12
                   "device_idle_share.images": 62.5}
