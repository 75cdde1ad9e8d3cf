"""Multi-step pipelining as a TRAINER option (ROADMAP 5d / ISSUE 12):
`SGD(steps_per_dispatch=N)` runs N consecutive batches as ONE jitted
scan-of-steps dispatch. The contract pinned here: the N-step trainer
walks the bit-level-identical training trajectory (per-step RNG and
optimizer math), fires the same per-batch events in the same order,
feeds evaluators every batch, and keeps the watchdog's on-device
non-finite skip semantics — only dispatch granularity changes.

This is what lets small-model bench rows measure the chip instead of
the per-program dispatch floor (the smallnet rows carry the
`pipeline_speedup` A/B field from exactly this option)."""

import jax
import numpy as np
import pytest

from paddle_tpu import dsl
from paddle_tpu.core.arg import id_arg, non_seq
from paddle_tpu.core.config import OptimizationConf
from paddle_tpu.trainer.events import EndIteration
from paddle_tpu.trainer.trainer import SGD


def _conf():
    with dsl.model() as m:
        x = dsl.data("x", dim=8)
        y = dsl.data("label", dim=(), is_ids=True)
        h = dsl.fc(x, size=16, act="relu")
        o = dsl.fc(h, size=4, act="")
        dsl.classification_cost(o, y)
    return m.conf


def _batches(n, bs=8, seed=0):
    rng = np.random.default_rng(seed)
    return [
        (rng.standard_normal((bs, 8)).astype(np.float32),
         rng.integers(0, 4, bs).astype(np.int32))
        for _ in range(n)
    ]


def _feeder(raw):
    return {"x": non_seq(raw[0]), "label": id_arg(raw[1])}


OPT = OptimizationConf(learning_method="adam", learning_rate=1e-2)


def _train_curve(spd, batches, num_passes=2, evaluators=None):
    t = SGD(_conf(), OPT, seed=7, steps_per_dispatch=spd,
            evaluators=evaluators)
    got = []
    t.train(
        reader=lambda: iter(batches), feeder=_feeder,
        num_passes=num_passes,
        event_handler=lambda e: got.append(e)
        if isinstance(e, EndIteration) else None,
    )
    return t, got


class TestTrajectoryEquality:
    @pytest.mark.parametrize("spd", [4, 5])
    def test_loss_curve_and_event_order_match_sequential(self, spd):
        """spd=5 over 12 batches also exercises the ragged tail chunk
        (12 % 5 != 0) — a partial chunk must continue the identical
        trajectory, not restart or pad it."""
        batches = _batches(12)
        _, seq_ev = _train_curve(1, batches)
        _, pip_ev = _train_curve(spd, batches)
        assert [(e.pass_id, e.batch_id) for e in seq_ev] == \
            [(e.pass_id, e.batch_id) for e in pip_ev]
        np.testing.assert_allclose(
            [e.cost for e in seq_ev], [e.cost for e in pip_ev],
            rtol=2e-5, atol=1e-6,
        )

    def test_shape_change_mid_pass_flushes_not_fails(self):
        """A differently-shaped batch mid-stream (ragged reader) makes
        the buffer flush early; training continues and every batch
        still fires its event once, in order."""
        batches = _batches(4) + _batches(1, bs=3, seed=9) + _batches(
            3, seed=5
        )
        _, ev = _train_curve(4, batches, num_passes=1)
        assert [(e.pass_id, e.batch_id) for e in ev] == [
            (0, i) for i in range(8)
        ]

    def test_evaluator_sees_every_batch(self):
        from paddle_tpu.core import flags as _flags

        evals = [{
            "type": "classification_error", "name": "err",
            "input": "__fc_1__", "label": "label",
        }]
        batches = _batches(8)
        prev = _flags.get_flag("log_period")
        _flags.set_flag("log_period", 2)
        try:
            t1, ev1 = _train_curve(1, batches, num_passes=1,
                                   evaluators=evals)
            t4, ev4 = _train_curve(4, batches, num_passes=1,
                                   evaluators=evals)
        finally:
            _flags.set_flag("log_period", prev)
        # the per-log-period results dicts (computed from evaluator
        # state over all batches so far) must agree batch-for-batch
        r1 = [e.evaluator_results for e in ev1 if e.evaluator_results]
        r4 = [e.evaluator_results for e in ev4 if e.evaluator_results]
        assert r1 == r4 and len(r1) == 4


class TestRunStepsApi:
    def test_run_steps_matches_run_step(self):
        batches = _batches(6)
        feeds = [_feeder(b) for b in batches]
        a = SGD(_conf(), OPT, seed=3)
        b = SGD(_conf(), OPT, seed=3)
        seq = [a.run_step(f)[0] for f in feeds]
        costs, finites, outs = b.run_steps(feeds)
        assert b.global_step == a.global_step == 6
        assert all(finites)
        np.testing.assert_allclose(seq, costs, rtol=2e-5, atol=1e-6)
        # outs leaves are stacked [n, ...]
        for leaf in jax.tree_util.tree_leaves(outs):
            assert leaf.shape[0] == 6

    def test_watchdog_skips_poisoned_batch_inside_chunk(self):
        """A NaN feed inside a chunk: that batch reports finite=False,
        the on-device skip keeps params clean, and the following
        batches in the SAME chunk train normally — identical to the
        sequential skip semantics."""
        batches = _batches(4)
        bad = batches[1][0].copy()
        bad[0, 0] = np.nan
        batches[1] = (bad, batches[1][1])
        feeds = [_feeder(b) for b in batches]
        t = SGD(_conf(), OPT, seed=3)
        assert t.step_fn.watchdog  # default-on flag
        costs, finites, _ = t.run_steps(feeds)
        assert finites == [True, False, True, True]
        assert all(np.isfinite(c) for i, c in enumerate(costs)
                   if i != 1)
        # params never poisoned: one more clean step stays finite
        c, fin, _ = t.run_steps([_feeder(_batches(1, seed=4)[0])])
        assert fin == [True] and np.isfinite(c[0])


def test_flag_default_and_validation():
    from paddle_tpu.core import flags as _flags

    assert _flags.get_flag("steps_per_dispatch") == 1
    with pytest.raises(ValueError):
        SGD(_conf(), OPT, steps_per_dispatch=0)
