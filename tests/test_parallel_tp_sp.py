"""Tensor-parallel, sequence-parallel (ring/Ulysses) and sharded-embedding
tests on the virtual 8-device CPU mesh (SURVEY.md §4 takeaway (3))."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.core.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    SEQ_AXIS,
    make_mesh,
    set_mesh,
)
from paddle_tpu.parallel import (
    Sharder,
    dense_attention,
    embedding_lookup,
    ring_attention,
    ulysses_attention,
)
from paddle_tpu.parallel.sparse import apply_rows, touched_rows


def rand(key, *shape):
    return jax.random.normal(jax.random.key(key), shape, jnp.float32)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, causal):
        mesh = make_mesh({DATA_AXIS: 2, SEQ_AXIS: 4})
        B, T, H, D = 4, 16, 2, 8
        q, k, v = rand(0, B, T, H, D), rand(1, B, T, H, D), rand(2, B, T, H, D)
        ref = dense_attention(q, k, v, causal=causal)
        out = ring_attention(q, k, v, mesh, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_kv_lens_mask(self):
        mesh = make_mesh({SEQ_AXIS: 8})
        B, T, H, D = 3, 16, 2, 4
        q, k, v = rand(3, B, T, H, D), rand(4, B, T, H, D), rand(5, B, T, H, D)
        lens = jnp.array([16, 9, 1], jnp.int32)
        ref = dense_attention(q, k, v, kv_len=lens)
        out = ring_attention(q, k, v, mesh, kv_lens=lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_grad_flows(self):
        mesh = make_mesh({SEQ_AXIS: 4})
        B, T, H, D = 2, 8, 2, 4
        q, k, v = rand(6, B, T, H, D), rand(7, B, T, H, D), rand(8, B, T, H, D)

        def loss_ring(q):
            return jnp.sum(ring_attention(q, k, v, mesh, causal=True) ** 2)

        def loss_dense(q):
            return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

        g1 = jax.grad(loss_ring)(q)
        g2 = jax.grad(loss_dense)(q)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-4)


class TestUlysses:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, causal):
        mesh = make_mesh({SEQ_AXIS: 4})
        B, T, H, D = 2, 16, 4, 8  # heads divisible by seq shards
        q, k, v = rand(0, B, T, H, D), rand(1, B, T, H, D), rand(2, B, T, H, D)
        lens = jnp.array([16, 11], jnp.int32)
        ref = dense_attention(q, k, v, causal=causal, kv_len=lens)
        out = ulysses_attention(q, k, v, mesh, causal=causal, kv_lens=lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


class TestShardedEmbedding:
    def test_lookup_matches_take(self):
        mesh = make_mesh({MODEL_AXIS: 8})
        V, D = 64, 5
        table = rand(0, V, D)
        ids = jnp.array([[0, 5, 63], [7, 8, 9]], jnp.int32)
        out = embedding_lookup(table, ids, mesh)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(jnp.take(table, ids, axis=0)), atol=1e-6
        )

    def test_backward_is_row_sparse(self):
        mesh = make_mesh({MODEL_AXIS: 4})
        V, D = 16, 3
        table = rand(1, V, D)
        ids = jnp.array([1, 3, 3], jnp.int32)

        g = jax.grad(
            lambda t: jnp.sum(embedding_lookup(t, ids, mesh) * 2.0)
        )(table)
        ref = jax.grad(lambda t: jnp.sum(jnp.take(t, ids, axis=0) * 2.0))(table)
        np.testing.assert_allclose(np.asarray(g), np.asarray(ref), atol=1e-6)
        # untouched rows get exactly zero gradient
        assert float(jnp.abs(g[0]).sum()) == 0.0

    def test_apply_rows_touched_only(self):
        V, D = 8, 2
        p = rand(2, V, D)
        grad = jnp.ones((V, D))
        t = touched_rows(jnp.array([2, 5]), V)
        new = apply_rows(lambda p, g: p - 0.1 * g, p, grad, t)
        np.testing.assert_allclose(np.asarray(new[2]), np.asarray(p[2] - 0.1))
        np.testing.assert_allclose(np.asarray(new[0]), np.asarray(p[0]))


class TestTensorParallelTraining:
    def test_dp_model_mesh_matches_single_device(self):
        """Same data, same init: a dp=2 × model=4 mesh training step must
        match the unsharded step (the exact-parity discipline of
        test_CompareTwoNets / checkRemoteParameterUpdater)."""
        from paddle_tpu.core.arg import id_arg, non_seq
        from paddle_tpu.core.config import OptimizationConf
        from paddle_tpu.dsl import (
            classification_cost,
            data,
            embedding,
            fc,
            model,
        )
        from paddle_tpu.network import Network
        from paddle_tpu.optimizers import create_optimizer
        from paddle_tpu.parallel.dp import TrainStep

        def make(mesh=None):
            with model() as m:
                x = data("x", dim=(16,))
                ids = data("ids", dim=(), is_ids=True)
                emb = embedding(ids, size=8, vocab_size=32, sharded=True)
                h = fc(x, emb, size=16, act="relu", name="h")
                out = fc(h, size=4, act="softmax", name="out")
                lbl = data("label", dim=(), is_ids=True)
                classification_cost(out, lbl)
            net = Network(m.conf)
            params = net.init_params(jax.random.key(0))
            opt = create_optimizer(
                OptimizationConf(learning_method="sgd", learning_rate=0.1),
                net.param_confs,
            )
            ostate = opt.init_state(params)
            step = TrainStep(net, opt, mesh=mesh, donate=False)
            if mesh is not None:
                params, ostate, _ = step.place(params, ostate, {})
            return net, step, params, ostate

        rng = np.random.default_rng(0)
        feed = {
            "x": non_seq(jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)),
            "ids": id_arg(rng.integers(0, 32, 8)),
            "label": id_arg(rng.integers(0, 4, 8)),
        }
        key = jax.random.key(9)

        _, step1, p1, o1 = make(mesh=None)
        p1, o1, _, loss1, _ = step1(p1, o1, {}, feed, 0, key)

        mesh = make_mesh({DATA_AXIS: 2, MODEL_AXIS: 4})
        set_mesh(mesh)
        _, stepN, pN, oN = make(mesh=mesh)
        pN, oN, _, lossN, _ = stepN(pN, oN, {}, feed, 0, key)

        np.testing.assert_allclose(float(loss1), float(lossN), rtol=1e-5)
        for name in p1:
            np.testing.assert_allclose(
                np.asarray(p1[name]),
                np.asarray(jax.device_get(pN[name])),
                atol=1e-5,
                err_msg=name,
            )

    def test_sharder_rules(self):
        from paddle_tpu.core.config import ParameterConf

        mesh = make_mesh({DATA_AXIS: 2, MODEL_AXIS: 4})
        s = Sharder(mesh, rules=[(r"special", P(MODEL_AXIS, None))])
        w = ParameterConf(name="_h.w0", dims=(16, 8))
        emb = ParameterConf(
            name="_e.w0", dims=(32, 8), sparse_remote_update=True
        )
        bad = ParameterConf(name="_o.w0", dims=(7, 9))  # indivisible
        spec_w = s.spec(w.name, w)
        assert spec_w == P(None, MODEL_AXIS)
        assert s.spec(emb.name, emb) == P(MODEL_AXIS, None)
        assert s.spec(bad.name, bad) == P()
        assert s.spec("special.w", bad) == P(MODEL_AXIS, None)


class TestAttentionLayer:
    @pytest.mark.parametrize("mode", ["none", "ring", "ulysses"])
    def test_layer_modes_agree(self, mode):
        from paddle_tpu.core.arg import seq
        from paddle_tpu.core.config import (
            InputConf,
            LayerConf,
            ModelConf,
        )
        from paddle_tpu.network import Network

        mesh = make_mesh({DATA_AXIS: 2, SEQ_AXIS: 4})
        set_mesh(mesh)
        B, T, D = 4, 8, 16
        conf = ModelConf(
            layers=[
                LayerConf(name="x", type="data", attrs={"dim": (D,), "is_seq": True}),
                LayerConf(
                    name="att",
                    type="multi_head_attention",
                    size=D,
                    bias=False,
                    inputs=[InputConf(name="x")],
                    attrs={"num_heads": 4, "causal": True, "seq_parallel": mode},
                ),
            ]
        )
        net = Network(conf)
        params = net.init_params(jax.random.key(0))
        x = seq(
            jax.random.normal(jax.random.key(1), (B, T, D)),
            jnp.array([8, 8, 5, 2], jnp.int32),
        )
        outs, _ = net.forward(params, {"x": x}, outputs=["att"])
        if not hasattr(self, "_ref"):
            type(self)._ref = {}
        type(self)._ref[mode] = np.asarray(outs["att"].value)
        if "none" in self._ref and mode != "none":
            np.testing.assert_allclose(
                self._ref[mode], self._ref["none"], atol=1e-5
            )


class TestSparseApply:
    """sparse_apply (gather-touched -> update -> scatter, O(k) not O(V))
    vs the dense apply_rows oracle — the large-model update rule
    (SparseRowMatrix.h:204, large_model_dist_train.md)."""

    def test_matches_dense_with_duplicates(self):
        from paddle_tpu.parallel.sparse import (
            apply_rows, sparse_apply, touched_rows,
        )

        V, D = 50, 8
        rng = np.random.default_rng(0)
        param = jnp.asarray(rng.standard_normal((V, D)), jnp.float32)
        ids = jnp.asarray([3, 7, 3, 49, 7, 7], jnp.int32)
        grads = jnp.asarray(rng.standard_normal((6, D)), jnp.float32)

        def upd(p, g):
            return p - 0.1 * g

        got, _ = sparse_apply(upd, param, ids, grads)

        dense_grad = jnp.zeros((V, D)).at[ids].add(grads)
        want = apply_rows(upd, param, dense_grad, touched_rows(ids, V))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-6
        )
        # untouched rows bit-identical
        untouched = [i for i in range(V) if i not in (3, 7, 49)]
        np.testing.assert_array_equal(
            np.asarray(got)[untouched], np.asarray(param)[untouched]
        )

    def test_momentum_state_rows(self):
        """Optimizer state (momentum) gathered/updated/scattered with
        the rows; untouched state rows unchanged."""
        from paddle_tpu.parallel.sparse import sparse_apply

        V, D = 30, 4
        rng = np.random.default_rng(1)
        param = jnp.asarray(rng.standard_normal((V, D)), jnp.float32)
        mom = jnp.asarray(rng.standard_normal((V, D)), jnp.float32)
        ids = jnp.asarray([5, 5, 12], jnp.int32)
        grads = jnp.asarray(rng.standard_normal((3, D)), jnp.float32)

        def upd(p, g, m):
            m2 = 0.9 * m + g
            return p - 0.1 * m2, m2

        newp, (newm,) = sparse_apply(
            upd, param, ids, grads, state=(mom,)
        )
        gsum5 = np.asarray(grads)[0] + np.asarray(grads)[1]
        m5 = 0.9 * np.asarray(mom)[5] + gsum5
        np.testing.assert_allclose(np.asarray(newm)[5], m5, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(newp)[5], np.asarray(param)[5] - 0.1 * m5,
            atol=1e-6,
        )
        np.testing.assert_array_equal(
            np.asarray(newm)[0], np.asarray(mom)[0]
        )

    def test_row_zero_alias_is_safe(self):
        """Unused unique slots alias row 0 as a scatter target; row 0
        must stay bit-identical when untouched (the masked-delta
        trick)."""
        from paddle_tpu.parallel.sparse import sparse_apply

        V, D = 10, 3
        param = jnp.ones((V, D), jnp.float32)
        ids = jnp.asarray([4], jnp.int32)
        grads = jnp.full((1, D), 2.0, jnp.float32)
        got, _ = sparse_apply(
            lambda p, g: p - g, param, ids, grads, num_slots=5
        )
        np.testing.assert_array_equal(np.asarray(got)[0], param[0])
        np.testing.assert_allclose(np.asarray(got)[4], -1.0)

    def test_step_time_independent_of_vocab(self):
        """With buffer donation the scatter updates the table in place:
        wall time must NOT scale with V (the 'step time independent of
        V' contract).
        16x the vocab is allowed at most ~4x the time — an O(V) update
        would be ~16x."""
        import time

        import jax as _jax

        from paddle_tpu.parallel.sparse import sparse_apply

        D, N = 64, 256

        def step(param, ids, grads):
            newp, _ = sparse_apply(
                lambda p, g: p - 0.1 * g, param, ids, grads
            )
            return newp

        f = _jax.jit(step, donate_argnums=0)
        times = {}
        for V in (1 << 18, 1 << 22):
            param = jnp.zeros((V, D), jnp.float32)
            ids = jnp.asarray(
                np.random.default_rng(0).integers(0, V, N), jnp.int32
            )
            grads = jnp.ones((N, D), jnp.float32)
            for _ in range(4):
                param = f(param, ids, grads)
            _jax.block_until_ready(param)
            t0 = time.perf_counter()
            for _ in range(20):
                param = f(param, ids, grads)
            _jax.block_until_ready(param)
            times[V] = time.perf_counter() - t0
        assert times[1 << 22] < times[1 << 18] * 4.0, times


class TestSparseUpdaterKernel:
    """SparseUpdater — the in-place Mosaic row-update kernel (interpret
    mode on the CPU mesh) vs the sparse_apply oracle. Production
    rationale + TPU measurements in PERF.md (the single-program XLA
    formulation pays full-table relayout copies)."""

    def _upd(self, p, g, m):
        m2 = 0.9 * m + g
        return p - 0.01 * m2, m2

    def test_matches_sparse_apply(self):
        from paddle_tpu.parallel.sparse import SparseUpdater, sparse_apply

        V, D, N = 200, 8, 48
        rng = np.random.default_rng(0)
        p0 = rng.standard_normal((V, D)).astype(np.float32)
        m0 = rng.standard_normal((V, D)).astype(np.float32)
        ids = jnp.asarray(rng.integers(0, V, N), jnp.int32)
        grads = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)

        ref_p, (ref_m,) = sparse_apply(
            self._upd, jnp.asarray(p0), ids, grads,
            state=(jnp.asarray(m0),),
        )
        u = SparseUpdater(self._upd)
        param, mom = u.place(p0), u.place(m0)
        param, (mom,) = u(param, ids, grads, (mom,))
        np.testing.assert_allclose(
            u.unplace(param), np.asarray(ref_p), rtol=1e-5, atol=1e-6
        )
        np.testing.assert_allclose(
            u.unplace(mom), np.asarray(ref_m), rtol=1e-5, atol=1e-6
        )

    def test_multiple_steps_and_no_state(self):
        from paddle_tpu.parallel.sparse import SparseUpdater, sparse_apply

        V, D, N = 64, 4, 16

        def upd(p, g):
            return p - 0.5 * g

        rng = np.random.default_rng(3)
        p0 = rng.standard_normal((V, D)).astype(np.float32)
        ref = jnp.asarray(p0)
        u = SparseUpdater(upd)
        param = u.place(p0)
        for step in range(3):
            ids = jnp.asarray(rng.integers(0, V, N), jnp.int32)
            grads = jnp.asarray(
                rng.standard_normal((N, D)), jnp.float32
            )
            ref, _ = sparse_apply(upd, ref, ids, grads)
            param, _ = u(param, ids, grads)
        np.testing.assert_allclose(
            u.unplace(param), np.asarray(ref), rtol=1e-5, atol=1e-6
        )

    def test_overflow_skips_not_corrupts(self):
        """num_slots below the unique count: overflowed ids are skipped
        this step; surviving rows update exactly, others unchanged."""
        from paddle_tpu.parallel.sparse import SparseUpdater

        V, D = 40, 4

        def upd(p, g):
            return p - g

        # 6 unique ids, capacity 4: the 4 smallest survive (unique'd
        # ascending), 2 overflow
        ids = jnp.asarray([10, 20, 30, 35, 5, 15], jnp.int32)
        grads = jnp.ones((6, D), jnp.float32)
        p0 = np.zeros((V, D), np.float32)
        u = SparseUpdater(upd, num_slots=4)
        param = u.place(p0)
        param, _ = u(param, ids, grads)
        out = u.unplace(param)
        updated = {i for i in (5, 10, 15, 20, 30, 35) if out[i].sum() != 0}
        untouched_ok = all(
            out[i].sum() == 0 for i in range(V)
            if i not in (5, 10, 15, 20, 30, 35)
        )
        assert untouched_ok
        assert updated == {5, 10, 15, 20}, updated
        for i in (5, 10, 15, 20):
            np.testing.assert_allclose(out[i], -np.ones(D), atol=1e-6)


def test_sparse_updater_other_dtype_compiles_anew():
    """The compiled steps are kept per argument shape AND dtype: the
    same shapes with ids of another integer width get a program of
    their own, as a retracing jit would give, not a type error."""
    from paddle_tpu.parallel.sparse import SparseUpdater

    def upd(p, g):
        return p - g

    V, D, N = 32, 4, 8
    u = SparseUpdater(upd)
    param = u.place(np.zeros((V, D), np.float32))
    grads = jnp.ones((N, D), jnp.float32)
    ids = np.arange(N)
    param, _ = u(param, jnp.asarray(ids, jnp.int32), grads)
    param, _ = u(param, jnp.asarray(ids, jnp.int16), grads)
    assert len(u._steps) == 2
    out = u.unplace(param)
    np.testing.assert_allclose(out[:N], -2 * np.ones((N, D)), atol=1e-6)
    assert not out[N:].any()


def test_compile_cache_bypass_overlapping_threads():
    """`bypassed()` flips a process-global option. Two blocks that
    overlap on two threads, leaving in the order they entered, must
    keep the cache off until the last one leaves and then put back what
    was there before the first."""
    import threading

    import jax

    from paddle_tpu.core import compile_cache

    before = jax.config.jax_enable_compilation_cache
    a_in, b_in, a_out = (threading.Event() for _ in range(3))
    seen = {}

    def first():
        with compile_cache.bypassed():
            a_in.set()
            b_in.wait(10)
        seen["after_first_left"] = jax.config.jax_enable_compilation_cache
        a_out.set()

    def second():
        a_in.wait(10)
        with compile_cache.bypassed():
            b_in.set()
            a_out.wait(10)
            with compile_cache.bypassed():  # nested on one thread
                pass
            seen["inside_second"] = jax.config.jax_enable_compilation_cache

    threads = [threading.Thread(target=f) for f in (first, second)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert seen == {"after_first_left": False, "inside_second": False}
    assert jax.config.jax_enable_compilation_cache == before


def test_sparse_updater_run_steps_matches_sequential():
    """run_steps (n updates fused into one dispatch — the amortized
    bench/catchUpWith path) must equal n sequential __call__ steps."""
    import jax.numpy as jnp

    from paddle_tpu.parallel.sparse import SparseUpdater

    def upd(p, g, m):
        m2 = 0.9 * m + g
        return p - 0.01 * m2, m2

    V, D, N, S = 96, 8, 24, 4
    rng = np.random.default_rng(7)
    p0 = rng.standard_normal((V, D)).astype(np.float32)
    m0 = np.zeros((V, D), np.float32)
    ids_seq = jnp.asarray(rng.integers(0, V, (S, N)), jnp.int32)
    grads_seq = jnp.asarray(
        rng.standard_normal((S, N, D)), jnp.float32
    )

    a = SparseUpdater(upd)
    pa, ma = a.place(p0), a.place(m0)
    for i in range(S):
        pa, (ma,) = a(pa, ids_seq[i], grads_seq[i], (ma,))

    b = SparseUpdater(upd)
    pb, mb = b.place(p0), b.place(m0)
    pb, (mb,) = b.run_steps(pb, ids_seq, grads_seq, (mb,))

    np.testing.assert_allclose(
        SparseUpdater.unplace(pb), SparseUpdater.unplace(pa),
        rtol=1e-5, atol=1e-6,
    )
    np.testing.assert_allclose(
        SparseUpdater.unplace(mb), SparseUpdater.unplace(ma),
        rtol=1e-5, atol=1e-6,
    )
