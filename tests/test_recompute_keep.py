"""What a recompute group keeps (ISSUE 36): a group's `jax.checkpoint` holds
the values of `network._KEEP`'s names from its forward to its backward; the
attention kernel and the scan tag what they produce, so that neither runs
forward a second time; the gauge `recompute.kept_bytes` says what that
holds. `_KEEP` set to None is the parent's bare `jax.checkpoint(group)`.
The three decoder graphs at their tests' tiny sizes on the CPU
(`tests/test_mellum.py`, `test_kimi.py`, `test_phi4flash.py`), and at the
smallest widths the kernels take where a test counts kernel calls (a jaxpr
alone: nothing runs)."""

import re
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jex

import paddle_tpu.network as N
from benchmarks.reference import train as RT
from paddle_tpu import ops
from paddle_tpu.network import Network
from paddle_tpu.obs import get_registry
from paddle_tpu.ops import gqa_attention as GA
from paddle_tpu.ops import selective_scan as SS
from tests import test_kimi as TK
from tests import test_mellum as TM
from tests import test_phi4flash as TP

# (the model's tests, its builder, the widths at which its kernels fit,
#  attention layers, Mamba layers); of the attention layers under a window
# of 64 at T 128: Mellum's three of four, none of Kimi's, Phi's first
WINDOWED = {"mellum": 3, "kimi": 0, "phi": 1}
MODELS = {
    "mellum": (TM, TM.mellum, dict(
        hidden_size=256, num_attention_heads=2, num_key_value_heads=1,
        head_dim=128, sliding_window=64), 4, 0),
    "kimi": (TK, TK.kimi, dict(
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        num_attention_heads=2, num_key_value_heads=2), 3, 0),
    "phi": (TP, TP.phi4flash, dict(
        hidden_size=256, num_attention_heads=4, num_key_value_heads=2,
        sliding_window=64), 2, 2),
}
F32 = 4


def graph(model, t=32, **over):
    """-> (the loss as a function of the parameters, seeded parameters,
    the graph's configuration)."""
    T, build, _, _, _ = MODELS[model]
    cfg = T.tiny_cfg(recompute="block", **over)
    conf = build(cfg)
    net = Network(conf)
    feed, _ = T.batch(cfg, t=t)
    p = RT.init_params(T.R.param_spec(cfg), 7)
    return (lambda p: net.loss_fn(p, feed, train=True)[0]), p, conf


def primitives(jaxpr, name) -> list:
    """Every equation of primitive `name` in `jaxpr`, a sub-jaxpr's once
    for each equation that holds it (the text prints a shared one once)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            found.append(eqn)
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if isinstance(sub, jex.Jaxpr):
                    found += primitives(sub, name)
    return found


def kept_bytes() -> dict:
    """group -> the gauge's reading."""
    return {re.search(r"group=(\w+)", k).group(1): v for k, v in
            get_registry().gauge("recompute.kept_bytes").snapshot().items()}


@pytest.fixture
def gauge():
    get_registry().reset_prefix("recompute.")
    yield kept_bytes
    get_registry().reset_prefix("recompute.")


@pytest.fixture
def keep_nothing(monkeypatch):
    """keep_nothing(): from here on every group recomputes all of its
    forward, as before ISSUE 36 (the policy is read as a group is traced)."""
    return lambda: monkeypatch.setattr(N, "_KEEP", None)


@pytest.mark.parametrize("model", MODELS)
def test_loss_and_every_gradient_are_the_same_kept_or_recomputed(model,
                                                                 keep_nothing):
    """The kept values are the values the second call would produce."""
    loss, p, _ = graph(model)
    l1, g1 = jax.jit(jax.value_and_grad(loss))(p)
    keep_nothing()
    l2, g2 = jax.jit(jax.value_and_grad(loss))(p)
    assert float(l1) == float(l2)
    assert set(g1) == set(g2)
    for k in g1:
        np.testing.assert_allclose(g1[k], g2[k], rtol=1e-6, atol=1e-7 * float(
            jnp.max(jnp.abs(g2[k])) + 1e-30), err_msg=k)


@pytest.mark.parametrize("model", MODELS)
def test_a_kept_groups_gradient_runs_the_portable_attention_forward_once(
        model, gauge, keep_nothing):
    """The portable lowering (one query block a layer at T 32): two forward
    products a layer fewer, and the gauge reads the kept output's shape.
    The portable scan's backward reads no output of its forward: a Mamba
    group keeps nothing."""
    attn = MODELS[model][3]

    def products():
        loss, p, conf = graph(model)
        return len(primitives(jax.make_jaxpr(jax.grad(loss))(p).jaxpr,
                              "dot_general")), conf

    kept, conf = products()
    read = gauge()
    keep_nothing()
    recomputed, _ = products()
    assert recomputed - kept == 2 * attn
    # 2 rows of 32 positions, query heads x value width
    heads, dv = {"mellum": (4, 16), "kimi": (4, 16), "phi": (8, 16)}[model]
    assert read == {g[0]: 0 if "mamba" in g[1] else 2 * 32 * F32 * heads * dv
                    for g in conf.recompute}


def on_a_tpu(monkeypatch):
    """The ops choose their kernels as on a TPU, in interpret mode."""
    monkeypatch.setattr(GA.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ops, "pallas_interpret", lambda requested=None: True)


@pytest.mark.parametrize("model", MODELS)
def test_a_kept_groups_gradient_calls_each_kernel_forward_once(
        model, monkeypatch, gauge, keep_nothing):
    """The kernels (a jaxpr of the gradient at T 128): one forward call a
    layer, not two, the backward calls as they were (ISSUE 38: a `dq` call
    only on a window layer; a full causal layer's `dkv` call also produces
    dq); the gauge reads the output and the log-sum-exp (the scan's output
    and chunk-end states)."""
    on_a_tpu(monkeypatch)
    _, _, wide, attn, mamba = MODELS[model]

    def calls():
        loss, p, conf = graph(model, t=128, **wide)
        calls = primitives(jax.make_jaxpr(jax.grad(loss))(p).jaxpr,
                           "pallas_call")
        return Counter(re.search(
            r"splash_mqa_\w+|selective_scan_\w+|$",
            str(c.params["name"])).group() for c in calls), conf

    passes = get_registry().counter("attn.backward_passes")
    traced = [passes.get(passes=1), passes.get(passes=2)]
    kept, conf = calls()
    read = gauge()
    # a traced call a layer, counted with the backward it was traced with
    assert [passes.get(passes=1) - traced[0], passes.get(passes=2)
            - traced[1]] == [attn - WINDOWED[model], WINDOWED[model]]
    assert kept["splash_mqa_fwd_residuals"] == attn
    assert kept["splash_mqa_dq_no_residuals"] == WINDOWED[model]
    assert kept["splash_mqa_dkv_no_residuals"] == attn
    assert kept["selective_scan_forward"] == mamba
    assert kept["selective_scan_backward"] == mamba
    keep_nothing()
    recomputed, _ = calls()
    assert recomputed - kept == Counter(
        {"splash_mqa_fwd_residuals": attn, "selective_scan_forward": mamba})
    # 2 rows of 128 positions; query heads x value width; float32 here
    heads, dv = {"mellum": (2, 128), "kimi": (2, 128), "phi": (4, 128)}[model]
    attention = 2 * 128 * heads * (dv * F32 + F32)
    c, n = 512, 4                                       # Phi: 2 x 256 channels
    scan = 2 * 128 * c * F32 + 2 * (128 // SS.CHUNK) * n * c * F32
    want = {g[0]: (scan if "mamba" in g[1] else attention)
            for g in conf.recompute}
    assert read == want


def held_beyond_arguments(fn, *args) -> int:
    """The bytes `fn` (under jax.checkpoint) holds from its forward to its
    backward beyond its arguments, asked of jax: the residuals of its
    linearisation that an equation produced (what
    jax.ad_checkpoint.print_saved_residuals lists as an output)."""
    jaxpr = jax.make_jaxpr(lambda *a: jax.linearize(fn, *a)[1])(*args).jaxpr
    given = {*jaxpr.invars, *jaxpr.constvars}
    kept = {v for v in jaxpr.outvars if isinstance(v, jex.Var)} - given
    return sum(v.aval.size * v.aval.dtype.itemsize for v in kept)


@pytest.mark.parametrize("lowering", ["portable", "kernels"])
@pytest.mark.parametrize("model", MODELS)
def test_the_gauge_counts_what_jax_holds_beyond_a_groups_inputs(
        model, lowering, monkeypatch, gauge):
    """The gauge is counted where the ops tag (asking jax traces every group
    a second time: too slow for a step's set-up); here jax is asked, group
    by group, and says the same."""
    over = {}
    if lowering == "kernels":
        on_a_tpu(monkeypatch)
        over = dict(t=128, **MODELS[model][2])
    groups = []
    plain = jax.checkpoint

    def checkpoint(f, **kw):
        fn = plain(f, **kw)
        if kw.get("policy") is not N._KEEP:
            return fn

        def call(*args):
            groups.append((fn, jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)))
            return fn(*args)
        return call

    monkeypatch.setattr(jax, "checkpoint", checkpoint)
    loss, p, conf = graph(model, **over)
    jax.make_jaxpr(jax.grad(loss))(p)
    read = gauge()
    asked = [held_beyond_arguments(fn, *args) for fn, args in groups]
    assert asked == [read[g[0]] for g in conf.recompute] and any(asked)


def test_a_group_that_tags_nothing_lowers_as_it_always_did(gauge, keep_nothing):
    """A group with no kernel in it: the text of a bare
    `jax.checkpoint(group)`."""
    def lowered(recompute=None):
        T, build = TM, TM.mellum
        cfg = T.tiny_cfg(recompute="block")
        conf = build(cfg)
        if recompute is not None:
            conf.recompute = recompute
        net = Network(conf)
        feed, _ = T.batch(cfg)
        p = RT.init_params(T.R.param_spec(cfg), 7)
        text = jax.jit(jax.grad(lambda p: net.loss_fn(
            p, feed, train=True)[0])).lower(p).as_text()
        # a private function's number is the order it was lowered in, and
        # one traced again under another policy object is written again:
        # the module's functions as a set, numbers off
        return set(re.sub(r"@(\w+?)_\d+\b", r"@\1", text).split(
            "\n  func.func "))

    experts_alone = [["l0_norm2", "l0_moe", "l0_res2"]]
    with_the_policy = lowered(experts_alone)
    assert gauge() == {"l0_norm2": 0}
    whole_blocks = lowered()
    keep_nothing()
    assert lowered(experts_alone) == with_the_policy
    assert lowered() != whole_blocks                    # those keep


def test_the_gauge_is_in_what_the_metrics_command_prints(gauge):
    loss, p, _ = graph("mellum")
    jax.jit(jax.value_and_grad(loss)).lower(p)
    text = get_registry().render_text()
    assert "recompute.kept_bytes" in text and "l3_norm1" in text
