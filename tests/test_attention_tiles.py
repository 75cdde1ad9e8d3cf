"""The attention kernel's tiles and the form of its backward follow the mask
(ISSUE 38): `gqa_attention.kernel_tiles` gives a full causal layer ONE
backward kernel (the `dkv` kernel also produces dq, a partial a key block,
summed after it) and a window layer two, with tiles under the window. In
interpret mode on the CPU, float32: every form against the portable
`_blocked` lowering, and the three counters that say how often each engages
against a hand count."""

import re
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.obs import get_registry
from paddle_tpu.ops import gqa_attention as GA

T = 512
# (query heads, KV heads, query/key width, value width): the three head
# shapes of the decoder cells
HEADS = {"128-128-grouped-8": (8, 1, 128, 128),
         "192-128-a-head-each": (2, 2, 192, 128),
         "64-128-grouped-2": (4, 2, 64, 128)}


def _qkv(h, kv, d, dv, seed=3):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (1, T, h, d)),
            jax.random.normal(ks[1], (1, T, kv, d)),
            jax.random.normal(ks[2], (1, T, kv, dv)))


def _loss(**how):
    return lambda q, k, v: jnp.sum(jnp.sin(GA.gqa_attention(q, k, v, **how)))


def _kernel_calls(fn, *args) -> Counter:
    names = re.findall(r"name=(splash_mqa_\w+)", str(jax.make_jaxpr(fn)(*args)))
    return Counter(names)


@pytest.mark.parametrize("heads", HEADS)
def test_the_one_pass_backward_gives_the_portable_lowerings_gradients(heads):
    q, k, v = _qkv(*HEADS[heads])
    kernel = dict(impl="pallas", block_q=128, block_kv=128)
    o1 = GA.gqa_attention(q, k, v, **kernel)
    o2 = GA._blocked(q, k, v, None, 128)
    np.testing.assert_allclose(o1, o2, atol=5e-6)
    grad = jax.grad(_loss(**kernel), (0, 1, 2))
    # full causal: the forward kernel and ONE backward kernel, no `dq` call
    assert _kernel_calls(grad, q, k, v) == {
        "splash_mqa_fwd_residuals": 1, "splash_mqa_dkv_no_residuals": 1}
    want = jax.grad(_loss(impl="blocked", block_q=128), (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", grad(q, k, v), want):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-5,
                                   err_msg="d" + name)


@pytest.mark.parametrize("tile", [128, 256, 512])
def test_a_window_layer_at_tiles_under_at_and_over_the_window(tile):
    window = 256
    q, k, v = _qkv(4, 2, 128, 128)
    kernel = dict(impl="pallas", window=window, block_q=tile, block_kv=tile)
    np.testing.assert_allclose(GA.gqa_attention(q, k, v, **kernel),
                               GA._blocked(q, k, v, window, 128), atol=5e-6)
    grad = jax.grad(_loss(**kernel), (0, 1, 2))
    # a window's mask: the two backward kernels, as before
    assert _kernel_calls(grad, q, k, v) == {
        "splash_mqa_fwd_residuals": 1, "splash_mqa_dq_no_residuals": 1,
        "splash_mqa_dkv_no_residuals": 1}
    want = jax.grad(_loss(impl="blocked", window=window, block_q=128),
                    (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", grad(q, k, v), want):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-5,
                                   err_msg="d" + name)


def test_the_rule_reads_the_mask_and_the_head_and_nothing_else():
    """Full causal (no window, or one the sequence does not reach): one
    pass, the library's dq tiles unset; a window: two passes; a caller's
    own tiles are the kernels' tiles in either form; a sequence with no
    room for the rule's tile gets the largest that divides it."""
    for t, d, dv, window in [(8192, 128, 128, None), (8192, 192, 128, None),
                             (8192, 64, 128, None), (2048, 128, 128, 4096)]:
        s = GA.kernel_tiles(t, d, dv, window)
        assert s.use_fused_bwd_kernel and s.block_q_dq is None, (t, d, window)
        assert s.has_backward_blocks
    for t, d, dv, window in [(8192, 128, 128, 1024), (8192, 64, 128, 512),
                             (8192, 192, 128, 4096), (1024, 128, 128, 128)]:
        s = GA.kernel_tiles(t, d, dv, window)
        assert not s.use_fused_bwd_kernel and s.has_backward_blocks
        for b in (s.block_q, s.block_kv, s.block_q_dq, s.block_kv_dq,
                  s.block_q_dkv, s.block_kv_dkv):
            assert t % b == 0 and b % GA.LANES == 0
    own = GA.kernel_tiles(8192, 128, 128, 1024, block_q=128, block_kv=256)
    assert (own.block_q, own.block_kv, own.block_q_dq, own.block_kv_dq,
            own.block_q_dkv, own.block_kv_dkv) == (128, 256) * 3
    own = GA.kernel_tiles(8192, 128, 128, None, block_q=128, block_kv=256)
    assert own.use_fused_bwd_kernel
    assert (own.block_q, own.block_kv, own.block_q_dkv,
            own.block_kv_dkv_compute) == (128, 256, 128, 256)
    # VMEM: past a 320-wide head the largest tile is 512
    wide = GA.kernel_tiles(8192, 384, 128, None)
    assert (wide.block_q, wide.block_kv_dkv) == (512, 512)
    assert GA.kernel_tiles(8192, 320, 128, None).block_q == 1024
    assert GA.kernel_tiles(8192, 128, 384, 4096).block_q_dq == 512
    # 384 = 3 x 128: no tile of the rule's divides it but 128 and 384
    odd = GA.kernel_tiles(384, 128, 128, None)
    assert (odd.block_q, odd.block_kv) == (384, 384)


def _counted(fn):
    reg = get_registry()
    names = ("attn.pairs_in_tiles", "attn.pairs_kept")
    before = [reg.counter(n).get() for n in names]
    passes = [reg.counter("attn.backward_passes").get(passes=p)
              for p in ("1", "2")]
    fn()
    return ([reg.counter(n).get() - b for n, b in zip(names, before)],
            [reg.counter("attn.backward_passes").get(passes=p) - b
             for p, b in zip(("1", "2"), passes)])


def test_the_counters_against_a_hand_count_on_a_4_by_4_block_mask():
    """T 512 in tiles of 128. A window of 200: query block i reaches key
    blocks i, i-1 and i-2 (the nearest pair of blocks i and i-3 lies 257
    apart), so 1 + 2 + 3 + 3 = 9 tiles of 16 for the 200 x 201 / 2 + 312 x
    200 pairs the mask keeps; full causal: the 10 tiles of the triangle for
    512 x 513 / 2 pairs. Counted once a TRACED call, a query head and row,
    with the backward the call was traced with."""
    q, k, v = _qkv(4, 2, 128, 128)

    def traced(window):
        return lambda: jax.make_jaxpr(lambda q, k, v: GA.gqa_attention(
            q, k, v, window=window, impl="pallas", block_q=128,
            block_kv=128))(q, k, v)

    assert _counted(traced(200)) == ([9 * 128 * 128, 82_500], [0, 1])
    assert _counted(traced(None)) == ([10 * 128 * 128, 131_328], [1, 0])
    # the portable lowering has no tiles and counts nothing
    assert _counted(lambda: GA.gqa_attention(
        q, k, v, window=200, impl="blocked")) == ([0, 0], [0, 0])
    text = get_registry().render_text()
    for name in ("attn.pairs_in_tiles", "attn.pairs_kept",
                 "attn.backward_passes{passes=1}",
                 "attn.backward_passes{passes=2}"):
        assert name in text
