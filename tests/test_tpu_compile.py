"""The Pallas kernels of the main path, compiled by the TPU's own
compiler for a described (not attached) v5e chip, at the widths the
chip smoke and the benchmark rows use.

Interpret mode — what every other kernel test here runs — cannot see
what Mosaic refuses: a block that breaks the (8, 128) tiling rule, a
kernel over the scoped-VMEM limit, a program that does not fit HBM.
These compiles can, at about two seconds each and no chip time. Nothing
runs: they say nothing about results or speed, and a pass here is not a
chip run (`chip_smoke.py` is).

Rules this file keeps (on-chip-measurement guide, section 2): the
topology is described inside a fixture, never while a module is
imported (only one process may hold the TPU library; the suite runs
under several workers); all such tests live in this one file; no child
processes; the persistent compile cache is off around them (an
executable compiled for a described chip cannot be read back without
one)."""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any refusal means "skip"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    """TPU-compiled HLO text of fn at the given (sharded) shapes."""
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _has_kernel(text: str) -> bool:
    return "tpu_custom_call" in text


# (B, T, H, D, dtype): the LM prefill bucket chip_smoke serves, then
# the long-context, NMT-probe and wide-head shapes of the bench rows
FLASH_SHAPES = [
    (2, 1024, 4, 64, jnp.float32),
    (4, 4096, 8, 64, jnp.bfloat16),
    (256, 32, 8, 64, jnp.bfloat16),
    (8, 1024, 16, 128, jnp.bfloat16),
]


@pytest.mark.parametrize("b,t,h,d,dtype", FLASH_SHAPES)
def test_flash_attention_forward_and_gradient(one_chip, b, t, h, d, dtype):
    from paddle_tpu.parallel import ring

    qkv = jax.ShapeDtypeStruct((b, t, h, d), dtype, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip)

    def fwd(q, k, v, n):
        return ring.flash_dense_attention(q, k, v, causal=True, kv_len=n,
                                          impl="pallas")

    def loss(q, k, v, n):
        return jnp.sum(fwd(q, k, v, n).astype(jnp.float32))

    assert _has_kernel(_compile(fwd, qkv, qkv, qkv, lens))
    assert _has_kernel(_compile(jax.grad(loss, argnums=(0, 1, 2)),
                                qkv, qkv, qkv, lens))


# the three 1x1 sites of ResNet-50 at bs=256: rows = B*H*W
RESNET_1X1 = [(802816, 64, 256), (50176, 256, 1024), (12544, 512, 2048)]


@pytest.mark.parametrize("n,cin,cout", RESNET_1X1)
def test_bn_act_conv1x1_forward_and_gradient(one_chip, monkeypatch,
                                             n, cin, cout):
    from paddle_tpu import ops
    from paddle_tpu.ops.pallas_fused import bn_act_conv1x1

    # the kernel asks the default backend, which is the CPU here; the
    # compile is for the chip, where interpret mode does not exist
    monkeypatch.setattr(ops, "pallas_interpret",
                        lambda requested=None: False)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    u = sds((n, cin), jnp.bfloat16)
    vec = sds((cin,), jnp.float32)
    w = sds((cin, cout), jnp.float32)

    def fwd(u, scale, shift, w, res):
        return bn_act_conv1x1(u, scale, shift, w, residual=res)

    def loss(u, scale, shift, w, res):
        y, s1, s2 = fwd(u, scale, shift, w, res)
        return (jnp.sum(y.astype(jnp.float32)) + jnp.sum(s1)
                + jnp.sum(s2))

    assert _has_kernel(_compile(fwd, u, vec, vec, w, u))
    assert _has_kernel(_compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
                                u, vec, vec, w, u))


def test_sparse_updater_row_kernel(one_chip):
    """The in-place row update at chip_smoke's CTR step: 2**20 rows of
    64, 16384 touched slots, one optimizer slot."""
    from paddle_tpu.parallel.sparse import SparseUpdater

    v, d, k = 1 << 20, 64, 256 * 64

    def momentum(p, g, m):
        m2 = 0.9 * m + g
        return p - 0.01 * m2, m2

    upd = SparseUpdater(momentum, interpret=False)
    step = upd._one_step(upd._make_call(v, d, k, 1, jnp.float32), v, k)
    table = jax.ShapeDtypeStruct((v + 1, 1, d), jnp.float32,
                                 sharding=one_chip)
    ids = jax.ShapeDtypeStruct((256, 64), jnp.int32, sharding=one_chip)
    grads = jax.ShapeDtypeStruct((k, d), jnp.float32, sharding=one_chip)
    text = _compile(lambda p, m, i, g: step(p, (m,), i, g),
                    table, table, ids, grads)
    assert _has_kernel(text)


def _fallbacks() -> float:
    from paddle_tpu import obs
    from paddle_tpu.obs.aggregate import family_total

    return family_total(obs.get_registry().snapshot()["counters"],
                        "pallas_rnn.fallbacks")


def _rnn_shapes(one_chip, b, t, h, mult, dtype=jnp.float32):
    def sds(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    return sds, sds((b, t, mult * h)), sds((b,), jnp.int32)


def test_fused_lstm_and_gru_hold_their_kernels(one_chip):
    """b=64, t=100, h=256 (the reference's LSTM benchmark width): both
    fused cells, forward and gradient, compile WITH their kernels and
    report no fallback."""
    from paddle_tpu.ops import pallas_rnn as pr

    b, t, h = 64, 100, 256
    before = _fallbacks()
    sds, x4, lens = _rnn_shapes(one_chip, b, t, h, 4)
    lstm_args = (x4, sds((h, 4 * h)), sds((4 * h,)), sds((h,)),
                 sds((h,)), sds((h,)), lens)

    def lstm(*a):
        return pr.lstm_fused(*a, False)

    assert _has_kernel(_compile(lstm, *lstm_args))
    assert _has_kernel(_compile(
        jax.grad(lambda *a: jnp.sum(lstm(*a)), argnums=(0, 1)),
        *lstm_args))

    _, x3, _ = _rnn_shapes(one_chip, b, t, h, 3)
    gru_args = (x3, sds((h, 2 * h)), sds((h, h)), sds((3 * h,)), lens)

    def gru(*a):
        return pr.gru_fused(*a, False)

    assert _has_kernel(_compile(gru, *gru_args))
    assert _has_kernel(_compile(
        jax.grad(lambda *a: jnp.sum(gru(*a)), argnums=(0, 1)),
        *gru_args))
    assert _fallbacks() == before


def test_fused_lstm_that_falls_back_says_so(one_chip):
    """b=128, t=32, h=512: the gradient kernel's batch block comes out
    under 32 rows and the scan reference runs instead. That has to be
    visible — the counter moves — and, fed bf16 activations against
    f32 weights, the reference has to compile at all (its scan carry
    used to break)."""
    from paddle_tpu.ops import pallas_rnn as pr

    b, t, h = 128, 32, 512
    sds, x4, lens = _rnn_shapes(one_chip, b, t, h, 4, jnp.bfloat16)
    f32 = jnp.float32
    args = (x4, sds((h, 4 * h), f32), sds((4 * h,), f32), sds((h,), f32),
            sds((h,), f32), sds((h,), f32), lens)
    before = _fallbacks()
    _compile(
        jax.grad(lambda *a: jnp.sum(pr.lstm_fused(*a, False)
                                    .astype(jnp.float32)),
                 argnums=(0, 1)),
        *args)
    assert _fallbacks() > before


# ---- the decoder block's kernels at the widths of the cell
# `mellum2_12b_ep4.train_seq8192`: 2 rows of 8,192 positions, 32 query
# heads on 4 KV heads of 128; 131,072 slots of 2,304 through 16 held
# experts of 896

@pytest.mark.parametrize("window", [1024, None])
def test_window_attention_forward_and_gradient(one_chip, monkeypatch, window):
    from paddle_tpu import ops
    from paddle_tpu.ops.gqa_attention import gqa_attention

    monkeypatch.setattr(ops, "pallas_interpret",
                        lambda requested=None: False)
    q = jax.ShapeDtypeStruct((2, 8192, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 8192, 4, 128), jnp.bfloat16,
                              sharding=one_chip)

    def fwd(q, k, v):
        return gqa_attention(q, k, v, window=window, impl="pallas")

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    assert _has_kernel(_compile(fwd, q, kv, kv))
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    # a window: forward, dq, dk and dv; full causal: forward, one backward
    assert text.count("tpu_custom_call") == (3 if window else 2)


def test_grouped_matmul_forward_and_gradient(one_chip, monkeypatch):
    from paddle_tpu import ops
    from paddle_tpu.ops.moe import grouped_matmul

    monkeypatch.setattr(ops, "pallas_interpret",
                        lambda requested=None: False)
    rows = jax.ShapeDtypeStruct((131072, 2304), jnp.bfloat16,
                                sharding=one_chip)
    w = jax.ShapeDtypeStruct((16, 2304, 896), jnp.bfloat16, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one_chip)

    def fwd(rows, w, sizes):
        return grouped_matmul(rows, w, sizes, impl="pallas")

    def loss(rows, w, sizes):
        return jnp.sum(fwd(rows, w, sizes).astype(jnp.float32))

    assert _has_kernel(_compile(fwd, rows, w, sizes))
    text = _compile(jax.grad(loss, argnums=(0, 1)), rows, w, sizes)
    assert text.count("tpu_custom_call") >= 2      # the rows', the weights'


def test_the_expert_layers_passes_run_in_place_chunk_by_chunk(one_chip,
                                                              monkeypatch):
    """The whole layer, out and back under recomputation, at the widths of
    `mellum2_12b_ep4.train_seq8192`: every pass over the row buffer is a
    loop over chunks that writes where it stands. A chunk loop that the
    compiler cannot run in place copies its whole buffer a trip (seen once,
    ISSUE 34: the write ordered before the read of the same chunk)."""
    import re

    from paddle_tpu import ops
    from paddle_tpu.ops import moe

    monkeypatch.setattr(ops, "pallas_interpret",
                        lambda requested=None: False)
    n, d, e, eh, h, k = 16384, 2304, 64, 16, 896, 8

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    @jax.checkpoint
    def block(x, wr, wg, wu, wd):
        return x + moe.dropless_moe(x, wr, wg, wu, wd, top_k=k,
                                    impl="pallas")[0]

    def loss(*a):
        return jnp.sum(block(*a).astype(jnp.float32) ** 2)

    text = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
                    shape((n, d)), shape((d, e), jnp.float32),
                    shape((eh, d, h)), shape((eh, d, h)), shape((eh, h, d)))
    assert text.count("tpu_custom_call") >= 9       # 3 products x 3 kernels
    assert len(re.findall(r" while\(", text)) >= 9
    chunk = moe._chunk_of(n * k)
    long = rf"= bf16\[(?:{n * k}|{n * k // chunk},{chunk}),\d+\]\S* "
    assert not re.findall(long + r"copy\(", text)
    # what makes a whole buffer is a kernel, a loop's update or a loop
    for line in re.findall(long + r"fusion\(.*", text):
        assert "dynamic_update_slice" in line or "dynamic-update" in line, line


# ---- the latent-attention decoder's kernels at the widths of the cell
# `kimi_vl_a3b_ep8.train_seq8192`: 2 rows of 8,192 positions, 16 heads with
# 192-wide queries and keys and 128-wide values; 98,304 slots of 2,048
# through 8 held experts of 1,408

def test_latent_attention_forward_and_gradient_at_192_128(one_chip,
                                                          monkeypatch):
    from paddle_tpu import ops
    from paddle_tpu.ops.gqa_attention import gqa_attention, pallas_fits

    monkeypatch.setattr(ops, "pallas_interpret",
                        lambda requested=None: False)
    assert pallas_fits(8192, 192, 128)
    qk = jax.ShapeDtypeStruct((2, 8192, 16, 192), jnp.bfloat16,
                              sharding=one_chip)
    v = jax.ShapeDtypeStruct((2, 8192, 16, 128), jnp.bfloat16,
                             sharding=one_chip)

    def fwd(q, k, v):
        return gqa_attention(q, k, v, impl="pallas")

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    assert _has_kernel(_compile(fwd, qk, qk, v))
    assert jax.eval_shape(fwd, qk, qk, v).shape == (2, 8192, 16, 128)
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), qk, qk, v)
    # full causal: the forward, and ONE backward that takes the scores once
    assert text.count("tpu_custom_call") == 2


def test_grouped_matmul_at_the_latent_decoders_widths(one_chip, monkeypatch):
    from paddle_tpu import ops
    from paddle_tpu.ops.moe import grouped_matmul

    monkeypatch.setattr(ops, "pallas_interpret",
                        lambda requested=None: False)
    sizes = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)
    for k, n in ((2048, 1408), (1408, 2048)):      # up and gate; down
        rows = jax.ShapeDtypeStruct((98304, k), jnp.bfloat16,
                                    sharding=one_chip)
        w = jax.ShapeDtypeStruct((8, k, n), jnp.bfloat16, sharding=one_chip)

        def loss(rows, w, sizes):
            return jnp.sum(grouped_matmul(rows, w, sizes, impl="pallas")
                           .astype(jnp.float32))

        text = _compile(jax.grad(loss, argnums=(0, 1)), rows, w, sizes)
        assert text.count("tpu_custom_call") >= 2  # the rows', the weights'


# ---- the hybrid decoder's kernels at the widths of the cell
# `phi4_mini_flash_pp8.train_seq8192`: 2 rows of 8,192 positions; a scan
# over 5,120 channels of 16 states; 40 query heads on 20 key heads of 64,
# each with a 128-wide value, over a window of 512 and full

def test_selective_scan_forward_and_gradient(one_chip, monkeypatch):
    from paddle_tpu import ops
    from paddle_tpu.ops.selective_scan import pallas_fits, selective_scan

    monkeypatch.setattr(ops, "pallas_interpret",
                        lambda requested=None: False)
    assert pallas_fits(8192, 5120)

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    args = (sds(2, 8192, 5120), sds(2, 8192, 5120), sds(5120, 16),
            sds(2, 8192, 16), sds(2, 8192, 16), sds(5120))

    def fwd(*a):
        return selective_scan(*a, impl="pallas")

    def loss(*a):
        return jnp.sum(fwd(*a))

    text = _compile(fwd, *args)
    assert text.count("tpu_custom_call") == 1      # ONE call a layer call
    assert jax.eval_shape(fwd, *args).shape == (2, 8192, 5120)
    text = _compile(jax.grad(loss, argnums=tuple(range(6))), *args)
    assert text.count("tpu_custom_call") == 2      # the forward, the backward
    assert "while" not in text.split("ENTRY")[1]   # no loop around either


@pytest.mark.parametrize("window", [512, None])
def test_differential_attentions_maps_at_64_128(one_chip, monkeypatch,
                                                window):
    from paddle_tpu import ops
    from paddle_tpu.ops.gqa_attention import gqa_attention, pallas_fits

    monkeypatch.setattr(ops, "pallas_interpret",
                        lambda requested=None: False)
    assert pallas_fits(8192, 64, 128)
    q = jax.ShapeDtypeStruct((2, 8192, 40, 64), jnp.bfloat16,
                             sharding=one_chip)
    k = jax.ShapeDtypeStruct((2, 8192, 20, 64), jnp.bfloat16,
                             sharding=one_chip)
    v = jax.ShapeDtypeStruct((2, 8192, 20, 128), jnp.bfloat16,
                             sharding=one_chip)

    def fwd(q, k, v):
        return gqa_attention(q, k, v, window=window, impl="pallas")

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    assert _has_kernel(_compile(fwd, q, k, v))
    assert jax.eval_shape(fwd, q, k, v).shape == (2, 8192, 40, 128)
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
    assert text.count("tpu_custom_call") == (3 if window else 2)


@pytest.mark.parametrize("window", [None, 1024, 512])
@pytest.mark.parametrize("g,d,dv", [(8, 128, 128), (1, 192, 128),
                                    (2, 64, 128), (1, 384, 128),
                                    (4, 64, 64)])
def test_every_tile_of_the_attention_rule_compiles(one_chip, monkeypatch,
                                                   g, d, dv, window):
    """The WHOLE table of `kernel_tiles` at T 8,192: every mask of the
    decoder cells against every head shape (one row, one KV head) and
    against a head past the width at which the rule halves its largest
    tile, so that a tile Mosaic refuses (VMEM at a wide head, a block off
    the tiling) fails here and not on the chip; the calls are the form the
    mask says."""
    from paddle_tpu import ops
    from paddle_tpu.ops.gqa_attention import gqa_attention

    monkeypatch.setattr(ops, "pallas_interpret",
                        lambda requested=None: False)
    q = jax.ShapeDtypeStruct((1, 8192, g, d), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, 8192, 1, d), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 8192, 1, dv), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(gqa_attention(q, k, v, window=window, impl="pallas"
                                     ).astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
    calls = re.findall(r"%(splash_mqa_\w+?)[.\d]* = .*tpu_custom_call", text)
    assert sorted(calls) == (
        ["splash_mqa_dkv_no_residuals", "splash_mqa_dq_no_residuals",
         "splash_mqa_fwd_residuals"] if window else
        ["splash_mqa_dkv_no_residuals", "splash_mqa_fwd_residuals"])


# ---- the gated decoder's kernels at the widths of the cell
# `laguna_xs2_ep16.train_seq8192`: 2 rows of 8,192 positions; 48 query heads
# on 8 KV heads of 128 under a full causal mask (6 a KV head) and 64 under a
# window of 512 (8 a KV head); 131,072 slots through 16 held experts of 2,048
# x 512

@pytest.mark.parametrize("h,window", [(48, None), (64, 512)],
                         ids=["48-on-8-full", "64-on-8-window-512"])
def test_gated_attentions_two_head_counts_forward_and_gradient(
        one_chip, monkeypatch, h, window):
    from paddle_tpu import ops
    from paddle_tpu.ops.gqa_attention import gqa_attention

    monkeypatch.setattr(ops, "pallas_interpret",
                        lambda requested=None: False)
    q = jax.ShapeDtypeStruct((2, 8192, h, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 8192, 8, 128), jnp.bfloat16,
                              sharding=one_chip)

    def fwd(q, k, v):
        return gqa_attention(q, k, v, window=window, impl="pallas")

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    assert _has_kernel(_compile(fwd, q, kv, kv))
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    # a window: forward, dq, dk and dv; full causal: forward, one backward
    assert text.count("tpu_custom_call") == (3 if window else 2)


def test_grouped_matmul_at_sixteen_small_experts(one_chip, monkeypatch):
    from paddle_tpu import ops
    from paddle_tpu.ops.moe import grouped_matmul

    monkeypatch.setattr(ops, "pallas_interpret",
                        lambda requested=None: False)
    sizes = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one_chip)
    for k, n in ((2048, 512), (512, 2048)):        # up and gate; down
        rows = jax.ShapeDtypeStruct((131072, k), jnp.bfloat16,
                                    sharding=one_chip)
        w = jax.ShapeDtypeStruct((16, k, n), jnp.bfloat16, sharding=one_chip)

        def loss(rows, w, sizes):
            return jnp.sum(grouped_matmul(rows, w, sizes, impl="pallas")
                           .astype(jnp.float32))

        text = _compile(jax.grad(loss, argnums=(0, 1)), rows, w, sizes)
        assert text.count("tpu_custom_call") >= 2  # the rows', the weights'


# dkv kernel's (query block, key block, keys a matmul) and the dq kernel's
# tiles, None where the backward is ONE pass
_W512 = ((512, 512, 512), (512, 512, 512), (512, 512))
_FULL = ((1024, 1024, 512), (1024, 1024, 1024), None)


@pytest.mark.parametrize("h,kv,d,dv,window,fwd,dkv,dq", [
    (32, 4, 128, 128, 1024, *_W512), (32, 4, 128, 128, None, *_FULL),
    (16, 16, 192, 128, None, *_FULL), (40, 20, 64, 128, 512, *_W512),
    (48, 8, 128, 128, None, *_FULL), (64, 8, 128, 128, 512, *_W512),
    (32, 8, 64, 64, None, *_FULL)],
    ids=["mellum-window", "mellum-full", "kimi", "phi-window",
         "laguna-full", "laguna-window", "lfm2"])
def test_attention_hands_the_kernel_each_models_own_widths(
        monkeypatch, h, kv, d, dv, window, fwd, dkv, dq):
    """The kernel is handed q, k and v at the model's own widths, nothing
    padded, in the tiles and with the backward the rule gives that mask and
    head (the lowering, not a compile: no chip is described for it)."""
    from paddle_tpu import ops
    from paddle_tpu.ops import gqa_attention as GA

    monkeypatch.setattr(ops, "pallas_interpret",
                        lambda requested=None: False)
    made = []
    plain = GA._splash_kernel

    def spy(*args):
        made.append(args)
        return plain(*args)

    monkeypatch.setattr(GA, "_splash_kernel", spy)
    q = jax.ShapeDtypeStruct((2, 8192, h, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((2, 8192, kv, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((2, 8192, kv, dv), jnp.bfloat16)
    assert GA.pallas_fits(8192, d, dv)
    jaxpr = str(jax.make_jaxpr(lambda q, k, v: GA.gqa_attention(
        q, k, v, window=window, impl="pallas"))(q, k, v))
    (t, group, w, sizes, interpret), = made
    assert (t, group, w, interpret) == (8192, h // kv, window, False)
    assert (sizes.block_q, sizes.block_kv, sizes.block_kv_compute) == fwd
    assert (sizes.block_q_dkv, sizes.block_kv_dkv,
            sizes.block_kv_dkv_compute) == dkv
    assert sizes.use_fused_bwd_kernel == (dq is None)
    assert (sizes.block_q_dq, sizes.block_kv_dq) == (dq or (None, None))
    assert " pad[" not in jaxpr and "pallas_call" in jaxpr
    assert f"bf16[2,{kv},{h // kv},8192,{d}]" in jaxpr      # q, grouped
    assert f"bf16[2,{kv},8192,{dv}]" in jaxpr               # v, unpadded


# ---- q and k from the projections' outputs to the kernel's operands in one
# pass each (ISSUE 40), at the three `gqa_attention` shapes of the cells: 2
# rows of 8,192 positions, heads of 128

_ROTARY = {
    "laguna-full": (48, 8, {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "beta_fast": 64, "beta_slow": 1,
        "original_max_position_embeddings": 4096,
        "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5}),
    "laguna-window": (64, 8, {"rope_type": "default", "rope_theta": 10000}),
    "mellum": (32, 4, {"rope_type": "default", "rope_theta": 500000}),
}


@pytest.mark.parametrize("cell", _ROTARY)
def test_the_rotary_pass_and_its_backward_move_q_and_k_once(one_chip,
                                                             monkeypatch,
                                                             cell):
    """The pass and its backward compile for the chip with no copy beside
    them, and the whole way of q and k from projection output to kernel
    operand (the tables made, q turned and scaled, k turned) moves under 1.5
    times q and k read once and written once, forward, and back again: the
    guard that no later change brings the float32 halves back (the plain
    composition moves 5 to 9 times that; held here too, so that the guard
    is known to see them)."""
    import math

    from paddle_tpu import ops
    from paddle_tpu.ops import rope

    monkeypatch.setattr(ops, "pallas_interpret",
                        lambda requested=None: False)
    h, kv, group = _ROTARY[cell]
    b, t, d = 2, 8192, 128
    r = rope.rotary_width(d, group)

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def there(impl):
        def f(q, k):
            cos, sin = rope.tables(t, r, group)
            return (rope.to_heads(q, cos, sin, h, 1 / math.sqrt(d),
                                  impl=impl),
                    rope.to_heads(k, cos, sin, kv, impl=impl))
        return f

    def there_and_back(impl):
        def f(q, k, gq, gk):
            out, back = jax.vjp(there(impl), q, k)
            return out, back((gq, gk))
        return f

    once = 2 * 2 * b * t * (h + kv) * d          # bfloat16, read and written
    shapes = (sds(b, t, h * d), sds(b, t, kv * d))
    grads = (sds(b, h, t, d), sds(b, kv, t, d))
    moved = {}
    for impl in ("pass", "plain"):
        fwd = jax.jit(there(impl)).lower(*shapes).compile()
        both = jax.jit(there_and_back(impl)).lower(*shapes, *grads).compile()
        moved[impl] = (fwd.cost_analysis()["bytes accessed"] / once,
                       both.cost_analysis()["bytes accessed"] / (2 * once))
        if impl == "pass":
            text = both.as_text()
            assert text.count("tpu_custom_call") == 4 and " copy(" not in text
            for name in ("rope_to_heads", "rope_from_heads"):
                assert len(re.findall(name + r"[\w.]* = .*tpu_custom_call",
                                      text)) == 2
            assert "f32[2,8192" not in text
    assert max(moved["pass"]) < 1.5, moved
    assert min(moved["plain"]) > 3.0, moved


# ---- a recompute group keeps what its kernels produced (ISSUE 36): one
# block of norm, mixer and residual as a recompute group of a small graph,
# its gradient compiled for the chip at the three attention widths of the
# decoder cells and at the scan's smallest block

_MIXERS = {
    "attention-128-128": ("gqa_attention", dict(
        num_heads=2, num_kv_heads=1, head_dim=128, window=None,
        rope={"rope_theta": 10000})),
    # a second branch off the block's normed input (the gate) and a rotary
    # width of half the head (ISSUE 39)
    "attention-128-128-gated": ("gqa_attention", dict(
        num_heads=6, num_kv_heads=1, head_dim=128, window=None,
        gate="per_head", rope={
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "beta_fast": 64, "beta_slow": 1,
            "original_max_position_embeddings": 4096,
            "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5})),
    "attention-192-128": ("mla_attention", dict(
        num_heads=2, kv_lora_rank=64, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, rope_theta=10000)),
    "attention-64-128": ("diff_attention", dict(
        num_heads=4, num_kv_heads=2, head_dim=64, layer_index=1,
        window=128)),
    "scan": ("mamba", dict(d_state=4, dt_rank=8)),
    # q and k normed a head before the rotary positions, 64-wide values
    # (ISSUE 41)
    "attention-64-64-qk-norm": ("gqa_attention", dict(
        num_heads=4, num_kv_heads=1, head_dim=64, window=None, qk_norm=True,
        rope={"rope_theta": 1000000})),
}


@pytest.mark.parametrize("mixer", _MIXERS)
def test_a_recompute_group_calls_its_kernel_forward_once(one_chip,
                                                          monkeypatch, mixer):
    """Forward, dq, dkv on a window layer; forward and ONE backward on a
    full causal layer (ISSUE 38) and for the scan; ONE of them the forward
    kernel; under a bare `jax.checkpoint` (`network._KEEP` None) the program
    before ISSUE 36: one call more, the recomputed forward."""
    import paddle_tpu.network as N
    from paddle_tpu import dsl, ops
    from paddle_tpu.core.arg import Arg
    from paddle_tpu.network import Network

    monkeypatch.setattr(ops, "pallas_interpret",
                        lambda requested=None: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    kind, attrs = _MIXERS[mixer]
    d, t, vocab = 256, 256, 128

    def kernel_calls():
        with dsl.model() as g:
            ids = dsl.data("ids", dim=(), is_ids=True, is_seq=True)
            label = dsl.data("label", dim=(), is_ids=True, is_seq=True)
            x = dsl.embedding(ids, size=d, vocab_size=vocab, name="emb")
            a = dsl._add("rms_norm", [x], name="norm", bias=False)
            m = dsl._add(kind, [a], name="mixer", size=d, bias=False, **attrs)
            x = dsl.addto(x, m, name="res")
            dsl._add("lm_head_cost", [x, label], name="head", bias=False,
                     vocab_size=vocab, chunk_rows=256)
        g.conf.recompute.append(["norm", "mixer", "res"])
        net = Network(g.conf)
        params = {k: jax.ShapeDtypeStruct(tuple(pc.dims), jnp.float32,
                                          sharding=one_chip)
                  for k, pc in net.param_confs.items()}
        ints = jax.ShapeDtypeStruct((2, t), jnp.int32, sharding=one_chip)
        lens = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=one_chip)

        def loss(p, ids, label, lens):
            feed = {"ids": Arg(ids=ids, seq_lens=lens),
                    "label": Arg(ids=label, seq_lens=lens)}
            return net.loss_fn(p, feed, train=True)[0]

        calls = re.findall(r"%(splash_mqa_\w+?|selective_scan_\w+?)[.\d]* = .*"
                           r"tpu_custom_call",
                           _compile(jax.grad(loss), params, ints, ints, lens))
        return len(calls), sum(c in FORWARD for c in calls)

    FORWARD = ("splash_mqa_fwd_residuals", "selective_scan_forward")
    want = 3 if attrs.get("window") else 2
    assert kernel_calls() == (want, 1)
    monkeypatch.setattr(N, "_KEEP", None)
    assert kernel_calls() == (want + 1, 2)


# ---- the step of the cell `lfm2_8b_a1b_ep4.train_seq8192` (ISSUE 41), whole:
# 2 rows of 8,192 positions through four gated short convolutions, one
# QK-normed attention layer of 32 heads on 8 of 64, a dense block and four
# expert layers of 8 held experts, Adam and the watchdog, compiled from shapes
# alone (no parameter is made)

_SIZES = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4, "pred": 1}
_NOT_MOVED = ("parameter", "get-tuple-element", "tuple", "constant", "bitcast")
_SHAPE = re.compile(r"\b(f32|bf16|s32|u32|pred)\[([\d,]*)\]")


def _nbytes(shapes):
    return sum(_SIZES[dt] * math.prod(int(x) for x in dims.split(",") if x)
               for dt, dims in shapes)


def _bytes_under(text, scopes):
    """{scope: bytes}: the shapes (result and operands) of the compiled
    program's top-level operations, those outside any fused computation,
    whose `op_name` holds the scope: what the step moves through HBM under
    it, forward, recomputed and backward. A kernel's operand that it names
    more than once (an array read through several blocks) counts once."""
    got, comp = dict.fromkeys(scopes, 0), ""
    for line in text.splitlines():
        if not line.startswith(" ") and line.endswith("{"):
            comp = line
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        op = re.match(r"\s*(?:ROOT )?%\S+ = .*? ([a-z][\w\-]*)\(", line)
        if ("fused" in comp or "wrapped" in comp or not name or not op
                or op.group(1) in _NOT_MOVED):
            continue
        shapes = line.split(", metadata=")[0].split(" = ", 1)[1]
        if op.group(1) == "custom-call":
            result, rest = shapes.split(" custom-call(", 1)
            operands = re.sub(r"/\*[^*]*\*/", "", rest.split(")", 1)[0])
            read = dict(zip([o.strip() for o in operands.split(",")],
                            _SHAPE.findall(rest.split(
                                "operand_layout_constraints=", 1)[-1])))
            n = _nbytes(_SHAPE.findall(result)) + _nbytes(read.values())
        else:
            n = _nbytes(_SHAPE.findall(shapes.split("calls=")[0]))
        for s in scopes:
            if s in name.group(1):
                got[s] += n
    return got


def test_the_lfm2_cells_step_compiles_and_fits(one_chip, monkeypatch):
    """The step fits the chip: `memory_analysis()` 10.22 GB (arguments: the
    float32 parameters and Adam's two moments, 6.09 GB; temporaries 4.12
    GB; 10.84 GB before the short convolution's pass); ONE splash
    forward and ONE one-pass backward (the attention layer, full causal); 36
    grouped products and 12 of their weight gradients (4 expert layers); the
    short convolution's pass 8 times forward (4 layers, forward and
    recomputed) and its backward 4 times, with no float32 [2, 8192, 6144]
    array left and no concatenation of the pieces of its gradient. The bytes
    moved under the scopes, the numbers a later change to them is sized
    from: `conv.mix` 4.04 GB a step (the pass reads [B | C | x] in bfloat16
    and writes y, or the gradient of [B | C | x], in bfloat16: 0.27 GB
    forward and 0.47 GB backward a layer; the float32 composition before it
    moved 4.03 GB at a quarter of the HBM's speed), `conv.in` 1.98 GB,
    `conv.out` 0.84 GB, `attn.qk_norm` 0.20 GB, `attn.rope` 0.81 GB (the
    plain composition: a 64-wide head is half a lane tile).

    The trace pin (what tracing and lowering the step costs, held without a
    clock): in the LOWERED program each of the pass's kernels is defined
    once for all four layers, the forward twice (with the gauge, and
    recomputed without it), however many layers call it."""
    from benchmarks import harness
    from benchmarks.kinds import train as T
    from paddle_tpu import ops
    from paddle_tpu.core import flags
    from paddle_tpu.core.arg import Arg
    from paddle_tpu.network import Network
    from paddle_tpu.optimizers import create_optimizer
    from paddle_tpu.parallel.dp import TrainStep

    monkeypatch.setattr(ops, "pallas_interpret",
                        lambda requested=None: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cell = harness.Cell("lfm2_8b_a1b_ep4.train_seq8192")
    cfg = cell.config
    was = flags.get_flag("matmul_precision")
    flags.set_flag("matmul_precision", cfg["matmul_precision"])
    try:
        net = Network(cell.model.program_conf(cfg))
        opt = create_optimizer(T.optimizer(cfg), net.param_confs)

        def sds(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

        params = {k: jax.ShapeDtypeStruct(tuple(pc.dims), jnp.float32,
                                          sharding=one_chip)
                  for k, pc in net.param_confs.items()}
        opt_state = jax.tree_util.tree_map(
            sds, jax.eval_shape(opt.init_state, params))
        ints = jax.ShapeDtypeStruct((2, 8192), jnp.int32, sharding=one_chip)
        lens = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=one_chip)
        feed = {"ids": Arg(ids=ints, seq_lens=lens),
                "label": Arg(ids=ints, seq_lens=lens)}
        rng = sds(jax.eval_shape(lambda: jax.random.key(0)))
        lowered = TrainStep(net, opt, watchdog=True)._step.lower(
            params, opt_state, {}, feed,
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip), rng,
            jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip))
        compiled = lowered.compile()
    finally:
        flags.set_flag("matmul_precision", was)
    defined = re.findall(r'kernel_name = "(short_conv\w*)"', lowered.as_text())
    assert sorted(defined) == ["short_conv_mix", "short_conv_mix",
                               "short_conv_mix_bwd"]
    ma = compiled.memory_analysis()
    total = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
             + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    assert 10.0e9 < total < 12.0e9, total
    assert ma.argument_size_in_bytes == pytest.approx(12 * 507820288, rel=1e-3)
    text = compiled.as_text()
    calls = re.findall(r"%([a-z_]+?)[.\d]* = [^\n]*tpu_custom_call", text)
    assert sorted(set(calls)) == ["gmm", "short_conv_mix",
                                  "short_conv_mix_bwd",
                                  "splash_mqa_dkv_no_residuals",
                                  "splash_mqa_fwd_residuals", "tgmm"]
    assert (calls.count("splash_mqa_fwd_residuals"),
            calls.count("splash_mqa_dkv_no_residuals"),
            calls.count("gmm"), calls.count("tgmm")) == (1, 1, 36, 12)
    assert (calls.count("short_conv_mix"),
            calls.count("short_conv_mix_bwd")) == (8, 4)
    assert "f32[2,8192,6144]" not in text
    # the backward's gradient of [B | C | x] goes to `conv.in`'s two
    # products as the kernel wrote it: no pass in between
    grads = re.findall(r"%(\S+) = bf16\[2,8192,6144\]\S* get-tuple-element"
                       r"\(%short_conv_mix_bwd[.\d]*\), index=0", text)
    assert len(grads) == 4, grads
    for grad in grads:
        users = [line for line in text.splitlines()
                 if re.search(rf"[(, ]%{re.escape(grad)}[,)]", line)]
        assert len(users) == 2, users
        assert all("/conv.in/" in line for line in users), users
    moved = _bytes_under(text, ("conv.mix", "conv.in", "conv.out",
                                "attn.qk_norm", "attn.rope"))
    assert 3.9e9 < moved["conv.mix"] < 4.2e9, moved
    assert 1.5e9 < moved["conv.in"] < 2.5e9, moved
    assert 0.5e9 < moved["conv.out"] < 1.2e9, moved
    assert 0.1e9 < moved["attn.qk_norm"] < 0.4e9, moved
    assert 0.5e9 < moved["attn.rope"] < 1.2e9, moved


# ---- the short convolution's pass: a kernel body whose size does not grow
# with the block or the width, so that tracing and lowering it costs the same
# at any shape (the pin beside the step's above)

def _equations(jaxpr):
    """Equations of a jaxpr, those of the jaxprs inside them counted too."""
    from jax.extend import core

    def inner(eqn):
        for v in eqn.params.values():
            for x in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(x, core.ClosedJaxpr):
                    yield x.jaxpr
                elif isinstance(x, core.Jaxpr):
                    yield x

    return sum(1 + sum(_equations(j) for j in inner(e)) for e in jaxpr.eqns)


def _kernel_bodies(jaxpr, found):
    """{kernel name: equations of its body} of every Pallas call in a
    jaxpr."""
    from jax.extend import core

    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            name = e.params["name"]
            found[str(getattr(name, "name", name))] = _equations(
                e.params["jaxpr"])
            continue
        for v in e.params.values():
            if isinstance(v, core.ClosedJaxpr):
                _kernel_bodies(v.jaxpr, found)
    return found


@pytest.mark.parametrize("t,d", [(1024, 256), (8192, 2048), (8192, 4096)])
def test_the_short_conv_pass_kernels_do_not_grow_with_the_shape(monkeypatch,
                                                                t, d):
    """The forward's and the backward's bodies: a fixed number of equations
    (49 and 98 at L 3) at a small shape, the cell's, and a wider one (the
    same kernels with their pieces of lanes unrolled held 201 and 407 at the
    cell's shape)."""
    from paddle_tpu import ops
    from paddle_tpu.ops import short_conv

    monkeypatch.setattr(ops, "pallas_interpret",
                        lambda requested=None: False)
    bcx = jax.ShapeDtypeStruct((2, t, 3 * d), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((d, 3), jnp.bfloat16)

    def loss(bcx, w):
        y, _ = short_conv.mix(bcx, w, impl="pass")
        return jnp.sum(y.astype(jnp.float32))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(bcx, w).jaxpr
    assert _kernel_bodies(jaxpr, {}) == {"short_conv_mix": 49,
                                         "short_conv_mix_bwd": 98}
