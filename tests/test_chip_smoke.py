"""chip_smoke.py on the CPU: the script itself must REFUSE to run here
(it is the proof that the system starts on the chip, so a CPU run that
passed would be worse than none), and each phase function must pass
when a test calls it directly at the documented test-only sizes
(`chip_smoke.TINY`, what `--tiny` selects). On the CPU the Pallas
kernels run interpreted and flash attention takes the blocked lowering;
what the phases check — shapes, finiteness, equalities against the
references — is the same code the chip runs at full width."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module", autouse=True)
def _process_settings():
    """The script's own process settings (bf16 AMP, rbg PRNG), undone
    afterwards so the rest of the session keeps the test defaults."""
    import jax

    from paddle_tpu.core import flags

    prng = jax.config.jax_default_prng_impl
    chip_smoke.setup()
    yield
    flags.reset_flags()
    jax.config.update("jax_default_prng_impl", prng)


@pytest.mark.parametrize("args", [[], ["--tiny"], ["--chips", "4"]])
def test_script_refuses_to_run_off_the_chip(args):
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        capture_output=True, text=True, cwd=REPO, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode != 0
    assert "found platform 'cpu'" in r.stderr, r.stderr
    assert r.stdout == ""  # no result line, no phase ran


def test_phase_train_image():
    out = {}
    chip_smoke.phase_train_image(chip_smoke.TINY, out)
    assert len(out["losses"]) == chip_smoke.TINY.steps


@pytest.fixture(scope="module")
def nmt_params():
    out = {}
    params = chip_smoke.phase_train_sequence(chip_smoke.TINY, out)
    assert len(out["losses"]) == chip_smoke.TINY.steps
    return params


@pytest.fixture(scope="module")
def decoder(nmt_params):
    out = {}
    dec = chip_smoke.phase_generate(chip_smoke.TINY, nmt_params, out)
    assert out["host_callbacks_in_jitted_loop"]
    assert out["callback_calls"] == out["steps"][2]
    # on the CPU the three programs agree token for token
    rows = f"{chip_smoke.TINY.gen_batch}/{chip_smoke.TINY.gen_batch}"
    assert out["rows_identical_k_vs_k1"] == rows
    assert out["rows_identical_hook_vs_k1"] == rows
    return dec


def test_phase_train_sequence(nmt_params):
    assert "trg_emb" in nmt_params


def test_phase_generate(decoder):
    assert decoder.tokens_per_dispatch == \
        chip_smoke.TINY.tokens_per_dispatch


def test_phase_serve(nmt_params, decoder):
    out = {}
    chip_smoke.phase_serve(chip_smoke.TINY, nmt_params, decoder, out)
    assert out["requests"] == {"nmt": 8, "lm": 8}
    assert out["lm_token_exact"] == "8/8"


def test_phase_kernels():
    # float32 for this one: at the toy batch the batch-norm statistics
    # of a bf16 ResNet are taken over 4 samples and amplify rounding
    # past any tolerance worth stating (first losses 15% apart at bs=4);
    # the chip runs it in bf16 at bs=256
    from paddle_tpu.core import flags

    flags.set_flag("matmul_precision", "default")
    out = {}
    chip_smoke.phase_kernels(chip_smoke.TINY, out)
    assert set(out) >= {"flash_attention", "sparse_updater",
                        "bn_act_conv1x1"}
