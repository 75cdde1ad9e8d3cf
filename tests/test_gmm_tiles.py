"""The grouped products' tile rule (`ops/moe._tile`): a function of the
dimension and the cap alone. The decoder cells' expert widths that have a
lane-multiple divisor of at least 512 keep the tiles they had (a divisor pads
nothing); a width without one (the Kimi cell's 1,408 = 11 x 128) is covered
in a few masked tiles, never in 128-wide slices. The kernels' masking of the
remainder is held in interpret mode in `tests/test_mellum.py`; the tiles
compiled for a described v5e in `tests/test_tpu_compile.py`."""

import pytest

from paddle_tpu.ops import moe as M

# the default scoped VMEM of a v5e kernel, which megablox does not raise
VMEM_BYTES = 16 * 2**20


@pytest.mark.parametrize("n,tile", [
    (2304, 768), (896, 896),            # Mellum: hidden, expert
    (1792, 896), (2048, 1024),          # LFM2: expert; hidden (and Kimi's,
    (512, 512)],                        # Laguna's); Laguna: expert
    ids=["2304", "896", "1792", "2048", "512"])
def test_the_cells_widths_with_a_divisor_keep_their_tiles(n, tile):
    assert M._tile(n, M.TILE_CAP) == tile


def test_a_1408_wide_expert_takes_two_tiles_of_768():
    """768 and 512 both pad 1,408 by 128 (to 1,536); the larger wins."""
    t = M._tile(1408, M.TILE_CAP)
    assert t == 768 and t != 128
    assert (-(-1408 // t), -1408 % t) == (2, 128)


@pytest.mark.parametrize("n", range(128, 4097, 128))
def test_every_lane_multiple_width_gets_a_wide_tile_or_itself(n):
    t = M._tile(n, M.TILE_CAP)
    assert t == n or (512 <= t <= M.TILE_CAP and t % 128 == 0), t
    # no tile of the range pads n less
    assert all(-n % t <= -n % u for u in range(512, M.TILE_CAP + 1, 128)
               if t != n)


def test_rows_are_tiled_by_a_divisor():
    """The kernel lays its groups out in whole row tiles."""
    assert M._row_tile(98304) == M._row_tile(131072) == 512
    assert M._row_tile(384) == 384 and M._row_tile(640) == 128


def _vmem(tm, tk, tn, tgmm):
    """A call's VMEM: its three bfloat16 blocks double-buffered and the
    float32 accumulator; tgmm also takes float32 copies of its two operand
    blocks (it selects the group's rows in float32)."""
    if not tgmm:
        return 2 * 2 * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn
    return (2 * 2 * (tm * tk + tm * tn + tk * tn) + 4 * tk * tn
            + 4 * (tm * tk + tm * tn))


# the Kimi cell's twelve calls a layer by kind: (m, k, n, tgmm)
_KIMI = {"up and gate": (98304, 2048, 1408, False),
         "down": (98304, 1408, 2048, False),
         "up and gate, rows' gradient": (98304, 1408, 2048, False),
         "down, rows' gradient": (98304, 2048, 1408, False),
         "up and gate, weights' gradient": (98304, 2048, 1408, True),
         "down, weights' gradient": (98304, 1408, 2048, True)}


@pytest.mark.parametrize("kind", list(_KIMI))
def test_the_kimi_calls_tiles_fit_the_scoped_vmem(kind):
    m, k, n, tgmm = _KIMI[kind]
    tiles = (M._row_tile(m), M._tile(k, M.TILE_CAP), M._tile(n, M.TILE_CAP))
    assert 768 in tiles and 128 not in tiles
    assert _vmem(*tiles, tgmm) < VMEM_BYTES, tiles
    # a whole 1,408 would not fit the weights' gradient
    if tgmm:
        whole = tuple(1408 if t == 768 else t for t in tiles)
        assert _vmem(*whole, tgmm) > VMEM_BYTES
