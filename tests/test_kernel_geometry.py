"""The one-pass kernel's time-tile rule (DESIGN.md §8): the largest
common divisor of the decision depth and the chunk's step count whose
whole kernel footprint fits the VMEM budget, capped by an explicit
``time_tile``."""
import dataclasses
import math

import pytest

from repro.codes.registry import get_code
from repro.configs.viterbi_k7 import (
    VITERBI_CELLS,
    ViterbiConfig,
    config_for_standard,
)
from repro.core import ViterbiDecoder
from repro.core.kernel_geometry import (
    DEFAULT_BLOCK_FRAMES,
    KERNEL_VMEM_BUDGET,
    MIN_ONE_PASS_TILE,
    forward_time_tile,
    forward_vmem_bytes,
    fused_decode_vmem_bytes,
    one_pass_time_tile,
    pick_time_tile,
    vmem_limit_bytes,
)

S, B, R = 64, 4, 4  # K=7, radix-4


def _fits(d, tt, bf, packed):
    return fused_decode_vmem_bytes(d, tt, bf, S, B, R, packed) <= (
        KERNEL_VMEM_BUDGET
    )


def _tile_at_32(d, t, bf, packed):
    """The rule as it stood with a fixed target of 32 steps: the largest
    common divisor <= 32, refused when below the floor or beyond VMEM."""
    tt = pick_time_tile(d, t, 32)
    if tt < min(MIN_ONE_PASS_TILE, d, t) or not _fits(d, tt, bf, packed):
        return None
    return tt


@pytest.mark.parametrize(
    "d,t,tile",
    [(2560, 32768, 512), (4480, 28672, 896), (2560, 4096, 512)],
    ids=["ccsds.links8", "dvbs.transponders4", "ccsds.links256"],
)
def test_one_pass_tile_at_cell_shapes(d, t, tile):
    """The cells' (depth, chunk) shapes in radix steps: the tile is the
    greatest common divisor, so the walk costs 6 ring steps per ACS
    step; an explicit time_tile caps it."""
    for packed in (True, False):
        assert one_pass_time_tile(d, t, S, packed) == tile
    assert (d + tile) // tile == 6
    assert one_pass_time_tile(d, t, S, True, time_tile=32) == 32
    assert one_pass_time_tile(d, t, S, True, time_tile=100) == 64
    assert one_pass_time_tile(d, t, S, True, time_tile=tile + 1) == tile


@pytest.mark.parametrize("packed", [True, False], ids=["packed", "i8"])
@pytest.mark.parametrize("bf", [128, 256])
def test_one_pass_tile_never_below_fixed_32(bf, packed):
    """Over a grid of shapes: wherever the fixed-32 rule ran one-pass
    the rule still does, at a tile at least as large, and every tile it
    gives divides both axes and fits the budget."""
    for d in (4, 64, 1000, 2560, 4480, 6144, 10240, 20480):
        for t in (4, 48, 1000, 2048, 3000, 4096, 28672, 32768):
            old = _tile_at_32(d, t, bf, packed)
            new = one_pass_time_tile(d, t, S, packed, block_frames=bf)
            if old is not None:
                assert new is not None and new >= old, (d, t, old, new)
            if new is not None:
                assert d % new == 0 and t % new == 0
                assert _fits(d, new, bf, packed)


@pytest.mark.parametrize("d,tile", [(4096, 2048), (6144, 768), (6656, 512)])
def test_one_pass_tile_walks_down_to_fit(d, tile):
    """Unpacked int8 rings whose largest common tile overflows VMEM: the
    rule walks down the common divisors to the largest that fits."""
    assert not _fits(d, d, DEFAULT_BLOCK_FRAMES, False)
    assert one_pass_time_tile(d, d, S, False) == tile
    assert _fits(d, tile, DEFAULT_BLOCK_FRAMES, False)
    larger = [c for c in range(tile + 1, d + 1) if d % c == 0]
    assert not any(_fits(d, c, DEFAULT_BLOCK_FRAMES, False) for c in larger)


@pytest.mark.parametrize(
    "d,t,packed",
    [(16 * 2560, 2048, True), (10240, 2048, False), (2560, 2044, True)],
    ids=["ring_16x_depth", "i8_ring_20480_stages", "common_tile_4"],
)
def test_one_pass_tile_refuses(d, t, packed):
    """No tile fits a ring 16x the default depth (or an unpacked
    20480-stage one), and a common divisor below the floor runs
    two-pass: the rule gives None, as before."""
    assert one_pass_time_tile(d, t, S, packed) is None
    if math.gcd(d, t) >= MIN_ONE_PASS_TILE:
        assert not _fits(d, MIN_ONE_PASS_TILE, DEFAULT_BLOCK_FRAMES, packed)


@pytest.mark.parametrize("cell", sorted(VITERBI_CELLS))
def test_config_route_tile_matches_engine_route(cell):
    """A decoder built from a cell's configuration picks the same
    one-pass time tile as the engine's ``from_standard`` decoder for a
    cell-sized chunk: the geometry follows the shape alone, and no
    configuration field caps it."""
    fields = {f.name for f in dataclasses.fields(ViterbiConfig)}
    assert not fields & {"time_tile", "block_frames", "transfer_tile"}
    code = VITERBI_CELLS[cell].code
    by_config = ViterbiDecoder.from_config(
        config_for_standard(code), use_kernel=True
    )
    by_engine = ViterbiDecoder.from_standard(code, use_kernel=True)
    assert by_config.decision_depth == by_engine.decision_depth
    stages = VITERBI_CELLS[cell].stream_len
    punct = get_code(code).puncture
    if punct is not None:  # stream_len counts kept serial LLRs
        stages = punct.stages_for(stages)
    rho = by_engine.rho
    t, d = stages // rho, by_engine.decision_depth // rho
    tile = by_engine._one_pass_tile(t, d)
    assert by_config._one_pass_tile(t, d) == tile
    if code == "dvb-s-r78":  # dvbs.transponders4's (T, D)
        assert (t, d) == (28672, 4480)
        assert tile == 896


MiB = 2**20


def test_two_pass_count_at_256_states():
    """The two-pass kernel at K=9 rate 1/3 (S 256, B 6), packed, at the
    UMTS cell's tile (TT 128, BF 256): Mosaic asks for 16.47 MiB of
    scoped VMEM, where three counted potentials made 13.25 MiB (compile
    rehearsal); eight (R*S, BF) float32 potentials make 18.25 MiB, and
    the limit is raised to that plus the headroom.  Tiles follow the
    step count (the 512- and 128-stage rungs, 256 and 64 steps).  The
    counts at 64 states are the same as before."""
    need = forward_vmem_bytes(128, 256, 256, 6, 4, True)
    assert need == 13 * MiB + MiB // 4 + 5 * MiB
    assert need > int(16.47 * MiB)
    assert vmem_limit_bytes(need) == need + 8 * MiB
    assert [forward_time_tile(t, 256, 256, 6, 4, True)
            for t in (256, 64, 32)] == [128, 64, 32]
    assert forward_vmem_bytes(128, 256, S, B, R, True) == 3_735_552
    assert fused_decode_vmem_bytes(2560, 512, 256, S, B, R, True) == (
        19_464_192)
    assert fused_decode_vmem_bytes(2560, 512, 256, S, B, R, False) == (
        57_212_928)
