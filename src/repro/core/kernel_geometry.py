"""One-pass kernel geometry (DESIGN.md §8) — pure-Python helpers shared
by the decoder front door and the Pallas kernels.

Lives in ``core`` (not ``kernels``) so that ``repro.core`` never imports
``jax.experimental.pallas`` at module load: the streaming entry points
need the ring layout, tile-eligibility and VMEM-budget rules to DECIDE
whether to launch the fused kernel, and only the launch itself (lazy,
in-function) touches Pallas.  ``kernels.viterbi_acs`` re-exports these
names, and is the only consumer that also implements them in silicon.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

__all__ = [
    "DEFAULT_BLOCK_FRAMES",
    "DEFAULT_TIME_TILE",
    "DEFAULT_TRANSFER_TILE",
    "DEFAULT_FORWARD_TIME_TILE",
    "VMEM_CAPACITY_BYTES",
    "DEFAULT_SCOPED_VMEM_BYTES",
    "VMEM_HEADROOM_BYTES",
    "KERNEL_VMEM_BUDGET",
    "MIN_ONE_PASS_TILE",
    "MIN_TIME_PARALLEL_TILES",
    "ring_words",
    "ring_dtype",
    "ring_auto_packed",
    "pick_time_tile",
    "one_pass_time_tile",
    "vmem_bytes",
    "vmem_limit_bytes",
    "fused_ring_vmem_bytes",
    "fused_decode_vmem_bytes",
    "forward_vmem_bytes",
    "forward_time_tile",
    "default_transfer_tile",
    "pick_transfer_tile",
    "time_parallel_plan",
    "transfer_block_frames",
    "transfer_tile_vmem_bytes",
    "ENGINE_MIN_CELL",
    "pick_cell_length",
    "pick_cell_frames",
]

DEFAULT_BLOCK_FRAMES = 256
DEFAULT_TIME_TILE = 32
# two-pass kernel: radix steps per grid program — the path-metric carry
# stays in VMEM across time tiles and survivors stream out tile by tile,
# so VMEM use is bounded by the tile, never by the frame length
DEFAULT_FORWARD_TIME_TILE = 128

# VMEM as Mosaic sees it on a TPU v5e core: a kernel whose buffers
# exceed the scoped limit it was compiled with fails RESOURCE_EXHAUSTED,
# and the limit can be raised per kernel up to the physical capacity.
# The headroom covers Mosaic's own temporaries (matmul results, spills).
VMEM_CAPACITY_BYTES = 128 * 2**20
DEFAULT_SCOPED_VMEM_BYTES = 16 * 2**20
VMEM_HEADROOM_BYTES = 8 * 2**20
# every kernel's counted footprint must stay under this; shapes beyond
# it are refused up front (one-pass falls back to two-pass, the other
# kernels shrink their tile or raise) rather than at Mosaic compile time
KERNEL_VMEM_BUDGET = VMEM_CAPACITY_BYTES - VMEM_HEADROOM_BYTES

# below this time tile the one-pass kernel degenerates (a near-full ring
# traceback per tiny tile): both streaming entry points fall back to the
# two-pass step instead — keep their criteria in sync via this constant
MIN_ONE_PASS_TILE = 8

# time-parallel decode (DESIGN.md §9): target steps per transfer-matrix
# tile, and the tile count below which a matrix scan has nothing to
# parallelize (the sequential path is already that shallow)
DEFAULT_TRANSFER_TILE = 64
MIN_TIME_PARALLEL_TILES = 4


def ring_words(n_states: int, pack_survivors: bool) -> int:
    """Last-axis width of a survivor ring/tensor entry: 16 slots per
    int32 word when packed (requires n_states % 16 == 0), else one int8
    per state.  The single source of truth for the ring layout."""
    return n_states // 16 if pack_survivors else n_states


def ring_dtype(pack_survivors: bool):
    return jnp.int32 if pack_survivors else jnp.int8


def ring_auto_packed(n_states: int, pack_survivors: bool) -> bool:
    """The ring PACKING POLICY, in one place: the §8 ring bit-packs
    whenever the state count allows (the paper's 32-bit compaction is
    part of the ring design), and always when explicitly requested."""
    return pack_survivors or n_states % 16 == 0


def _divisors(n: int):
    """Every divisor of ``n`` >= 1, ascending."""
    small = [c for c in range(1, math.isqrt(n) + 1) if n % c == 0]
    return small + [n // c for c in reversed(small) if c * c != n]


def pick_time_tile(d_steps: int, t_steps: int, target=None) -> int:
    """Largest time tile <= ``target`` dividing both the decision depth
    and the step count — the one-pass kernel needs the ring and the time
    grid on a common tile (DESIGN.md §8).  Always >= 1."""
    target = target or DEFAULT_TIME_TILE
    g = math.gcd(int(d_steps), int(t_steps))
    return max((c for c in _divisors(g) if c <= target), default=1)


def vmem_bytes(shape, dtype) -> int:
    """Bytes one VMEM buffer of ``shape`` takes as Mosaic lays it out on
    a TPU v5e: the minor dim padded to 128 lanes; the second-minor dim
    rounded up to a multiple of 8 rows, or, when it holds 4 rows or
    fewer, to a power of two of at least one 32-bit sublane word (1 row
    of 32-bit, 2 of 16-bit, 4 of 8-bit); leading dims unpadded.  So a
    (2592, 256, 4) int32 buffer takes 339,738,624 bytes (s32[2592,256,128]
    in Mosaic's allocation report), not the 10.6 MB its elements hold,
    while (2592, 4, 256) int32 takes exactly its 10,616,832 bytes."""
    itemsize = jnp.dtype(dtype).itemsize
    dims = (1,) * max(0, 2 - len(shape)) + tuple(int(d) for d in shape)
    rows = dims[-2]
    if rows <= 4:
        second = max(1 << (rows - 1).bit_length(), 4 // itemsize)
    else:
        second = -(-rows // 8) * 8
    minor = -(-dims[-1] // 128) * 128
    return math.prod(dims[:-2]) * second * minor * itemsize


def vmem_limit_bytes(need: int):
    """Scoped-VMEM limit to compile a kernel of footprint ``need`` with:
    None (the compiler default) when it fits the default scoped limit,
    else the footprint plus headroom, capped at the physical capacity —
    the limit is raised only for kernels that really need it."""
    want = int(need) + VMEM_HEADROOM_BYTES
    if want <= DEFAULT_SCOPED_VMEM_BYTES:
        return None
    return min(want, VMEM_CAPACITY_BYTES)


def _acs_operand_bytes(time_tile, block_frames, n_states, llr_block,
                       n_slots, matmul_dtype) -> int:
    """VMEM shared by both frame-batched ACS kernels (frames on lanes):
    the double-buffered (TT, B, BF) LLR blocks, the entry/exit metric
    blocks and the carry scratch, the slot-major weights, and the
    (R*S, BF) potentials of one step."""
    S, B, R, BF = n_states, llr_block, n_slots, block_frames
    return (
        2 * vmem_bytes((time_tile, B, BF), matmul_dtype)
        + 5 * vmem_bytes((S, BF), jnp.float32)
        + 2 * vmem_bytes((R * S, B), matmul_dtype)
        + 2 * vmem_bytes((R * S, S), matmul_dtype)
        + 3 * vmem_bytes((R * S, BF), jnp.float32)
    )


def fused_ring_vmem_bytes(
    depth_steps: int,
    time_tile: int,
    block_frames: int,
    n_states: int,
    pack_survivors: bool,
) -> int:
    """VMEM footprint of the one-pass kernel's (D+TT, W, BF) survivor
    ring, frames on lanes — the term that bounds usable decision depths
    (DESIGN.md §8 table)."""
    return vmem_bytes(
        (depth_steps + time_tile,
         ring_words(n_states, pack_survivors),
         block_frames),
        ring_dtype(pack_survivors),
    )


def fused_decode_vmem_bytes(
    depth_steps: int,
    time_tile: int,
    block_frames: int,
    n_states: int,
    llr_block: int,
    n_slots: int,
    pack_survivors: bool,
    matmul_dtype=jnp.float32,
) -> int:
    """Whole VMEM footprint of one ``acs_decode_fused_pallas`` program:
    the survivor ring plus the ACS operands and the (TT/G, G, BF)
    decision block (the entry/exit rings stay in HBM, moved by DMA)."""
    g = math.gcd(time_tile, 8)
    return (
        fused_ring_vmem_bytes(
            depth_steps, time_tile, block_frames, n_states, pack_survivors
        )
        + _acs_operand_bytes(time_tile, block_frames, n_states, llr_block,
                             n_slots, matmul_dtype)
        + 2 * vmem_bytes((time_tile // g, g, block_frames), jnp.int32)
    )


def forward_vmem_bytes(
    time_tile: int,
    block_frames: int,
    n_states: int,
    llr_block: int,
    n_slots: int,
    pack_survivors: bool,
    matmul_dtype=jnp.float32,
) -> int:
    """VMEM footprint of one ``acs_forward_pallas`` program: the ACS
    operands plus the double-buffered (TT, W, BF) survivor block."""
    return (
        _acs_operand_bytes(time_tile, block_frames, n_states, llr_block,
                           n_slots, matmul_dtype)
        + 2 * vmem_bytes(
            (time_tile, ring_words(n_states, pack_survivors), block_frames),
            ring_dtype(pack_survivors),
        )
    )


def forward_time_tile(
    t_steps: int,
    block_frames: int,
    n_states: int,
    llr_block: int,
    n_slots: int,
    pack_survivors: bool,
    matmul_dtype=jnp.float32,
) -> int:
    """The two-pass kernel's VMEM guard: the largest time tile <=
    ``DEFAULT_FORWARD_TIME_TILE`` (capped at the step count) whose
    footprint fits ``KERNEL_VMEM_BUDGET``, halving from there.  Raises
    when not even one step per program fits."""
    tt = max(1, min(DEFAULT_FORWARD_TIME_TILE, t_steps))
    args = (block_frames, n_states, llr_block, n_slots, pack_survivors,
            matmul_dtype)
    while tt > 1 and forward_vmem_bytes(tt, *args) > KERNEL_VMEM_BUDGET:
        tt //= 2
    need = forward_vmem_bytes(tt, *args)
    if need > KERNEL_VMEM_BUDGET:
        raise ValueError(
            f"two-pass kernel needs {need} bytes of VMEM at one step per "
            f"program (budget {KERNEL_VMEM_BUDGET}); lower block_frames"
        )
    return tt


def default_transfer_tile(t_steps: int) -> int:
    """Shape-derived transfer-tile target ~ sqrt(T'): balances the tile
    depth (formation/recovery loops) against the scan size (n_tiles S x S
    composes) — the right neighbourhood on every backend."""
    target = 1
    while target * target < t_steps:
        target *= 2
    return max(DEFAULT_TRANSFER_TILE, min(target, 2048))


def pick_transfer_tile(t_steps: int, target=None) -> int:
    """Largest divisor of ``t_steps`` <= ``target`` (default: the
    sqrt-scaled ``default_transfer_tile``) — transfer-matrix tiles must
    tile the step axis exactly (a zero-LLR remainder pad would perturb
    the final metrics, unlike the one-pass ring which carries state
    across ragged chunks).  Always >= 1."""
    return pick_time_tile(
        t_steps, t_steps, target or default_transfer_tile(t_steps)
    )


def time_parallel_plan(
    n_frames: int,
    t_steps: int,
    n_states: int,
    time_parallel=None,
    transfer_tile=None,
    underfill_rows=None,
):
    """Shared time-parallel eligibility (DESIGN.md §9) for every decode
    entry point: the transfer tile (in radix steps) to decode with, or
    None when the shape should stay on the sequential scan.

    ``time_parallel=False`` forces sequential; ``True`` engages whenever
    a usable tile grid exists; ``None`` auto-selects — engage only when
    ``n_frames * n_states`` fits the device's idle-row budget
    (``backend.device_underfill_rows``; small-F/large-T serving), since
    the transfer-matrix formation multiplies the perfectly-parallel work
    by S to cut the sequential depth from T' to tile + log2(tiles).
    """
    if time_parallel is False:
        return None
    if t_steps <= 0 or n_frames <= 0:
        return None
    tt = pick_transfer_tile(t_steps, transfer_tile)
    if tt < 2 or t_steps // tt < MIN_TIME_PARALLEL_TILES:
        return None
    if time_parallel:
        return tt
    if underfill_rows is None:
        from .backend import device_underfill_rows

        underfill_rows = device_underfill_rows()
    return tt if n_frames * n_states <= underfill_rows else None


def transfer_block_frames(n_frames: int, n_states: int) -> int:
    """Frames per ``transfer_matrix_pallas`` program: enough to give the
    (S*FB, S) matrix carry ~512 MXU rows, a multiple of 8 (the sublane
    tile the entry-major row layout needs), never more than the frame
    count rounded up to 8."""
    target = max(8, (512 // n_states) // 8 * 8)
    return min(target, -(-n_frames // 8) * 8)


def transfer_tile_vmem_bytes(
    time_tile: int,
    block_frames: int,
    n_states: int,
    llr_block: int,
    n_slots: int,
) -> int:
    """VMEM footprint of one ``transfer_matrix_pallas`` program as Mosaic
    lays it out: the double-buffered (TT, FB, B) LLR blocks and per-slot
    weights, the (S*FB, S) matrix carry with the per-slot potentials of
    one step, and the double-buffered (S, FB, S) result — the term that
    bounds usable transfer tiles on-chip (DESIGN.md §9 table)."""
    S, B, R, FB = n_states, llr_block, n_slots, block_frames
    f32 = jnp.float32
    return (
        2 * vmem_bytes((time_tile, FB, B), f32)
        + 2 * vmem_bytes((R, B, S), f32)
        + 2 * vmem_bytes((R, S, S), f32)
        + (R + 2) * vmem_bytes((S * FB, S), f32)
        + 2 * vmem_bytes((S, FB, S), f32)
    )


# serving-engine cell geometry (DESIGN.md §10): ragged request lengths
# are bucketed onto a power-of-two ladder starting here, so the number
# of distinct jitted (F, T) decode programs stays logarithmic in the
# length spread while per-request padding waste stays < 2x worst case
ENGINE_MIN_CELL = 64


def pick_cell_length(n: int, min_cell: int = ENGINE_MIN_CELL,
                     multiple: int = 1) -> int:
    """Serving-cell length rung for an n-element request (DESIGN.md §10):
    the smallest power-of-two ladder rung >= n (>= ``min_cell``), rounded
    up to ``multiple`` — punctured codes pass their kept-bits-per-period
    so every cell depunctures to whole pattern periods.  The rung is the
    T half of the engine's (F, T) cell key, so two engines fed the same
    requests always agree on the cells (bucketing determinism)."""
    if n <= 0:
        raise ValueError(f"request length must be positive, got {n}")
    cell = min_cell
    while cell < n:
        cell *= 2
    return cell + (-cell) % multiple


def pick_cell_frames(n: int, max_batch: int) -> int:
    """Frame-count rung of an engine cell (DESIGN.md §10): the smallest
    power of two >= ``n``, capped at ``max_batch`` — the F half of the
    cell key, bounding jit-cache entries to log2(max_batch) per length
    rung while keeping batch occupancy >= 50% by construction."""
    f = 1
    while f < min(n, max_batch):
        f *= 2
    return min(f, max_batch)


def one_pass_time_tile(
    d_steps: int,
    t_steps: int,
    n_states: int,
    ring_packed: bool,
    time_tile=None,
    block_frames=None,
    llr_block: int = 4,
    n_slots: int = 4,
    matmul_dtype=jnp.float32,
):
    """Shared one-pass tile rule for every streaming entry point
    (decoder.decode_chunk and the tiled window path): the time tile to
    launch the fused kernel with, or None when the shape should take the
    two-pass fallback.

    After each tile of TT steps the kernel walks its whole (D+TT)-step
    ring to commit the oldest TT, so the walk costs (D+TT)/TT steps per
    ACS step and the largest tile is the fastest.  The tile is the
    largest common divisor of the depth and the step count (at most
    ``time_tile`` when given) whose whole kernel footprint, ring
    included and counted as Mosaic lays it out, fits the VMEM budget.
    None when packing is impossible or no tile of at least
    ``MIN_ONE_PASS_TILE`` fits (a tile near 1 walks the whole ring per
    step)."""
    if d_steps <= 0 or t_steps <= 0:
        return None
    if ring_packed and n_states % 16:
        return None
    least = min(MIN_ONE_PASS_TILE, d_steps, t_steps)
    bf = block_frames or DEFAULT_BLOCK_FRAMES
    for tt in reversed(_divisors(math.gcd(int(d_steps), int(t_steps)))):
        if tt < least:
            break
        if time_tile and tt > time_tile:
            continue
        if (
            fused_decode_vmem_bytes(d_steps, tt, bf, n_states, llr_block,
                                    n_slots, ring_packed, matmul_dtype)
            <= KERNEL_VMEM_BUDGET
        ):
            return tt
    return None
