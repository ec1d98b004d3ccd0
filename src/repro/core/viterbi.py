"""Matrix-form Viterbi decoding (paper §V, §VIII) in JAX.

The forward ACS recursion is expressed as ONE fused matmul per radix-2^rho
step (DESIGN.md §2), the TPU-native generalization of the paper's packed
16x16 tensor op (Fig. 15):

    potentials = [L_t | Lambda_{t-rho}] @ [Theta-hat^T ; P]     (MXU)
    Lambda_t   = max_slots   potentials                         (VPU)
    phi_t      = argmax_slots potentials                        (VPU)

  * rho = 1 reproduces the paper's radix-2 butterfly formulation (Eq. 16-22),
  * rho = 2 reproduces the radix-4 super-branch formulation (Eq. 33-35); the
    predecessor one-hot P plays the role of the paper's dragonfly-group
    permutation (§VIII-D) and works for ANY (k, beta, polys).

Frames are batched on the leading axis so that on TPU they occupy the
128-wide lane dimension of the MXU (frames-in-lanes, DESIGN.md §2).

Precision: the paper's Fig. 13 study maps to `AcsPrecision` — matmul inputs
may be bf16 (paper: fp16 A/B), the accumulated path-metric carry must be f32
(paper: fp32 C) or BER degrades.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .kernel_geometry import (  # noqa: F401 — pallas-free geometry + re-export
    DEFAULT_BLOCK_FRAMES,
    one_pass_time_tile,
    pick_time_tile,
    ring_auto_packed,
    ring_dtype,
    ring_words,
    time_parallel_plan,
)
from .semiring import NEG, TROPICAL, Semiring
from .trellis import AcsTables, CodeSpec, build_acs_tables

_HIGHEST = jax.lax.Precision.HIGHEST

__all__ = [
    "AcsPrecision",
    "forward_fused",
    "fused_potentials",
    "traceback",
    "traceback_with_state",
    "decode_frames",
    "TiledDecoderConfig",
    "tiled_decode_stream",
    "tiled_decode_streams",
    "blocks_from_llrs",
    "pick_time_tile",
    "NEG",
]


@dataclasses.dataclass(frozen=True)
class AcsPrecision:
    """Precision knobs mirroring the paper's Table I / Fig. 13 axes."""

    matmul_dtype: jnp.dtype = jnp.float32  # A/B operands (paper: half)
    carry_dtype: jnp.dtype = jnp.float32  # accumulated path metric (paper: C)
    channel_dtype: jnp.dtype = jnp.float32  # LLR storage (paper: 'channel')
    renorm: bool = True  # subtract per-frame max every step
    split_dot: bool = False  # §Perf C5: branch metrics in bf16 on the MXU
    # + path-metric routing (Lambda @ P) in f32 — keeps the carry exact so
    # renorm can be dropped without the bf16xno-renorm BER interaction

    def label(self) -> str:
        """Unique name for BENCH rows: every knob that changes the
        compiled program is encoded, so e.g. split_dot on/off never
        aliases to the same row name."""
        short = {jnp.float32: "f32", jnp.bfloat16: "bf16", jnp.float16: "f16"}
        parts = [
            f"C={short.get(self.carry_dtype, self.carry_dtype)}",
            f"mm={short.get(self.matmul_dtype, self.matmul_dtype)}",
            f"ch={short.get(self.channel_dtype, self.channel_dtype)}",
        ]
        if self.split_dot:
            parts.append("split")
        if not self.renorm:
            parts.append("norenorm")
        return ",".join(parts)

    # -- §14 headroom introspection (core/validate.py renorm guard) --------

    def carry_mantissa_digits(self) -> int:
        """Significand width of the carry dtype, implicit bit included
        (f32: 24, f16: 11, bf16: 8) — the log2 of the magnitude at which
        unit-scale branch increments start being absorbed."""
        return int(jnp.finfo(self.carry_dtype).nmant) + 1

    def carry_absorb_limit(self) -> float:
        """Carry magnitude beyond which adding a unit-scale increment
        loses at least one bit of the increment (2**mantissa_digits).
        The §14 renorm guard derives its soft threshold from this."""
        return float(2.0 ** self.carry_mantissa_digits())

    def carry_max(self) -> float:
        """Largest finite value of the carry dtype (the wrap-to-Inf
        ceiling the §14 hard limit must stay under)."""
        return float(jnp.finfo(self.carry_dtype).max)


def fused_potentials(
    l_t: jnp.ndarray,  # (rows, B) LLR block
    lam: jnp.ndarray,  # (rows, S) path metrics
    w: jnp.ndarray,  # (B+S, S*R) stacked [Theta^T ; P]
    w_theta: jnp.ndarray,  # (B, S*R)
    w_pred: jnp.ndarray,  # (S, S*R) f32 one-hot
    precision: AcsPrecision,
) -> jnp.ndarray:
    """One fused-ACS matmul (DESIGN.md §2): branch metrics + path-metric
    routing in a single MXU op, f32 accumulation.  f32 operands are
    multiplied at ``Precision.HIGHEST``: a TPU's default f32 dot rounds
    its inputs to bf16, which would quietly quantize the path metrics
    the precision policy keeps in f32 (a no-op on CPU).  Shared by the
    sequential scan and the §9 transfer-matrix formation so the two
    paths quantize identically.  Returns (rows, S*R) f32 potentials."""
    if precision.split_dot:
        return jnp.dot(
            l_t.astype(precision.matmul_dtype),
            w_theta,
            precision=_HIGHEST,
            preferred_element_type=jnp.float32,
        ) + jnp.dot(
            lam.astype(jnp.float32), w_pred,
            precision=_HIGHEST,
            preferred_element_type=jnp.float32,
        )
    x = jnp.concatenate(
        [l_t.astype(precision.matmul_dtype),
         lam.astype(precision.matmul_dtype)],
        axis=1,
    )
    return jnp.dot(
        x, w, precision=_HIGHEST, preferred_element_type=jnp.float32
    )


def blocks_from_llrs(llrs: jnp.ndarray, rho: int) -> jnp.ndarray:
    """(F, n, beta) LLRs -> (T', F, rho*beta) fused-step blocks.

    n must be divisible by rho (pad with zero LLRs beforehand — a zero LLR
    carries no information and does not bias the path metrics).
    """
    F, n, beta = llrs.shape
    if n % rho:
        raise ValueError(f"n={n} not divisible by rho={rho}")
    t = n // rho
    # stage-major flattening matches trellis.superbranch_output_bits order
    blocks = llrs.reshape(F, t, rho * beta)
    return jnp.transpose(blocks, (1, 0, 2))


def init_metric(n_frames: int, n_states: int, initial_state: Optional[int]):
    """Metric at t=0: one-hot (known encoder start) or uniform (truncated)."""
    if initial_state is None:
        return jnp.zeros((n_frames, n_states), jnp.float32)
    lam = jnp.full((n_frames, n_states), NEG, jnp.float32)
    return lam.at[:, initial_state].set(0.0)


@functools.partial(
    jax.jit,
    static_argnames=(
        "tables", "precision", "use_kernel", "pack_survivors", "semiring",
    ),
)
def forward_fused(
    blocks: jnp.ndarray,
    lam0: jnp.ndarray,
    tables: AcsTables,
    precision: AcsPrecision = AcsPrecision(),
    use_kernel: bool = False,
    pack_survivors: bool = False,
    semiring: Semiring = TROPICAL,
):
    """Fused forward procedure.

    blocks: (T', F, rho*beta); lam0: (F, S).
    Returns (lam_final (F, S) f32, phis) with phis (T', F, S) int8 slots,
    or (T', F, S//16) int32 when ``pack_survivors`` (§Perf C2 — the
    paper's 32-bit output compaction applied to the survivor store).

    ``semiring`` selects the slot reduction (DESIGN.md §15): TROPICAL
    (max — the bit-exact Viterbi default) or LOGPROB (logsumexp — the
    BCJR alpha recursion; ``phis`` then carry the per-slot argmax,
    which soft decodes ignore).
    """
    if use_kernel:  # pragma: no cover - exercised via kernels tests
        from repro.kernels import ops as kernel_ops

        return kernel_ops.viterbi_forward(
            blocks, lam0, tables, precision, pack_survivors=pack_survivors,
            semiring=semiring.name,
        )

    W = jnp.asarray(tables.fused_w, precision.matmul_dtype)  # (B+S, S*R)
    S, R = tables.n_states, tables.n_slots
    B = tables.llr_block
    W_theta = jnp.asarray(tables.theta_t, precision.matmul_dtype)
    W_pred = jnp.asarray(tables.pred_onehot, jnp.float32)
    blocks = blocks.astype(precision.channel_dtype)
    bits = {2: 1, 4: 2, 8: 3, 16: 4}[R]

    def step(lam, l_t):
        pot = fused_potentials(l_t, lam, W, W_theta, W_pred, precision)
        pot = pot.reshape(lam.shape[0], S, R)
        new_lam = semiring.sum(pot, axis=-1)
        phi = jnp.argmax(pot, axis=-1)
        if pack_survivors:
            grp = phi.reshape(phi.shape[0], S // 16, 16).astype(jnp.int32)
            shifts = bits * jnp.arange(16, dtype=jnp.int32)
            phi = jnp.sum(grp << shifts, axis=-1).astype(jnp.int32)
        else:
            phi = phi.astype(jnp.int8)
        if precision.renorm:
            new_lam = new_lam - jnp.max(new_lam, axis=-1, keepdims=True)
        new_lam = new_lam.astype(precision.carry_dtype)
        return new_lam, phi

    lam_final, phis = jax.lax.scan(step, lam0.astype(precision.carry_dtype), blocks)
    return lam_final.astype(jnp.float32), phis


def _traceback_scan(
    phis: jnp.ndarray, final_state: jnp.ndarray, tables: AcsTables
):
    """Shared Algorithm-2 scan: returns (start_state (F,), bits (F, T'*rho))
    where start_state is the survivor path's state BEFORE the first stage
    in ``phis`` (the tail-biting consistency probe, DESIGN.md §7)."""
    k, rho = tables.spec.k, tables.rho
    mask = (1 << (k - 1 - rho)) - 1
    packed = phis.dtype == jnp.int32
    slot_bits = {2: 1, 4: 2, 8: 3, 16: 4}[tables.n_slots]

    def step(j, phi_t):
        if packed:
            word = jnp.take_along_axis(phi_t, (j // 16)[:, None], axis=1)
            slot = (word[:, 0] >> (slot_bits * (j % 16))) & (
                tables.n_slots - 1
            )
        else:
            slot = jnp.take_along_axis(
                phi_t.astype(jnp.int32), j[:, None], axis=1
            )[:, 0]
        v = j >> (k - 1 - rho)  # the rho decoded bits of this step
        pred = ((j & mask) << rho) | slot
        return pred, v

    start, vs = jax.lax.scan(
        step, final_state.astype(jnp.int32), phis, reverse=True
    )
    # vs: (T', F) -> bits (F, T'*rho), chronological within each block
    bits = (vs[..., None] >> jnp.arange(rho)) & 1  # (T', F, rho)
    return start, jnp.transpose(bits, (1, 0, 2)).reshape(
        final_state.shape[0], -1
    )


@functools.partial(jax.jit, static_argnames=("tables",))
def traceback(
    phis: jnp.ndarray, final_state: jnp.ndarray, tables: AcsTables
):
    """Vectorized Algorithm 2 over frames, one radix step at a time.

    phis: (T', F, S) int8 slots OR (T', F, S//16) int32 packed (§Perf C2
    — unpacked lazily per step, never materialized); final_state: (F,).
    Returns decoded bits (F, T'*rho) int32 — the survivor path's branch
    inputs, which for this FSM are the top rho bits of each visited state
    (chronological order = LSB-first of that field, see trellis.py).
    """
    return _traceback_scan(phis, final_state, tables)[1]


@functools.partial(jax.jit, static_argnames=("tables",))
def traceback_with_state(
    phis: jnp.ndarray, final_state: jnp.ndarray, tables: AcsTables
):
    """`traceback` that also returns the path's start state (F,) — used by
    the wrap-around (tail-biting) decoder to test start/end agreement."""
    return _traceback_scan(phis, final_state, tables)


def decode_frames(
    llrs: jnp.ndarray,
    spec: CodeSpec,
    rho: int = 2,
    initial_state: Optional[int] = 0,
    final_state: Optional[int] = None,
    precision: AcsPrecision = AcsPrecision(),
    use_kernel: bool = False,
    pack_survivors: bool = False,
):
    """Decode a batch of independent frames.  llrs: (F, n, beta)."""
    tables = build_acs_tables(spec, rho)
    blocks = blocks_from_llrs(jnp.asarray(llrs), rho)
    lam0 = init_metric(llrs.shape[0], spec.n_states, initial_state)
    lam, phis = forward_fused(
        blocks, lam0, tables, precision, use_kernel, pack_survivors
    )
    if final_state is None:
        fs = jnp.argmax(lam, axis=-1).astype(jnp.int32)
    else:
        fs = jnp.full((llrs.shape[0],), final_state, jnp.int32)
    return traceback(phis, fs, tables)


# ---------------------------------------------------------------------------
# Tiled stream decoder (paper §III tiling scheme + our frames-in-lanes batch)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TiledDecoderConfig:
    """Frame tiling (paper §III): each frame decodes `frame_len` bits and
    carries `overlap` stages of history on BOTH sides (Eq. 5's v)."""

    frame_len: int = 64
    overlap: int = 32
    rho: int = 2

    def __post_init__(self):
        if (self.frame_len + 2 * self.overlap) % self.rho:
            raise ValueError("frame_len + 2*overlap must be divisible by rho")
        if self.frame_len % self.rho:
            raise ValueError("frame_len must be divisible by rho")

    @property
    def window(self) -> int:
        return self.frame_len + 2 * self.overlap


def _one_pass_window_plan(
    spec: CodeSpec,
    cfg: TiledDecoderConfig,
    precision: AcsPrecision,
    pack_survivors: bool,
    time_tile: Optional[int],
    block_frames: Optional[int],
):
    """(time_tile, ring_packed) for decoding tiling windows through the
    one-pass kernel, or None to fall back to two-pass — the shared
    ``one_pass_time_tile`` eligibility (tile grid + VMEM budget, the
    same guard decode_chunk uses) plus the window-specific requirement
    that the overlap sits on the rho grid (the ring holds whole radix
    steps)."""
    v, rho = cfg.overlap, cfg.rho
    if v % rho:
        return None
    packed = ring_auto_packed(spec.n_states, pack_survivors)
    tt = one_pass_time_tile(
        v // rho, cfg.window // rho, spec.n_states, packed,
        time_tile, block_frames, rho * spec.beta, 1 << rho,
        precision.matmul_dtype,
    )
    return None if tt is None else (tt, packed)


def _one_pass_windows(
    frames: jnp.ndarray,  # (n_frames, window, beta)
    spec: CodeSpec,
    cfg: TiledDecoderConfig,
    precision: AcsPrecision,
    time_tile: int,
    ring_packed: bool,
    block_frames: Optional[int],
) -> jnp.ndarray:
    """Decode tiling windows through the one-pass kernel (DESIGN.md §8).

    The left overlap plays the warmup, the right overlap the lookahead:
    with decision depth D = overlap/rho steps, every center stage is
    committed by the in-kernel sliding traceback with >= overlap stages
    of lookahead — the same merge guarantee the two-pass tiled stitcher
    relies on — and the kernel's emitted rows [2*overlap :) are exactly
    the centers, so no flush traceback is needed at all.
    """
    from repro.kernels import ops as kernel_ops

    v, rho = cfg.overlap, cfg.rho
    blocks = blocks_from_llrs(frames, rho)
    d_steps = v // rho
    tables = build_acs_tables(spec, rho)
    n_frames = frames.shape[0]
    lam0 = init_metric(n_frames, spec.n_states, None)
    # the VMEM ring is bit-packed whenever the state count allows — the
    # paper's 32-bit compaction is part of the §8 ring design
    hist0 = jnp.zeros(
        (d_steps, n_frames, ring_words(spec.n_states, ring_packed)),
        ring_dtype(ring_packed),
    )
    bits, _, _ = kernel_ops.viterbi_decode_fused(
        blocks,
        lam0,
        hist0,
        tables,
        precision,
        time_tile=time_tile,
        block_frames=block_frames or DEFAULT_BLOCK_FRAMES,
        pack_survivors=ring_packed,
    )
    # rows r <-> stage r - v; centers are stages [v, v+f) = rows [2v, 2v+f)
    return bits[2 * v:, :].T.astype(jnp.int32)  # (n_frames, f)


def tiled_decode_stream(
    llrs: jnp.ndarray,
    spec: CodeSpec,
    cfg: TiledDecoderConfig = TiledDecoderConfig(),
    **kw,
) -> jnp.ndarray:
    """One stream (n, beta) -> (n,) bits: ``tiled_decode_streams`` on a
    batch of one (keyword arguments as there)."""
    return tiled_decode_streams(llrs[None], spec, cfg, **kw)[0]


def tiled_decode_streams(
    llrs: jnp.ndarray,
    spec: CodeSpec,
    cfg: TiledDecoderConfig = TiledDecoderConfig(),
    precision: AcsPrecision = AcsPrecision(),
    use_kernel: bool = False,
    pack_survivors: bool = False,
    one_pass: bool = False,
    time_tile: Optional[int] = None,
    block_frames: Optional[int] = None,
    time_parallel: Optional[bool] = None,
    transfer_tile: Optional[int] = None,
) -> jnp.ndarray:
    """Decode N long LLR streams (N, n, beta) -> (N, n) via overlapping
    parallel frames; the windows of every stream form one frame batch.

    Each stream is zero-LLR padded by `overlap` on both ends, sliced into
    n/frame_len windows of length frame_len + 2*overlap, all windows of
    all streams decoded in parallel (truncated Viterbi: uniform start
    metric, argmax end state), and the center frame_len decisions of each
    window are stitched back together per stream.

    With ``one_pass=True`` the windows run through the time-tiled
    ACS+traceback kernel (DESIGN.md §8): survivors stay in a VMEM ring
    and decisions are committed in-kernel with >= overlap stages of
    lookahead, so the (T, F, S) survivor tensor never reaches HBM.
    Decisions agree with the two-pass path wherever survivor paths merge
    within the overlap — the same assumption window stitching itself
    makes.  Falls back to two-pass when the overlap is not on the rho
    grid (the ring needs whole radix steps) or states cannot be packed.

    ``time_parallel`` (None = auto) additionally routes the window ACS
    through the §9 transfer-matrix scan — the small-window-count /
    long-window regime (large ``frame_len`` configs) where frames-only
    batching leaves the accelerator idle.  The auto rule is the shared
    ``time_parallel_plan`` one: engage when ``n_windows * n_states``
    fits the device's idle-row budget (n_states being the formation
    work multiplier) AND the window tiles usefully; the window decode
    then runs in O(tile + log2 tiles) sequential depth instead of
    window/rho.  Precedence: an EXPLICIT ``time_parallel=True`` beats
    the one-pass kernel plan; on auto, an eligible one-pass plan wins
    (same depth class per window, none of the S x formation work).
    """
    n_streams, n, beta = llrs.shape
    f, v = cfg.frame_len, cfg.overlap
    n_win = -(-n // f)  # windows per stream (ceil)
    n_frames = n_streams * n_win
    padded_len = n_win * f + 2 * v
    pad_lo = v
    pad_hi = padded_len - n - v
    padded = jnp.pad(jnp.asarray(llrs), ((0, 0), (pad_lo, pad_hi), (0, 0)))
    idx = jnp.arange(n_win)[:, None] * f + jnp.arange(cfg.window)[None, :]
    frames = padded[:, idx].reshape(n_frames, cfg.window, beta)
    tp_tile = time_parallel_plan(
        n_frames, cfg.window // cfg.rho, spec.n_states,
        time_parallel, transfer_tile,
    )
    plan = (
        _one_pass_window_plan(
            spec, cfg, precision, pack_survivors, time_tile, block_frames
        )
        if one_pass else None
    )
    # an explicitly requested time-parallel path beats the one-pass
    # kernel; on auto, an eligible one-pass plan wins (same per-window
    # depth class without the S x formation work)
    if plan is not None and not (time_parallel is True and tp_tile):
        center = _one_pass_windows(
            frames, spec, cfg, precision, plan[0], plan[1], block_frames,
        )
        return center.reshape(n_streams, -1)[:, :n]
    if tp_tile is not None:
        from .timeparallel import decode_time_parallel

        decoded = decode_time_parallel(
            frames,
            spec,
            rho=cfg.rho,
            initial_state=None,
            final_state=None,
            precision=precision,
            transfer_tile=tp_tile,
            use_kernel=use_kernel,
            pack_survivors=pack_survivors,
        )
    else:
        decoded = decode_frames(
            frames,
            spec,
            rho=cfg.rho,
            initial_state=None,
            final_state=None,
            precision=precision,
            use_kernel=use_kernel,
            pack_survivors=pack_survivors,
        )
    center = decoded[:, v : v + f]  # (n_frames, f)
    return center.reshape(n_streams, -1)[:, :n]
