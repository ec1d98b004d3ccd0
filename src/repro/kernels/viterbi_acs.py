"""Pallas TPU kernels: the fused radix-2^rho Viterbi ACS forward pass,
the one-pass time-tiled ACS+traceback decode kernel, and the §9
transfer-matrix formation kernel.

This is the compute hot-spot the paper optimizes with tensor cores (§V,
§VIII); here it is re-derived for the TPU MXU (DESIGN.md §2):

  * frames-in-lanes: a tile of BF frames forms the lane (N) dimension of
    the MXU matmuls of every radix step, states sit on sublanes;
  * the operand W = [Theta-hat^T ; P] turns BOTH the super-branch metric
    computation (Eq. 33) and the predecessor path-metric routing (the
    paper's dragonfly-group permutation, §VIII-D) into matmuls.  The
    wrappers hand the kernels W's columns SLOT-MAJOR and transposed, so
    slot r's potentials are the aligned sublane slice [r*S, (r+1)*S):

        pot       = Theta^T_sm * L_t  +  P^T_sm @ Lambda
        Lambda'   = max over the R slot slices               # VPU
        phi       = first argmax over the slot slices        # VPU

    The routing P^T @ Lambda (K = S) is the MXU matmul; the K = B
    branch-metric term is B exact +-1 multiply-adds on the VPU in step
    order — the same sum as the XLA path's concatenated dot, where the
    one-hot routing adds exactly one term.  The transfer kernel keeps
    the row-major orientation of the XLA formation and both dots;
  * the t-loop lives INSIDE the kernel (fori_loop), and the time axis is
    a grid axis: the path-metric carry stays in VMEM scratch across time
    tiles, the analogue of the paper keeping C resident in the
    tensor-core accumulator;
  * survivors may be bit-packed 16-per-int32 (2-bit slots for rho=2) —
    the analogue of the paper's 32-bit output compaction.

In the two ACS kernels no VMEM array has a 4-wide minor axis (Mosaic
pads the minor axis to 128 lanes): LLR blocks are (TT, B, BF),
survivors (TT, W, BF), the one-pass ring (D+TT, W, BF).  The transfer
kernel's (TT, FB, B) blocks keep B minor; its tile is sized with that
padding counted.  ``core.kernel_geometry`` counts every
buffer as Mosaic lays it out, and a kernel is compiled with a raised
scoped-VMEM limit only when its footprint needs one.

``acs_forward_pallas`` — the exact two-pass path: forward only, the
survivor tensor phi (T, F, W) streams to HBM tile by tile and an XLA
scan traces it back.  Stays the batch / tail-biting decode backend
(WAVA needs every survivor).

``acs_decode_fused_pallas`` (DESIGN.md §8) — the one-pass streaming
path: survivors stay in a VMEM ring of decision_depth + time_tile steps
and a per-tile sliding-window traceback INSIDE the kernel emits the
decisions — phi never touches HBM.  It replays the chunked-streaming
state machine of ``core.decoder`` exactly (one delayed traceback per
tile, commit the oldest tile of the window), so it is bit-identical to
the XLA chunked path at equal tile size by construction.

``transfer_matrix_pallas`` (DESIGN.md §9) — per-tile S x S semiring
transfer matrices with the entry-state axis folded into the matmul rows.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "acs_forward_pallas",
    "acs_decode_fused_pallas",
    "transfer_matrix_pallas",
    "unpack_survivors",
    "on_tpu",
    "ring_words",
    "ring_dtype",
    "pick_time_tile",
    "pick_transfer_tile",
    "one_pass_time_tile",
    "fused_ring_vmem_bytes",
    "DEFAULT_BLOCK_FRAMES",
    "DEFAULT_TIME_TILE",
    "KERNEL_VMEM_BUDGET",
]

# backend probes + geometry (ring layout, tile eligibility, VMEM budget)
# are shared with the pallas-free decoder front door — single source of
# truth in repro.core.backend / repro.core.kernel_geometry
from repro.core.backend import (  # noqa: E402 — shared backend probes
    on_tpu,
    resolve_interpret as _resolve_interpret,
)
from repro.core.kernel_geometry import (  # noqa: E402,F401 — re-exports
    DEFAULT_BLOCK_FRAMES,
    DEFAULT_TIME_TILE,
    KERNEL_VMEM_BUDGET,
    MIN_ONE_PASS_TILE,
    forward_time_tile,
    forward_vmem_bytes,
    fused_decode_vmem_bytes,
    fused_ring_vmem_bytes,
    one_pass_time_tile,
    pick_time_tile,
    pick_transfer_tile,
    ring_auto_packed,
    ring_dtype,
    ring_words,
    transfer_block_frames,
    transfer_tile_vmem_bytes,
    vmem_limit_bytes,
)

_SLOT_BITS = {2: 1, 4: 2, 8: 3, 16: 4}  # slot width in bits per radix
_HIGHEST = jax.lax.Precision.HIGHEST  # f32 operands stay f32 on the MXU


def _compiler_params(need: int, semantics):
    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=vmem_limit_bytes(need),
    )


def _frame_tiling(n_frames: int, block_frames: int):
    """(BF, Fp): frames per program and the padded frame count, for the
    frames-on-lanes kernels.  Frames pad to whole 128-lane vregs (a
    narrower lane axis computes no faster, and Mosaic cannot slice a
    lane-padded buffer down to it), then to a multiple of BF."""
    bf = min(block_frames, -(-n_frames // 128) * 128)
    return bf, -(-n_frames // bf) * bf


def _slot_major_t(w_part: jnp.ndarray, n_states: int, n_slots: int):
    """(K, S*R) columns j*R + r  ->  (R*S, K) rows r*S + j: slot r's
    operand becomes the sublane slice [r*S, (r+1)*S)."""
    K = w_part.shape[0]
    return (
        w_part.reshape(K, n_states, n_slots)
        .transpose(2, 1, 0)
        .reshape(n_slots * n_states, K)
    )


def _slot_reduce(pots, semiring: str):
    """Slot reduction over the R per-slot potential arrays (DESIGN.md
    §15) as an elementwise compare chain: (max, first argmax) for
    "tropical" (bit-exact Viterbi); for "logprob" (BCJR) the
    max-normalized logsumexp — the normalization keeps the exp()
    argument <= 0 so the accumulator never overflows whatever the carry
    dtype — with the argmax alongside."""
    best = pots[0]
    arg = jnp.zeros(best.shape, jnp.int32)
    for r in range(1, len(pots)):
        upd = pots[r] > best
        best = jnp.where(upd, pots[r], best)
        arg = jnp.where(upd, jnp.int32(r), arg)
    if semiring == "tropical":
        return best, arg
    acc = jnp.exp(pots[0] - best)
    for p in pots[1:]:
        acc = acc + jnp.exp(p - best)
    return best + jnp.log(acc), arg


def _acs_step(l_t, lam, theta, pred, *, n_states, n_slots, matmul_dtype,
              semiring):
    """One fused radix step, frames on lanes: l_t (B, BF), lam (S, BF)
    f32 -> (new metrics (S, BF) f32, slot indices (S, BF) int32).

    The path-metric routing P^T @ Lambda (K = S) runs on the MXU.  The
    K = B branch-metric sum runs on the VPU as B multiply-adds in step
    order: the +-1 products are exact, so this is the very sum the XLA
    path's dot accumulates, whatever shape the kernel is tiled to."""
    S, R = n_states, n_slots
    lt = l_t.astype(matmul_dtype).astype(jnp.float32)
    bm = theta[:, 0:1] * lt[0:1, :]
    for b in range(1, lt.shape[0]):
        bm = bm + theta[:, b:b + 1] * lt[b:b + 1, :]
    pot = bm + jnp.dot(
        pred, lam.astype(matmul_dtype),
        precision=_HIGHEST, preferred_element_type=jnp.float32,
    )  # (R*S, BF)
    return _slot_reduce([pot[r * S:(r + 1) * S] for r in range(R)], semiring)


def _argmax_rows(x: jnp.ndarray) -> jnp.ndarray:
    """First argmax over the sublane (state) axis, (S, BF) -> (1, BF)."""
    peak = jnp.max(x, axis=0, keepdims=True)
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.min(jnp.where(x == peak, rows, x.shape[0]), axis=0,
                   keepdims=True)


def _pack_phi(phi: jnp.ndarray, n_states: int, bits: int) -> jnp.ndarray:
    """(S, BF) slot indices -> (S//16, BF) int32, 16 slots per word:
    word w holds states 16w..16w+15, state 16w+i at bit offset bits*i."""
    grp = phi.reshape(n_states // 16, 16, phi.shape[-1])
    shifts = bits * jax.lax.broadcasted_iota(jnp.int32, (1, 16, 1), 1)
    return jnp.sum(grp << shifts, axis=1)


def _acs_weights(w, n_states, n_slots, llr_block, matmul_dtype):
    """The slot-major transposed halves of W: Theta^T (R*S, B) in f32
    (+-1, exact) and the one-hot P^T (R*S, S) in the matmul dtype."""
    return (
        _slot_major_t(w[:llr_block].astype(jnp.float32), n_states, n_slots),
        _slot_major_t(
            w[llr_block:].astype(matmul_dtype), n_states, n_slots
        ),
    )


def _acs_forward_kernel(
    blocks_ref,  # (TT, B, BF)  this tile's LLR blocks (matmul dtype)
    lam0_ref,  # (S, BF)       entry path metrics f32
    theta_ref,  # (R*S, B)     slot-major Theta^T (f32)
    pred_ref,  # (R*S, S)      slot-major one-hot P^T (matmul dtype)
    lam_out_ref,  # (S, BF)    exit path metrics f32
    phi_ref,  # (TT, S, BF) int8  OR  (TT, S//16, BF) int32 when packed
    lam_scr,  # VMEM (S, BF) f32   carry across time tiles
    *,
    n_states: int,
    n_slots: int,
    n_steps: int,
    n_time_tiles: int,
    carry_dtype,
    matmul_dtype,
    renorm: bool,
    pack_survivors: bool,
    semiring: str,
):
    TT = blocks_ref.shape[0]
    S, R = n_states, n_slots
    bits = _SLOT_BITS[R]
    j = pl.program_id(1)
    ragged = n_steps % TT != 0  # the last tile runs past the stream end

    @pl.when(j == 0)
    def _init():
        lam_scr[...] = lam0_ref[...].astype(carry_dtype).astype(jnp.float32)

    theta = theta_ref[...]
    pred = pred_ref[...]

    def step(t, lam):
        new_lam, phi = _acs_step(
            blocks_ref[t], lam, theta, pred, n_states=S, n_slots=R,
            matmul_dtype=matmul_dtype, semiring=semiring,
        )
        if pack_survivors:
            phi_ref[t] = _pack_phi(phi, S, bits)
        else:
            phi_ref[t] = phi.astype(jnp.int8)
        if renorm:
            new_lam = new_lam - jnp.max(new_lam, axis=0, keepdims=True)
        # f32 storage of the carry-rounded value: the numerics of the
        # XLA scan's astype chain
        new_lam = new_lam.astype(carry_dtype).astype(jnp.float32)
        if ragged:
            new_lam = jnp.where(j * TT + t < n_steps, new_lam, lam)
        return new_lam

    lam_scr[...] = jax.lax.fori_loop(0, TT, step, lam_scr[...])

    @pl.when(j == n_time_tiles - 1)
    def _flush():
        lam_out_ref[...] = lam_scr[...]


def _forward_call(blocks_t, lam0_t, theta, pred, *, n_states, n_slots,
                  n_steps, time_tile, block_frames, carry_dtype,
                  matmul_dtype, renorm, pack_survivors, semiring,
                  interpret):
    """The two-pass pallas_call on lane-major operands: blocks_t
    (Tp, B, Fp), lam0_t (S, Fp); Tp and Fp multiples of the tiles.
    Returns (lam (S, Fp) f32, phi (Tp, W, Fp))."""
    Tp, B, Fp = blocks_t.shape
    S, R, TT, BF = n_states, n_slots, time_tile, block_frames
    W = ring_words(S, pack_survivors)
    phi_dt = ring_dtype(pack_survivors)
    nt = Tp // TT
    need = forward_vmem_bytes(TT, BF, S, B, R, pack_survivors, matmul_dtype)
    kernel = functools.partial(
        _acs_forward_kernel,
        n_states=S,
        n_slots=R,
        n_steps=n_steps,
        n_time_tiles=nt,
        carry_dtype=carry_dtype,
        matmul_dtype=matmul_dtype,
        renorm=renorm,
        pack_survivors=pack_survivors,
        semiring=semiring,
    )
    return pl.pallas_call(
        kernel,
        grid=(Fp // BF, nt),  # time innermost: sequential carry in VMEM
        in_specs=[
            pl.BlockSpec((TT, B, BF), lambda i, j: (j, 0, i)),
            pl.BlockSpec((S, BF), lambda i, j: (0, i)),
            pl.BlockSpec(theta.shape, lambda i, j: (0, 0)),
            pl.BlockSpec(pred.shape, lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((S, BF), lambda i, j: (0, i)),
            pl.BlockSpec((TT, W, BF), lambda i, j: (j, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, Fp), jnp.float32),
            jax.ShapeDtypeStruct((Tp, W, Fp), phi_dt),
        ],
        scratch_shapes=[pltpu.VMEM((S, BF), jnp.float32)],
        compiler_params=_compiler_params(need, ("parallel", "arbitrary")),
        interpret=interpret,
    )(blocks_t, lam0_t, theta, pred)


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_states",
        "n_slots",
        "block_frames",
        "carry_dtype",
        "matmul_dtype",
        "renorm",
        "pack_survivors",
        "semiring",
        "interpret",
    ),
)
def acs_forward_pallas(
    blocks: jnp.ndarray,  # (T, F, B)
    lam0: jnp.ndarray,  # (F, S) f32
    w: jnp.ndarray,  # (B+S, S*R)
    *,
    n_states: int,
    n_slots: int,
    block_frames: int = DEFAULT_BLOCK_FRAMES,
    carry_dtype=jnp.float32,
    matmul_dtype=jnp.float32,
    renorm: bool = True,
    pack_survivors: bool = False,
    semiring: str = "tropical",
    interpret=None,
):
    """Run the fused forward pass.  Returns (lam_final (F,S) f32, phi).

    phi is (T, F, S) int8 slot indices, or (T, F, S//16) int32 when
    ``pack_survivors`` (16 slots x 2 bits per word for rho=2).
    ``semiring`` selects the slot reduction (DESIGN.md §15): "tropical"
    (max, bit-exact default) or "logprob" (max-normalized logsumexp,
    the BCJR alpha recursion — phi then carries the per-slot argmax,
    which soft decodes ignore).

    Grid (frame tiles, time tiles): the carry stays in VMEM across time
    tiles and survivors stream out one (TT, W, BF) block at a time, so
    VMEM use is set by the time tile (``forward_time_tile`` guards it),
    not by T.  ``interpret=None`` auto-detects: Mosaic on TPU, emulation
    elsewhere.
    """
    interpret = _resolve_interpret(interpret)
    T, F, B = blocks.shape
    S, R = n_states, n_slots
    if pack_survivors and S % 16:
        raise ValueError("pack_survivors requires n_states % 16 == 0")
    BF, Fp = _frame_tiling(F, block_frames)
    TT = forward_time_tile(T, BF, S, B, R, pack_survivors, matmul_dtype)
    Tp = T + (-T) % TT
    blocks_t = jnp.pad(
        jnp.transpose(blocks, (0, 2, 1)).astype(matmul_dtype),
        ((0, Tp - T), (0, 0), (0, Fp - F)),
    )
    lam0_t = jnp.pad(lam0.T, ((0, 0), (0, Fp - F)))
    theta, pred = _acs_weights(w, S, R, B, matmul_dtype)
    lam_t, phi = _forward_call(
        blocks_t, lam0_t, theta, pred, n_states=S, n_slots=R, n_steps=T,
        time_tile=TT, block_frames=BF, carry_dtype=carry_dtype,
        matmul_dtype=matmul_dtype, renorm=renorm,
        pack_survivors=pack_survivors, semiring=semiring,
        interpret=interpret,
    )
    return lam_t[:, :F].T, jnp.transpose(phi[:T, :, :F], (0, 2, 1))


def unpack_survivors(phi_packed: jnp.ndarray, n_states: int, n_slots: int):
    """(T, F, S//16) int32 -> (T, F, S) int8 slot indices."""
    bits = _SLOT_BITS[n_slots]
    T, F, _ = phi_packed.shape
    shifts = bits * jnp.arange(16, dtype=jnp.int32)
    un = (phi_packed[..., None] >> shifts) & (n_slots - 1)
    return un.reshape(T, F, n_states).astype(jnp.int8)


# ---------------------------------------------------------------------------
# One-pass time-tiled decode kernel (DESIGN.md §8)
# ---------------------------------------------------------------------------


def _ring_select(phi_s, state, *, n_states, n_slots, pack_survivors):
    """Per-frame survivor-slot lookup phi_s[state[f], f] without a gather,
    frames on lanes: phi_s (W, BF), state (1, BF) -> (1, BF).

    A one-hot compare + masked sum over the (short) sublane axis lowers
    cleanly on the VPU — for the packed ring the compare runs over S/16
    words only, then a per-lane variable shift extracts the 2-bit slot.
    """
    if pack_survivors:
        rows = jax.lax.broadcasted_iota(jnp.int32, phi_s.shape, 0)
        word = jnp.sum(
            jnp.where(rows == (state >> 4), phi_s, 0), axis=0, keepdims=True
        )
        shift = _SLOT_BITS[n_slots] * (state & 15)
        return (word >> shift) & (n_slots - 1)
    rows = jax.lax.broadcasted_iota(jnp.int32, phi_s.shape, 0)
    return jnp.sum(
        jnp.where(rows == state, phi_s.astype(jnp.int32), 0),
        axis=0, keepdims=True,
    )


def _fused_decode_kernel(
    blocks_ref,  # (TT, B, BF)   this tile's LLR blocks (matmul dtype)
    lam0_ref,  # (S, BF)         entry path metrics f32
    hist0_hbm,  # (D, W, Fp)     entry survivor ring in HBM (chronological)
    theta_ref,  # (R*S, B)       slot-major Theta^T (f32)
    pred_ref,  # (R*S, S)        slot-major one-hot P^T (matmul dtype)
    v_out_ref,  # (TT/G, G, BF) int32  committed step decisions, this tile
    lam_out_ref,  # (S, BF) f32  exit path metrics
    hist_out_hbm,  # (D, W, Fp)  exit survivor ring in HBM (chronological)
    lam_scr,  # VMEM (S, BF) f32       carry across time tiles
    ring_scr,  # VMEM (RING, W, BF)    survivor ring, RING = D + TT steps
    *,
    n_states: int,
    n_slots: int,
    k: int,
    rho: int,
    n_time_tiles: int,
    carry_dtype,
    matmul_dtype,
    renorm: bool,
    pack_survivors: bool,
):
    TT, _, BF = blocks_ref.shape
    D = hist0_hbm.shape[0]
    G = v_out_ref.shape[1]
    S, R = n_states, n_slots
    RING = D + TT
    bits = _SLOT_BITS[R]
    mask = (1 << (k - 1 - rho)) - 1
    i = pl.program_id(0)
    j = pl.program_id(1)
    n_ring_tiles = RING // TT  # = D//TT + 1; ring slot tile of step s

    def hbm_tile(ref, steps=slice(None)):
        """This program's frames (lanes) of an HBM ring, at ``steps``.
        One frame tile takes the whole lane axis unsliced; otherwise BF
        is a multiple of 128 lanes, as Mosaic's DMA slices need."""
        if ref.shape[2] == BF:
            return ref.at[steps]
        return ref.at[steps, :, pl.ds(pl.multiple_of(i * BF, BF), BF)]

    # -- (re)initialize the carry at the first time tile of a frame tile --
    @pl.when(j == 0)
    def _init():
        # round through carry_dtype first, like the XLA scan's init cast
        lam_scr[...] = lam0_ref[...].astype(carry_dtype).astype(jnp.float32)
        # entry ring holds steps -D..-1; step s lives at slot s mod RING,
        # so step -D+n lands at slot TT+n — one DMA from HBM.
        pltpu.sync_copy(hbm_tile(hist0_hbm), ring_scr.at[pl.ds(TT, D)])

    # -- ACS over this tile's TT steps, survivors into the VMEM ring ------
    write_base = jax.lax.rem(j, n_ring_tiles) * TT  # slot of step j*TT
    theta = theta_ref[...]
    pred = pred_ref[...]

    def step(t, lam):
        new_lam, phi = _acs_step(
            blocks_ref[t], lam, theta, pred, n_states=S, n_slots=R,
            matmul_dtype=matmul_dtype, semiring="tropical",
        )
        if pack_survivors:
            ring_scr[write_base + t] = _pack_phi(phi, S, bits)
        else:
            ring_scr[write_base + t] = phi.astype(jnp.int8)
        if renorm:
            new_lam = new_lam - jnp.max(new_lam, axis=0, keepdims=True)
        # scratch stays f32 but holds the carry-rounded value, so the
        # numerics are identical to the XLA scan's astype chain
        return new_lam.astype(carry_dtype).astype(jnp.float32)

    lam = jax.lax.fori_loop(0, TT, step, lam_scr[...])
    lam_scr[...] = lam

    # -- sliding-window traceback: commit the oldest tile of the window --
    # window = steps [(j+1)*TT - RING, (j+1)*TT); the committed TT steps
    # get >= D steps of lookahead — exactly decoder._chunk_step per tile.
    front = _argmax_rows(lam)  # (1, BF)
    read_base = jax.lax.rem(j + 1, n_ring_tiles) * TT  # slot of window[0]

    def walk(n, state):
        # one backward step at window offset n
        slot = read_base + n
        slot = jnp.where(slot >= RING, slot - RING, slot)
        sel = _ring_select(
            ring_scr[slot], state,
            n_states=S, n_slots=R, pack_survivors=pack_survivors,
        )
        return ((state & mask) << rho) | sel

    # phase 1 (lookahead region, newest D steps): walk only
    state = jax.lax.fori_loop(
        0, D, lambda n, st: walk(RING - 1 - n, st), front
    )

    # phase 2 (oldest TT steps): walk and emit this tile's decisions, G
    # steps per stored (G, BF) row group, newest group first
    rows = jax.lax.broadcasted_iota(jnp.int32, (G, BF), 0)

    def group(m, state):
        g = TT // G - 1 - m
        acc = jnp.zeros((G, BF), jnp.int32)
        for r in range(G - 1, -1, -1):
            v = state >> (k - 1 - rho)  # the rho decoded bits of the step
            acc = jnp.where(rows == r, v, acc)
            state = walk(g * G + r, state)
        v_out_ref[g] = acc
        return state

    jax.lax.fori_loop(0, TT // G, group, state)

    # -- stream out the final carry + ring at the last time tile ----------
    @pl.when(j == n_time_tiles - 1)
    def _flush():
        lam_out_ref[...] = lam_scr[...]
        # exit ring = the newest D steps, rotated back to chronological;
        # the rotation is static because n_time_tiles is static.
        base = ((n_time_tiles + 1) % n_ring_tiles) * TT
        n1 = min(D, RING - base)
        pltpu.sync_copy(
            ring_scr.at[pl.ds(base, n1)], hbm_tile(hist_out_hbm, pl.ds(0, n1))
        )
        if D > n1:
            pltpu.sync_copy(
                ring_scr.at[pl.ds(0, D - n1)],
                hbm_tile(hist_out_hbm, pl.ds(n1, D - n1)),
            )


def _fused_call(blocks_t, lam0_t, hist0_t, theta, pred, *, n_states,
                n_slots, k, rho, time_tile, block_frames, carry_dtype,
                matmul_dtype, renorm, pack_survivors, interpret):
    """The one-pass pallas_call on lane-major operands: blocks_t
    (T, B, Fp), lam0_t (S, Fp), hist0_t (D, W, Fp).  Returns
    (v (T/G, G, Fp) int32 step decisions, lam (S, Fp), hist (D, W, Fp))."""
    T, B, Fp = blocks_t.shape
    D, W, _ = hist0_t.shape
    S, R, TT, BF = n_states, n_slots, time_tile, block_frames
    G = math.gcd(TT, 8)
    nt = T // TT
    need = fused_decode_vmem_bytes(D, TT, BF, S, B, R, pack_survivors,
                                   matmul_dtype)
    kernel = functools.partial(
        _fused_decode_kernel,
        n_states=S,
        n_slots=R,
        k=k,
        rho=rho,
        n_time_tiles=nt,
        carry_dtype=carry_dtype,
        matmul_dtype=matmul_dtype,
        renorm=renorm,
        pack_survivors=pack_survivors,
    )
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        kernel,
        grid=(Fp // BF, nt),  # time axis innermost: sequential carry in VMEM
        in_specs=[
            pl.BlockSpec((TT, B, BF), lambda i, j: (j, 0, i)),
            pl.BlockSpec((S, BF), lambda i, j: (0, i)),
            any_space,
            pl.BlockSpec(theta.shape, lambda i, j: (0, 0)),
            pl.BlockSpec(pred.shape, lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((TT // G, G, BF), lambda i, j: (j, 0, i)),
            pl.BlockSpec((S, BF), lambda i, j: (0, i)),
            any_space,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T // G, G, Fp), jnp.int32),
            jax.ShapeDtypeStruct((S, Fp), jnp.float32),
            jax.ShapeDtypeStruct((D, W, Fp), hist0_t.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((S, BF), jnp.float32),
            pltpu.VMEM((D + TT, W, BF), hist0_t.dtype),
        ],
        compiler_params=_compiler_params(need, ("parallel", "arbitrary")),
        interpret=interpret,
    )(blocks_t, lam0_t, hist0_t, theta, pred)


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_states",
        "n_slots",
        "k",
        "rho",
        "time_tile",
        "block_frames",
        "carry_dtype",
        "matmul_dtype",
        "renorm",
        "pack_survivors",
        "interpret",
    ),
)
def acs_decode_fused_pallas(
    blocks: jnp.ndarray,  # (T, F, B), T divisible by time_tile
    lam0: jnp.ndarray,  # (F, S) f32
    hist0: jnp.ndarray,  # (D, F, W) survivor ring at entry (chronological)
    w: jnp.ndarray,  # (B+S, S*R)
    *,
    n_states: int,
    n_slots: int,
    k: int,
    rho: int,
    time_tile: int = DEFAULT_TIME_TILE,
    block_frames: int = DEFAULT_BLOCK_FRAMES,
    carry_dtype=jnp.float32,
    matmul_dtype=jnp.float32,
    renorm: bool = True,
    pack_survivors: bool = False,
    interpret=None,
):
    """One-pass time-tiled decode (DESIGN.md §8).

    Consumes T radix steps of LLR blocks and a decision-depth survivor
    ring carried from an earlier call (zeros for a fresh stream), runs
    the ACS recursion with the path-metric carry resident in VMEM, and
    commits delayed decisions tile by tile with an in-kernel traceback —
    the survivor tensor never reaches HBM.  The entry and exit rings
    move between HBM and the VMEM ring by DMA, once per frame tile.

    Returns (bits, lam, hist):
      * bits (T*rho, F) int8 — decisions for steps [-D, T-D) relative to
        this call's first step (rows r <-> step r/rho - D); rows for
        negative steps replay whatever ``hist0`` held (warmup filler on a
        fresh stream — the caller slices them off, exactly like the XLA
        chunked path's emission accounting);
      * lam (F, S) f32 — path metrics at the stream front;
      * hist (D, F, W) — the exit ring (the newest D steps), chronological,
        ready for the next call or for ``core.viterbi.traceback`` (flush).

    Semantics are exactly ``decoder._chunk_step`` applied per time tile,
    so output is bit-identical to the XLA chunked-streaming path at
    chunk = time_tile by construction, and agrees with any other chunking
    (and with full-sequence decode) wherever survivor paths merge within
    the decision depth.  A footprint beyond ``KERNEL_VMEM_BUDGET`` is
    refused here (``one_pass_time_tile`` refuses it before dispatch).
    """
    interpret = _resolve_interpret(interpret)
    T, F, B = blocks.shape
    D = hist0.shape[0]
    S, R = n_states, n_slots
    TT = min(time_tile, T)
    if T % TT:
        raise ValueError(f"T={T} not divisible by time_tile={TT}")
    if D % TT:
        raise ValueError(f"depth D={D} steps not divisible by time_tile={TT}")
    if pack_survivors and S % 16:
        raise ValueError("pack_survivors requires n_states % 16 == 0")
    W = ring_words(S, pack_survivors)
    ring_dt = ring_dtype(pack_survivors)
    if hist0.shape[2] != W or hist0.dtype != ring_dt:
        raise ValueError(
            f"hist0 {hist0.shape}/{hist0.dtype} does not match "
            f"pack_survivors={pack_survivors} (want (*, F, {W}) {ring_dt})"
        )
    BF, Fp = _frame_tiling(F, block_frames)
    need = fused_decode_vmem_bytes(D, TT, BF, S, B, R, pack_survivors,
                                   matmul_dtype)
    if need > KERNEL_VMEM_BUDGET:
        raise ValueError(
            f"one-pass kernel needs {need} bytes of VMEM at D={D}, "
            f"TT={TT}, BF={BF} (budget {KERNEL_VMEM_BUDGET})"
        )
    fpad = ((0, 0), (0, 0), (0, Fp - F))
    theta, pred = _acs_weights(w, S, R, B, matmul_dtype)
    v, lam_t, hist_t = _fused_call(
        jnp.pad(jnp.transpose(blocks, (0, 2, 1)).astype(matmul_dtype), fpad),
        jnp.pad(lam0.T, fpad[1:]),
        jnp.pad(jnp.transpose(hist0, (0, 2, 1)), fpad),
        theta, pred, n_states=S, n_slots=R, k=k, rho=rho, time_tile=TT,
        block_frames=BF, carry_dtype=carry_dtype, matmul_dtype=matmul_dtype,
        renorm=renorm, pack_survivors=pack_survivors, interpret=interpret,
    )
    v = v.reshape(T, Fp)[:, :F]
    # step t's rho decisions, chronological (LSB-first, trellis.py)
    bits = (v[:, None, :] >> jnp.arange(rho)[None, :, None]) & 1
    return (
        bits.reshape(T * rho, F).astype(jnp.int8),
        lam_t[:, :F].T,
        jnp.transpose(hist_t[:, :, :F], (0, 2, 1)),
    )


# ---------------------------------------------------------------------------
# Transfer-matrix formation kernel (DESIGN.md §9)
# ---------------------------------------------------------------------------


def _transfer_kernel(
    blocks_ref,  # (TT, FB, B)    this tile's LLR blocks (f32)
    theta_ref,  # (R, B, S)       per-slot Theta^T (f32)
    pred_ref,  # (R, S, S)        per-slot one-hot P (f32)
    m_out_ref,  # (1, S, FB, S)   tile transfer matrix, entry-major, f32
    *,
    n_states: int,
    n_slots: int,
    carry_dtype,
    matmul_dtype,
    split_dot: bool,
    semiring: str,
):
    """Build one tile's semiring transfer matrices in VMEM.

    The entry-state axis is folded into the matmul rows, entry-major:
    row i*FB + f carries the metric-from-entry-i vector of frame f, so
    every composition with the next stage is the §2 fused step on
    (S*FB, S) rows — per slot r, the branch metrics L_t @ Theta_r of
    the FB frames (tiled over the S entry states) plus the routing
    M @ P_r on the MXU (S x S tiles are MXU-native for K=7), then the
    slot reduction on the VPU.  With ``split_dot`` the branch-metric
    half runs in matmul_dtype and the metric-routing half (the one-hot
    P) in f32, exactly like ``viterbi.fused_potentials``, so the carry
    quantization matches the XLA formation for every precision policy.
    The matrix carry never leaves VMEM; HBM sees one (S, FB, S) result
    per (tile, frame-block) grid cell.
    """
    TT, FB, _ = blocks_ref.shape
    S, R = n_states, n_slots
    rows = S * FB
    route_dtype = jnp.float32 if split_dot else matmul_dtype
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, S), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, S), 1)
    m0 = jnp.where(
        col == jax.lax.div(row, FB), jnp.float32(0.0), jnp.float32(-1.0e9)
    )
    # operand casts hoisted out of the step loop
    theta = [theta_ref[r].astype(matmul_dtype) for r in range(R)]
    pred = [pred_ref[r].astype(route_dtype) for r in range(R)]

    def step(t, m):
        l_t = blocks_ref[t].astype(matmul_dtype)  # (FB, B)
        m_r = m.astype(route_dtype)
        pots = []
        for r in range(R):
            bm = jnp.dot(l_t, theta[r], precision=_HIGHEST,
                         preferred_element_type=jnp.float32)  # (FB, S)
            route = jnp.dot(m_r, pred[r], precision=_HIGHEST,
                            preferred_element_type=jnp.float32)
            pots.append(jnp.tile(bm, (S, 1)) + route)
        new, _ = _slot_reduce(pots, semiring)
        # no per-row renorm (a per-entry offset would skew the tropical
        # product); the per-frame normalization below bounds the scan
        return new.astype(carry_dtype).astype(jnp.float32)

    m = jax.lax.fori_loop(0, TT, step, m0).reshape(S, FB, S)
    # per-frame normalization (a per-frame-tile constant, DESIGN.md §9)
    peak = jnp.max(jnp.max(m, axis=2, keepdims=True), axis=0, keepdims=True)
    m_out_ref[0] = m - peak


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_states",
        "n_slots",
        "transfer_tile",
        "block_frames",
        "carry_dtype",
        "matmul_dtype",
        "split_dot",
        "semiring",
        "interpret",
    ),
)
def transfer_matrix_pallas(
    blocks: jnp.ndarray,  # (T', F, B), T' divisible by transfer_tile
    w: jnp.ndarray,  # (B+S, S*R)
    *,
    n_states: int,
    n_slots: int,
    transfer_tile: int,
    block_frames: int = 0,  # 0 = auto: keep S*FB rows MXU-sized
    carry_dtype=jnp.float32,
    matmul_dtype=jnp.float32,
    split_dot: bool = False,
    semiring: str = "tropical",
    interpret=None,
):
    """Per-tile semiring transfer matrices M (N, F, S, S) f32, normalized
    per (tile, frame) by their max entry (DESIGN.md §9).  Grid
    (n_tiles, frame_blocks) — tiles are independent, so the whole
    formation is one embarrassingly-parallel launch; the associative
    scan over tiles stays in XLA where its log-depth schedule belongs.
    Frames are padded to a multiple of the frame block (a multiple of
    8); the block auto-shrinks (by 8s) until the per-program footprint
    fits the VMEM budget (``transfer_tile_vmem_bytes``), and a tile too
    large even at 8 frames per program is rejected up front rather than
    at Mosaic compile.  ``interpret=None`` auto-detects: Mosaic on TPU,
    emulation elsewhere.
    """
    interpret = _resolve_interpret(interpret)
    T, F, B = blocks.shape
    S, R = n_states, n_slots
    TT = min(transfer_tile, T)
    if T % TT:
        raise ValueError(f"T'={T} not divisible by transfer_tile={TT}")
    n_tiles = T // TT
    FB = block_frames or transfer_block_frames(F, S)
    if FB % 8:
        raise ValueError(f"block_frames={FB} must be a multiple of 8")
    while FB > 8 and (
        transfer_tile_vmem_bytes(TT, FB, S, B, R) > KERNEL_VMEM_BUDGET
    ):
        FB -= 8
    need = transfer_tile_vmem_bytes(TT, FB, S, B, R)
    if need > KERNEL_VMEM_BUDGET:
        raise ValueError(
            f"transfer_tile={TT} needs {need} bytes of VMEM even at "
            f"{FB} frames/program (budget {KERNEL_VMEM_BUDGET}); pick a "
            f"smaller tile"
        )
    Fp = F + (-F) % FB
    blocks = jnp.pad(blocks.astype(jnp.float32), ((0, 0), (0, Fp - F), (0, 0)))
    w = w.astype(jnp.float32)
    theta = w[:B].reshape(B, S, R).transpose(2, 0, 1)  # (R, B, S)
    pred = w[B:].reshape(S, S, R).transpose(2, 0, 1)  # (R, S, S)

    kernel = functools.partial(
        _transfer_kernel,
        n_states=S,
        n_slots=R,
        carry_dtype=carry_dtype,
        matmul_dtype=matmul_dtype,
        split_dot=split_dot,
        semiring=semiring,
    )
    m = pl.pallas_call(
        kernel,
        grid=(n_tiles, Fp // FB),
        in_specs=[
            pl.BlockSpec((TT, FB, B), lambda n, f: (n, f, 0)),
            pl.BlockSpec(theta.shape, lambda n, f: (0, 0, 0)),
            pl.BlockSpec(pred.shape, lambda n, f: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, S, FB, S), lambda n, f: (n, 0, f, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((n_tiles, S, Fp, S), jnp.float32),
        compiler_params=_compiler_params(need, ("parallel", "parallel")),
        interpret=interpret,
    )(blocks, theta, pred)
    # entry-major (N, S_i, F, S_j) -> (N, F, S_i, S_j)
    return jnp.transpose(m[:, :, :F], (0, 2, 1, 3))
