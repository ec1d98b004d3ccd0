"""Time-parallel decode via tropical transfer matrices (DESIGN.md §9).

Every other decode path carries the path-metric vector SEQUENTIALLY
across the stream: parallelism is frames-only and single-stream latency
is linear in T.  But the ACS recurrence

    Lambda_{t+1}[j] = max_i ( Lambda_t[i] + A_t[i, j] )

is a max-plus (tropical) matrix-vector product with the stage transfer
matrix A_t[i, j] = branch metric of edge i -> j (-inf off-trellis), and
the tropical semiring is associative: transfer matrices over whole TILES
of steps compose in any order.  That is the block-parallel decomposition
of the Gb/s block-based GPU decoder (arXiv:1608.00066) and the
memory-efficient parallel decoder of arXiv:2011.09337, expressed here on
the paper's dense tensor-op formulation so the MXU does the lifting:

  1. **formation** — per tile of ``transfer_tile`` steps, compose the
     stage matrices into M_tile (F, S, S).  Each composition is one §2
     fused step with the ENTRY-STATE axis folded into the matmul batch:
     rows (tile, frame, entry) carry the metric-from-entry vector, so
     the broadcasted-add + segment-max is shaped as a dense
     (N*F*S, B+S) @ (B+S, S*R) matmul in ``precision.matmul_dtype`` with
     f32 accumulation (``viterbi.fused_potentials`` — the exact op the
     sequential scan runs, batch = S per frame-tile).
  2. **prefix scan** — ``jax.lax.associative_scan`` of the tropical
     matmul over tiles: all tile ENTRY metrics in O(log2 n_tiles) depth
     instead of O(T').
  3. **recovery** — the ordinary fused ACS re-runs every tile IN
     PARALLEL (tiles folded into the frame/lane axis) from its scanned
     entry metric: the survivors are the sequential scan's survivors by
     construction, bit-exact up to float associativity.
  4. **parallel traceback** — a reverse associative scan gives each
     tile's best-metric-to-the-end vector; prefix + suffix pins the
     survivor path's state at every tile boundary at once, and one
     vmapped per-tile traceback emits all bits in tile depth.

Total sequential depth: 3*tile + O(log2 n_tiles) dependent steps vs T'
for the scan — the latency axis.  The price is S x more formation work
(perfectly parallel), which is why the auto-select
(``kernel_geometry.time_parallel_plan``) only engages when frames-only
batching underfills the device (small-F / large-T serving).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .kernel_geometry import pick_transfer_tile
from .semiring import TROPICAL, Semiring
from .trellis import AcsTables, CodeSpec, build_acs_tables
from .viterbi import (
    NEG,
    AcsPrecision,
    blocks_from_llrs,
    forward_fused,
    fused_potentials,
    init_metric,
    traceback,
)

__all__ = [
    "tropical_matmul",
    "tropical_identity",
    "tiled_blocks",
    "transfer_matrices",
    "prefix_entry_metrics",
    "entry_from_prefix",
    "transfer_prefix",
    "timeparallel_forward",
    "decode_time_parallel",
]


def tropical_matmul(
    a: jnp.ndarray, b: jnp.ndarray, matmul_dtype=jnp.float32
) -> jnp.ndarray:
    """Max-plus compose  C[..., i, j] = max_k A[..., i, k] + B[..., k, j].

    Operands are quantized to ``matmul_dtype`` (mirroring the MXU input
    dtype of the §2 fused step) and accumulated in f32 — the broadcasted
    add + reduce-max is the VPU's dense-matmul analogue.  Now a thin
    alias of ``Semiring.matmul`` at TROPICAL (DESIGN.md §15), kept for
    the historical call sites; bit-identical to the pre-semiring code.
    """
    return TROPICAL.matmul(a, b, matmul_dtype)


def tropical_identity(n_states: int) -> jnp.ndarray:
    """The tropical unit matrix: 0 on the diagonal, -inf elsewhere.
    (Shared by both semirings — see ``Semiring.identity``.)"""
    return jnp.where(
        jnp.eye(n_states, dtype=bool), jnp.float32(0.0), NEG
    )


def tiled_blocks(blocks: jnp.ndarray, transfer_tile: int) -> jnp.ndarray:
    """(T', F, B) -> (tile, N, F, B) with step t = n*tile + i."""
    T, F, B = blocks.shape
    if T % transfer_tile:
        raise ValueError(
            f"T'={T} steps not divisible by transfer_tile={transfer_tile}"
        )
    n = T // transfer_tile
    return blocks.reshape(n, transfer_tile, F, B).transpose(1, 0, 2, 3)


def transfer_matrices(
    blocks: jnp.ndarray,  # (T', F, B)
    tables: AcsTables,
    precision: AcsPrecision = AcsPrecision(),
    transfer_tile: int = None,
    use_kernel: bool = False,
    semiring: Semiring = TROPICAL,
) -> jnp.ndarray:
    """Per-tile semiring transfer matrices M (N, F, S, S) (DESIGN.md §9).

    M[n, f, i, j] = best path metric (TROPICAL) or total log-score
    (LOGPROB, DESIGN.md §15) entering tile n in state i and leaving in
    state j, normalized per (n, f) by its max entry (a per-frame-tile
    constant, invisible to every argmax downstream and cancelled
    per-boundary in BCJR LLRs) so scanned products stay bounded however
    long the stream.  Formation runs the §2 fused step with the entry
    axis folded into the matmul batch; ``use_kernel`` routes it through
    the Pallas kernel (``kernels.viterbi_acs.transfer_matrix_pallas``)
    which keeps the matrix carry in VMEM.
    """
    transfer_tile = transfer_tile or pick_transfer_tile(blocks.shape[0])
    if use_kernel:  # pragma: no cover - exercised via kernels tests
        from repro.kernels import ops as kernel_ops

        return kernel_ops.viterbi_transfer_matrices(
            blocks, tables, precision, transfer_tile=transfer_tile,
            semiring=semiring.name,
        )
    T, F, B = blocks.shape
    S, R = tables.n_states, tables.n_slots
    n_tiles = T // transfer_tile
    tiles = tiled_blocks(
        blocks.astype(precision.channel_dtype), transfer_tile
    )
    W = jnp.asarray(tables.fused_w, precision.matmul_dtype)
    W_theta = jnp.asarray(tables.theta_t, precision.matmul_dtype)
    W_pred = jnp.asarray(tables.pred_onehot, jnp.float32)
    rows = n_tiles * F * S
    m0 = jnp.broadcast_to(
        tropical_identity(S), (n_tiles, F, S, S)
    )

    def step(m, l_t):  # m (N, F, S, S); l_t (N, F, B)
        lam = m.reshape(rows, S)
        l = jnp.broadcast_to(
            l_t[:, :, None, :], (n_tiles, F, S, B)
        ).reshape(rows, B)
        pot = fused_potentials(l, lam, W, W_theta, W_pred, precision)
        new = semiring.sum(pot.reshape(rows, S, R), axis=-1)
        # no per-row renorm here: a per-ENTRY-state offset would change
        # the tropical products; the per-(tile, frame) normalization
        # below is the semantics-preserving analogue
        new = new.astype(precision.carry_dtype).astype(jnp.float32)
        return new.reshape(n_tiles, F, S, S), None

    m, _ = jax.lax.scan(step, m0, tiles)
    return m - jnp.max(m, axis=(-2, -1), keepdims=True)


def prefix_entry_metrics(
    m: jnp.ndarray,  # (N, F, S, S) tile transfer matrices
    lam0: jnp.ndarray,  # (F, S) stream-entry metrics
    matmul_dtype=jnp.float32,
    semiring: Semiring = TROPICAL,
) -> jnp.ndarray:
    """Entry metric of every tile, (N, F, S), in O(log2 N) compose depth:
    entry_0 = lam0 and entry_p = lam0 (x) (M_0 o ... o M_{p-1}) via one
    ``associative_scan`` over the semiring matmul.  Equal to the
    sequential scan's metric at each tile boundary up to a per-frame
    constant and float associativity (asserted in
    tests/test_timeparallel.py)."""
    compose = functools.partial(semiring.matmul, matmul_dtype=matmul_dtype)
    prefix = jax.lax.associative_scan(compose, m, axis=0)
    return entry_from_prefix(prefix, lam0, semiring)


def entry_from_prefix(
    prefix: jnp.ndarray,  # (N, F, S, S) INCLUSIVE tile prefix products
    lam0: jnp.ndarray,  # (F, S) metrics entering tile 0
    semiring: Semiring = TROPICAL,
) -> jnp.ndarray:
    """Tile entry metrics (N, F, S) from already-scanned inclusive
    prefix products — the piece the time-sharded decoder reuses (it
    needs the raw prefixes for the device all-gather too)."""
    heads = semiring.sum(lam0[None, :, :, None] + prefix[:-1], axis=-2)
    return jnp.concatenate([lam0[None], heads], axis=0)


def _suffix_to_final(
    m: jnp.ndarray,  # (N, F, S, S)
    final_state: jnp.ndarray,  # (F,) int32 traceback start state
    matmul_dtype=jnp.float32,
) -> jnp.ndarray:
    """v (N, F, S): best metric from state s at the START of tile p to
    ``final_state`` at the stream end — the reverse associative scan of
    the same tropical matmul, gathered at the final state's column.

    ``reverse=True`` hands the LATER element in as the left operand, so
    the (non-commutative) compose is flipped to keep suffix products in
    stream order:  suffix_p = M_p o M_{p+1} o ... o M_{N-1}."""
    def compose(a, b):
        return tropical_matmul(b, a, matmul_dtype=matmul_dtype)

    suffix = jax.lax.associative_scan(compose, m, axis=0, reverse=True)
    idx = final_state[None, :, None, None].astype(jnp.int32)
    return jnp.take_along_axis(
        suffix, jnp.broadcast_to(idx, suffix.shape[:-1] + (1,)), axis=-1
    )[..., 0]


@functools.partial(
    jax.jit,
    static_argnames=(
        "tables", "precision", "transfer_tile", "use_kernel", "semiring",
    ),
)
def transfer_prefix(
    blocks: jnp.ndarray,  # (T', F, B)
    tables: AcsTables,
    precision: AcsPrecision = AcsPrecision(),
    transfer_tile: int = 32,
    use_kernel: bool = False,
    semiring: Semiring = TROPICAL,
) -> jnp.ndarray:
    """Inclusive tile prefix products (N, F, S, S) — formation + scan,
    the lam0-INDEPENDENT half of ``timeparallel_forward``.  WAVA
    precomputes it once and reuses it across circulations (only the
    wrap-around entry metric changes between passes)."""
    m = transfer_matrices(
        blocks, tables, precision, transfer_tile, use_kernel=use_kernel,
        semiring=semiring,
    )
    compose = functools.partial(
        semiring.matmul, matmul_dtype=precision.matmul_dtype
    )
    return jax.lax.associative_scan(compose, m, axis=0)


def _recovery(
    blocks: jnp.ndarray,
    entry: jnp.ndarray,  # (N, F, S) tile entry metrics
    tables: AcsTables,
    precision: AcsPrecision,
    transfer_tile: int,
    use_kernel: bool,
    pack_survivors: bool,
):
    """Phase 3: re-run every tile in parallel from its entry metric.
    Returns (lam_fin (N,F,S) exit metrics per tile, phis
    (tile, N*F, S|S//16) survivors)."""
    T, F, _ = blocks.shape
    n_tiles = T // transfer_tile
    tiles = tiled_blocks(blocks, transfer_tile)
    lam_fin, phis = forward_fused(
        tiles.reshape(transfer_tile, n_tiles * F, -1),
        entry.reshape(n_tiles * F, -1),
        tables,
        precision,
        use_kernel,
        pack_survivors,
    )
    return lam_fin.reshape(n_tiles, F, -1), phis


def _formation_and_recovery(
    blocks: jnp.ndarray,
    lam0: jnp.ndarray,
    tables: AcsTables,
    precision: AcsPrecision,
    transfer_tile: int,
    use_kernel: bool,
    pack_survivors: bool,
):
    """Phases 1-3: tile matrices, scanned entries, parallel re-run.

    Returns (m (N,F,S,S), entry (N,F,S), lam_fin (N,F,S) exit metrics
    per tile, phis (tile, N*F, S|S//16) survivors)."""
    m = transfer_matrices(
        blocks, tables, precision, transfer_tile, use_kernel=use_kernel
    )
    entry = prefix_entry_metrics(m, lam0, precision.matmul_dtype)
    lam_fin, phis = _recovery(
        blocks, entry, tables, precision, transfer_tile, use_kernel,
        pack_survivors,
    )
    return m, entry, lam_fin, phis


@functools.partial(
    jax.jit,
    static_argnames=(
        "tables", "precision", "transfer_tile", "use_kernel",
        "pack_survivors",
    ),
)
def timeparallel_forward(
    blocks: jnp.ndarray,  # (T', F, B)
    lam0: jnp.ndarray,  # (F, S)
    tables: AcsTables,
    precision: AcsPrecision = AcsPrecision(),
    transfer_tile: int = 32,
    use_kernel: bool = False,
    pack_survivors: bool = False,
    prefix: Optional[jnp.ndarray] = None,
):
    """Plug-compatible ``forward_fused``: (lam_final (F, S) f32, phis
    (T', F, S) int8 / packed int32) — but with sequential depth
    transfer_tile + O(log2 n_tiles) instead of T'.  lam_final comes from
    the last tile's recovery pass, so downstream argmax/traceback (and
    the WAVA wrap-around probe, which feeds it back as the next
    circulation's lam0) see the sequential scan's values.

    ``prefix`` lets callers that run several forwards over the SAME
    blocks (WAVA circulations) pass ``transfer_prefix`` precomputed
    once — formation and the scan depend only on the blocks, not lam0.
    """
    T, F, _ = blocks.shape
    n_tiles = T // transfer_tile
    if prefix is None:
        _, _, lam_fin, phis = _formation_and_recovery(
            blocks, lam0, tables, precision, transfer_tile, use_kernel,
            pack_survivors,
        )
    else:
        entry = entry_from_prefix(prefix, lam0)
        lam_fin, phis = _recovery(
            blocks, entry, tables, precision, transfer_tile, use_kernel,
            pack_survivors,
        )
    w = phis.shape[-1]
    phis_full = phis.reshape(transfer_tile, n_tiles, F, w).transpose(
        1, 0, 2, 3
    ).reshape(T, F, w)
    return lam_fin[-1], phis_full


@functools.partial(
    jax.jit,
    static_argnames=(
        "tables", "precision", "transfer_tile", "use_kernel",
        "pack_survivors", "final_state",
    ),
)
def _decode_tp(
    blocks: jnp.ndarray,
    lam0: jnp.ndarray,
    tables: AcsTables,
    precision: AcsPrecision,
    transfer_tile: int,
    use_kernel: bool,
    pack_survivors: bool,
    final_state: Optional[int],
):
    T, F, _ = blocks.shape
    rho = tables.rho
    n_tiles = T // transfer_tile
    m, entry, lam_fin, phis = _formation_and_recovery(
        blocks, lam0, tables, precision, transfer_tile, use_kernel,
        pack_survivors,
    )
    if final_state is None:
        fs = jnp.argmax(lam_fin[-1], axis=-1).astype(jnp.int32)
    else:
        fs = jnp.full((F,), final_state, jnp.int32)
    # pin the survivor path's state at every tile boundary at once:
    # through state s at the start of tile p, the best full path scores
    # entry_p[s] + (best s -> final_state over the remaining tiles)
    v = _suffix_to_final(m, fs, precision.matmul_dtype)
    starts = jnp.argmax(entry + v, axis=-1).astype(jnp.int32)  # (N, F)
    exits = jnp.concatenate([starts[1:], fs[None]], axis=0)
    bits = traceback(phis, exits.reshape(n_tiles * F), tables)
    return bits.reshape(n_tiles, F, transfer_tile * rho).transpose(
        1, 0, 2
    ).reshape(F, T * rho)


def decode_time_parallel(
    llrs: jnp.ndarray,
    spec: CodeSpec,
    rho: int = 2,
    initial_state: Optional[int] = 0,
    final_state: Optional[int] = None,
    precision: AcsPrecision = AcsPrecision(),
    transfer_tile: Optional[int] = None,
    use_kernel: bool = False,
    pack_survivors: bool = False,
) -> jnp.ndarray:
    """Time-parallel ``decode_frames``: llrs (F, n, beta) -> bits (F, n)
    with n divisible by rho.  Same contract, same survivors (bit-exact
    up to float associativity), sequential depth O(tile + log2 tiles).
    """
    tables = build_acs_tables(spec, rho)
    blocks = blocks_from_llrs(jnp.asarray(llrs), rho)
    tt = pick_transfer_tile(blocks.shape[0], transfer_tile)
    lam0 = init_metric(llrs.shape[0], spec.n_states, initial_state)
    return _decode_tp(
        blocks, lam0, tables, precision, tt, use_kernel, pack_survivors,
        final_state,
    )
