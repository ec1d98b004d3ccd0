"""Sharded multi-device Viterbi decode (DESIGN.md §6).

Frames are embarrassingly parallel: the ACS recursion never mixes
information across the frame axis, so decode scales to any device count
by sharding frames (the MXU lane dimension) and replicating the fused
operand W = [Theta-hat^T ; P] — no collectives at all, the same
"frames-in-lanes" layout as the single-device path, tiled once more
across the mesh.  ``shard_map`` (not plain pjit sharding) is used so the
per-device program is EXACTLY the single-device program: numerics are
bit-identical to one device by construction, and the Pallas kernel path
(``use_kernel=True``) drops in unchanged because each shard calls it on
a local (T, F/ndev, B) block.

Three serving shapes are covered:
  * ``sharded_decode_frames``  — (F, n, beta) independent frames,
    frame axis sharded (the decode_batch path);
  * ``sharded_decode_streams`` — (N, n, beta) long streams, stream axis
    sharded, each device running the tiled window decoder locally (the
    serve/step.py path);
  * ``sharded_decode_time_parallel`` — (F, n, beta) with the TIME axis
    sharded (DESIGN.md §9): each device forms and scans the transfer
    matrices of its own span of tiles, ONE all-gather of per-device
    (S, S) prefix products stitches the spans, and every device recovers
    its survivors/bits locally.  This is the long-single-stream serving
    shape frame-sharding cannot touch: F < n_devices, latency bounded by
    tile + log2(tiles) per device instead of T.

Frame counts that do not divide the device count are zero-LLR padded
(a zero LLR is information-free) and the padding is sliced off.  The
time-sharded path instead REQUIRES the step count to divide evenly:
a zero-LLR tail pad would perturb the final metrics.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.kernel_geometry import pick_transfer_tile
from repro.core.trellis import CodeSpec, build_acs_tables
from repro.core.validate import validate_llrs
from repro.core.viterbi import (
    AcsPrecision,
    TiledDecoderConfig,
    blocks_from_llrs,
    forward_fused,
    init_metric,
    tiled_decode_streams,
    traceback,
)

__all__ = [
    "frame_mesh",
    "engine_dispatch_ready",
    "replan_mesh",
    "sharded_decode_frames",
    "sharded_decode_streams",
    "sharded_decode_time_parallel",
]


def frame_mesh(n_devices: Optional[int] = None, axis: str = "frames") -> Mesh:
    """1-D mesh over the first ``n_devices`` (default: all) devices."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), (axis,))


def engine_dispatch_ready(
    n_frames: int, mesh: Optional[Mesh] = None, axis: str = "frames"
) -> bool:
    """Whether a serving-engine cell batch should dispatch onto the
    sharded frame decoder (DESIGN.md §10): True when the cell's frame
    count fills every device of ``mesh`` without remainder.  Engine
    cells are already padded to frame rungs, so letting
    ``sharded_decode_frames`` zero-LLR-pad a ragged remainder on top
    would double-count padding waste — underfilled cells stay on the
    single-device path instead."""
    mesh = mesh or frame_mesh(axis=axis)
    n_dev = mesh.shape[axis]
    return n_frames >= n_dev and n_frames % n_dev == 0


def replan_mesh(mesh: Mesh, failed_devices) -> Optional[Mesh]:
    """Shrink a 1-D frame mesh onto its surviving devices
    (DESIGN.md §13 failover): drop every device whose ``id`` is in
    ``failed_devices`` and keep the largest power-of-two prefix of the
    survivors — the same largest-power-of-two rule as
    ``runtime.failure.ElasticPlanner`` (engine frame rungs are powers of
    two, so a power-of-two device count keeps ``engine_dispatch_ready``
    divisibility intact).  Returns None when no device survives (the
    engine then degrades sharded cells to the single-device batch
    path)."""
    failed = set(int(d) for d in failed_devices)
    axis = mesh.axis_names[0]
    alive = [d for d in mesh.devices.reshape(-1) if d.id not in failed]
    if not alive:
        return None
    n = 1 << (len(alive).bit_length() - 1)
    return Mesh(np.asarray(alive[:n]), (axis,))


def _pad_to(llrs: jnp.ndarray, multiple: int) -> jnp.ndarray:
    pad = (-llrs.shape[0]) % multiple
    if not pad:
        return llrs
    return jnp.concatenate(
        [llrs, jnp.zeros((pad,) + llrs.shape[1:], llrs.dtype)], axis=0
    )


@functools.lru_cache(maxsize=32)
def _frames_fn(
    spec: CodeSpec,
    rho: int,
    mesh: Mesh,
    axis: str,
    initial_state: Optional[int],
    final_state: Optional[int],
    precision: AcsPrecision,
    use_kernel: bool,
    pack_survivors: bool,
):
    """Jitted shard_map decode, cached so repeat calls (serving loops,
    benchmark iterations) hit the jit cache instead of re-tracing."""
    tables = build_acs_tables(spec, rho)

    def local(llrs_loc):
        blocks = blocks_from_llrs(llrs_loc, rho)
        lam0 = init_metric(llrs_loc.shape[0], spec.n_states, initial_state)
        lam, phis = forward_fused(
            blocks, lam0, tables, precision, use_kernel, pack_survivors
        )
        if final_state is None:
            fs = jnp.argmax(lam, axis=-1).astype(jnp.int32)
        else:
            fs = jnp.full((llrs_loc.shape[0],), final_state, jnp.int32)
        return traceback(phis, fs, tables)

    return jax.jit(
        jax.shard_map(
            local, mesh=mesh, in_specs=P(axis), out_specs=P(axis),
            check_vma=False,
        )
    )


def sharded_decode_frames(
    llrs: jnp.ndarray,
    spec: CodeSpec,
    rho: int = 2,
    mesh: Optional[Mesh] = None,
    axis: str = "frames",
    initial_state: Optional[int] = 0,
    final_state: Optional[int] = None,
    precision: Optional[AcsPrecision] = None,
    use_kernel: bool = False,
    pack_survivors: bool = False,
) -> jnp.ndarray:
    """Batch decode with the frame axis sharded across ``mesh``.

    llrs: (F, n, beta) -> bits (F, n).  Bit-identical to single-device
    decode_frames: each shard runs the identical forward + traceback on
    its local frames.
    """
    mesh = mesh or frame_mesh(axis=axis)
    n_dev = mesh.shape[axis]
    F = llrs.shape[0]
    # §14 host-side hardening: a single NaN entering shard_map poisons
    # every path metric of its shard with no visible failure
    llrs, _ = validate_llrs(llrs, where="sharded")
    llrs = _pad_to(jnp.asarray(llrs), n_dev)
    fn = _frames_fn(
        spec, rho, mesh, axis, initial_state, final_state,
        precision or AcsPrecision(), use_kernel, pack_survivors,
    )
    return fn(llrs)[:F]


@functools.lru_cache(maxsize=32)
def _streams_fn(
    spec: CodeSpec,
    cfg: TiledDecoderConfig,
    mesh: Mesh,
    axis: str,
    precision: AcsPrecision,
    use_kernel: bool,
    pack_survivors: bool,
    one_pass: bool,
    time_tile,
    block_frames,
):
    decode_local = functools.partial(
        tiled_decode_streams,
        spec=spec,
        cfg=cfg,
        precision=precision,
        use_kernel=use_kernel,
        pack_survivors=pack_survivors,
        one_pass=one_pass,
        time_tile=time_tile,
        block_frames=block_frames,
    )
    return jax.jit(
        jax.shard_map(
            decode_local,
            mesh=mesh,
            in_specs=P(axis),
            out_specs=P(axis),
            check_vma=False,
        )
    )


def sharded_decode_streams(
    llrs: jnp.ndarray,
    spec: CodeSpec,
    cfg: Optional[TiledDecoderConfig] = None,
    mesh: Optional[Mesh] = None,
    axis: str = "frames",
    precision: Optional[AcsPrecision] = None,
    use_kernel: bool = False,
    pack_survivors: bool = False,
    one_pass: bool = False,
    time_tile: Optional[int] = None,
    block_frames: Optional[int] = None,
) -> jnp.ndarray:
    """Serve-shape decode: (N, n, beta) streams, stream axis sharded.

    Each device runs the tiled window decoder over its local streams
    (their windows form one frame batch); equals ``tiled_decode_streams``
    on one device.  With
    ``one_pass=True`` every shard's windows run through the time-tiled
    ACS+traceback kernel (DESIGN.md §8) — the per-device program is still
    exactly the single-device program, so numerics stay bit-identical to
    one device by construction.
    """
    mesh = mesh or frame_mesh(axis=axis)
    n_dev = mesh.shape[axis]
    N = llrs.shape[0]
    llrs, _ = validate_llrs(llrs, where="sharded")
    llrs = _pad_to(jnp.asarray(llrs), n_dev)
    fn = _streams_fn(
        spec, cfg or TiledDecoderConfig(), mesh, axis,
        precision or AcsPrecision(), use_kernel, pack_survivors,
        one_pass, time_tile, block_frames,
    )
    return fn(llrs)[:N]


@functools.lru_cache(maxsize=32)
def _time_parallel_fn(
    spec: CodeSpec,
    rho: int,
    mesh: Mesh,
    axis: str,
    transfer_tile: int,
    initial_state: Optional[int],
    final_state: Optional[int],
    precision: AcsPrecision,
    use_kernel: bool,
    pack_survivors: bool,
):
    from repro.core import timeparallel as tp

    tables = build_acs_tables(spec, rho)
    n_dev = mesh.shape[axis]
    S = spec.n_states

    def compose(a, b):
        return tp.tropical_matmul(a, b, precision.matmul_dtype)

    def local(blocks_loc):  # (T'/n_dev, F, B) — this device's time span
        t_loc, F, B = blocks_loc.shape
        n_loc = t_loc // transfer_tile
        idx = jax.lax.axis_index(axis)
        eye = jnp.broadcast_to(tp.tropical_identity(S), (F, S, S))
        lam0 = init_metric(F, S, initial_state)

        # -- local formation + prefix scan, then ONE all-gather of the
        # per-device (F, S, S) span products stitches the spans --------
        m = tp.transfer_matrices(
            blocks_loc, tables, precision, transfer_tile,
            use_kernel=use_kernel,
        )
        prefix = jax.lax.associative_scan(compose, m, axis=0)
        tots = jax.lax.all_gather(prefix[-1], axis)  # (n_dev, F, S, S)
        # exclusive prefix over devices, replicated fold (n_dev is tiny)
        acc = eye
        for d in range(n_dev - 1):
            acc = jnp.where(d < idx, compose(acc, tots[d]), acc)
        v0 = jnp.max(lam0[:, :, None] + acc, axis=-2)  # device entry (F, S)
        entry = tp.entry_from_prefix(prefix, v0)  # (n_loc, F, S)

        # -- local recovery: every tile re-runs the fused ACS from its
        # exact entry metric, tiles folded into the lane axis ----------
        tiles = tp.tiled_blocks(blocks_loc, transfer_tile)
        lam_fin, phis = forward_fused(
            tiles.reshape(transfer_tile, n_loc * F, B),
            entry.reshape(n_loc * F, S),
            tables, precision, use_kernel, pack_survivors,
        )
        lam_fin = lam_fin.reshape(n_loc, F, S)
        lam_ends = jax.lax.all_gather(lam_fin[-1], axis)  # (n_dev, F, S)
        if final_state is None:
            fs = jnp.argmax(lam_ends[-1], axis=-1).astype(jnp.int32)
        else:
            fs = jnp.full((F,), final_state, jnp.int32)

        # -- boundary states: local suffix scan x device-suffix fold ---
        suffix = jax.lax.associative_scan(
            lambda a, b: compose(b, a), m, axis=0, reverse=True
        )
        acc2 = eye
        for d in range(n_dev - 1, 0, -1):
            acc2 = jnp.where(d > idx, compose(tots[d], acc2), acc2)
        w_end = jnp.take_along_axis(
            acc2, fs[:, None, None].astype(jnp.int32).repeat(S, 1), axis=-1
        )[..., 0]  # (F, S): best s-at-device-end -> fs
        v = jnp.max(suffix + w_end[None, :, None, :], axis=-1)
        starts = jnp.argmax(entry + v, axis=-1).astype(jnp.int32)
        starts0 = jax.lax.all_gather(starts[0], axis)  # (n_dev, F)
        nxt = jnp.take(
            starts0, jnp.minimum(idx + 1, n_dev - 1), axis=0
        )
        dev_exit = jnp.where(idx == n_dev - 1, fs, nxt)
        exits = jnp.concatenate([starts[1:], dev_exit[None]], axis=0)

        bits = traceback(phis, exits.reshape(n_loc * F), tables)
        return bits.reshape(n_loc, F, transfer_tile * rho).transpose(
            1, 0, 2
        ).reshape(F, t_loc * rho)

    return jax.jit(
        jax.shard_map(
            local,
            mesh=mesh,
            in_specs=P(axis),
            out_specs=P(None, axis),
            check_vma=False,
        )
    )


def sharded_decode_time_parallel(
    llrs: jnp.ndarray,
    spec: CodeSpec,
    rho: int = 2,
    mesh: Optional[Mesh] = None,
    axis: str = "tiles",
    initial_state: Optional[int] = None,
    final_state: Optional[int] = None,
    precision: Optional[AcsPrecision] = None,
    transfer_tile: Optional[int] = None,
    use_kernel: bool = False,
    pack_survivors: bool = False,
) -> jnp.ndarray:
    """Time-sharded decode (DESIGN.md §9): llrs (F, n, beta) -> (F, n)
    with the transfer-matrix TILE axis spread over ``mesh``.

    Each device runs formation + associative scan + recovery on its own
    contiguous span; the only cross-device traffic is one all-gather of
    the per-device (S, S) span products (plus two vector-sized gathers
    for the final metric and boundary states).  Bits equal the
    single-device time-parallel path, which equals the sequential scan
    — the same exactness story, now with T sharded.  n must put a whole
    number of tiles on every device.
    """
    mesh = mesh or frame_mesh(axis=axis)
    n_dev = mesh.shape[axis]
    llrs = jnp.asarray(llrs)
    F, n, _ = llrs.shape
    blocks = blocks_from_llrs(llrs, rho)
    t_steps = blocks.shape[0]
    if t_steps % n_dev:
        raise ValueError(
            f"T'={t_steps} steps not divisible by {n_dev} devices — a "
            "zero-LLR tail pad would perturb the final metrics"
        )
    tile = pick_transfer_tile(t_steps // n_dev, transfer_tile)
    fn = _time_parallel_fn(
        spec, rho, mesh, axis, tile, initial_state, final_state,
        precision or AcsPrecision(), use_kernel, pack_survivors,
    )
    return fn(blocks)
