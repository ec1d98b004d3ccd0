"""Device-profile adapter: modeled HBM traffic, flops, trip-count depth
and roofline terms per engine dispatch (DESIGN.md §12).

This is the fold of three accounting layers that already exist into ONE
per-dispatch record the span layer can attach:

  * **static interface bytes** — the ``kernels/traffic.py`` rules: a
    Pallas call's HBM traffic IS its BlockSpec interface; XLA stages are
    charged by the same materialize-at-the-boundary model.  For the §8
    one-pass streaming path the numbers come straight from
    ``traffic.one_pass_stream_traffic(xla="static")``; the other routes
    use the same shape arithmetic inline (phi round-trip for two-pass
    batch, transfer-matrix formation + scan levels for §9, two
    circulations for WAVA).
  * **trip-count depth** — the ``hlocount`` sequential-dependency model
    (DESIGN.md §9): forward + traceback loops for sequential paths,
    ``3*tile + log2(tiles)`` for the time-parallel scan.  The modeled
    depth mirrors what ``hlocount.total_trip_count`` reports on the
    lowered HLO (asserted in tests on a small shape).
  * **roofline terms** — the peaks of the device the process runs on,
    from ``roofline.PEAKS_BY_DEVICE_KIND`` (keyed by ``device_kind``):
    ``t_compute = flops/peak``, ``t_memory = bytes/bw``, the bottleneck
    label, and arithmetic intensity; ``achieved(wall)`` turns a measured
    dispatch wall time into achieved rates, and into fractions of peak
    only where the device has peaks.  A device not in the table (a CPU
    host) gets no roofline terms and no fractions — never another
    chip's numbers.

Everything is pure shape arithmetic; profiles are cached per
(spec, path, cell) so the per-dispatch cost when tracing is enabled is
one dict lookup.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import numpy as np

from repro.core.trellis import CodeSpec, build_acs_tables
from repro.roofline import HW, hw_for_device_kind

__all__ = ["DispatchProfile", "dispatch_profile", "measured_depth"]

# decode routes the adapter can model — the engine's routing-table
# labels (DESIGN.md §10) plus the session (chunk-multi) dispatch
_PATHS = (
    "batch", "time_parallel", "stream", "wava", "sharded", "session"
)


@dataclasses.dataclass(frozen=True)
class DispatchProfile:
    """Modeled cost of one dispatched (code, path, F, T) cell."""

    path: str
    f_cell: int
    n_stages: int
    hbm_bytes: int        # static interface bytes (traffic.py rules)
    flops: float          # fused-ACS matmul model (2*T'*F*S*(B+S) core)
    depth: int            # modeled sequential trip count (hlocount rules)
    hw: Optional[HW] = None  # peaks of the device; None = not in the table

    @property
    def intensity(self) -> float:
        """Arithmetic intensity, flops per HBM byte."""
        return self.flops / self.hbm_bytes if self.hbm_bytes else 0.0

    @property
    def t_compute(self) -> Optional[float]:
        return None if self.hw is None else self.flops / self.hw.peak_flops

    @property
    def t_memory(self) -> Optional[float]:
        return None if self.hw is None else self.hbm_bytes / self.hw.hbm_bw

    @property
    def bottleneck(self) -> Optional[str]:
        if self.hw is None:
            return None
        return "compute" if self.t_compute >= self.t_memory else "memory"

    def span_attrs(self) -> dict:
        """The per-dispatch attributes the engine attaches to its
        dispatch spans (flat, JSON-able); the roofline terms only where
        the device has peaks."""
        attrs = {
            "hbm_bytes_modeled": int(self.hbm_bytes),
            "flops_modeled": float(self.flops),
            "intensity": round(self.intensity, 4),
            "depth_modeled": int(self.depth),
        }
        if self.hw is not None:
            attrs.update(
                t_memory_us=round(self.t_memory * 1e6, 3),
                t_compute_us=round(self.t_compute * 1e6, 3),
                bottleneck=self.bottleneck,
                hw=self.hw.name,
            )
        return attrs

    def achieved(self, wall_s: float, n_devices: int = 1) -> dict:
        """Achieved rates at a measured dispatch wall time, and their
        fractions of peak where the device has peaks."""
        if wall_s <= 0:
            return {}
        dev = max(n_devices, 1)
        bw = self.hbm_bytes / wall_s / dev
        fl = self.flops / wall_s / dev
        out = {"wall_s": wall_s, "achieved_hbm_Bps": bw, "achieved_flops": fl}
        if self.hw is not None:
            out.update(
                achieved_hbm_frac=bw / self.hw.hbm_bw,
                achieved_flops_frac=fl / self.hw.peak_flops,
            )
        return out


def _two_pass_batch_bytes(T, F, S, R, B, W_bytes, mm) -> int:
    """Dense two-pass decode: forward (blocks in, phi out, lam carry) +
    traceback (phi read back, bits out) — the §8 phi round-trip."""
    phi = T * F * W_bytes
    return int(
        T * F * B * mm          # branch-metric blocks in
        + (B + S) * S * R * mm  # fused weight matrix
        + 2 * F * S * 4         # lam in/out
        + 2 * phi               # phi: write forward, read traceback
        + F * T * 2 * 4         # bits out (rho=2 stages, int32)
    )


def _profile_key(dec, path: str, f_cell: int, n_stages: int):
    return (
        dec.spec, dec.rho, path, int(f_cell), int(n_stages),
        dec.decision_depth, bool(dec.ring_packed),
        np.dtype(dec.precision.matmul_dtype).itemsize,
        dec.transfer_tile,
    )


@functools.lru_cache(maxsize=512)
def _profile_cached(
    spec: CodeSpec, rho: int, path: str, f_cell: int, n_stages: int,
    decision_depth: int, packed: bool, mm: int,
    transfer_tile: Optional[int], hw: Optional[HW],
) -> DispatchProfile:
    from repro.core.kernel_geometry import pick_transfer_tile
    from repro.kernels.viterbi_acs import ring_dtype, ring_words

    tables = build_acs_tables(spec, rho)
    S, R, B = tables.n_states, tables.n_slots, tables.llr_block
    T = max(-(-n_stages // rho), 1)
    F = max(int(f_cell), 1)
    D = max(decision_depth // rho, 1)
    W_bytes = ring_words(S, packed) * np.dtype(ring_dtype(packed)).itemsize

    # fused-ACS core: one (B+S)-contraction matmul per step per frame
    acs_flops = 2.0 * T * F * S * (B + S)

    if path in ("stream", "session"):
        # the §8 one-pass accounting, straight from traffic.py's static
        # interface model (survivors never leave VMEM)
        from repro.kernels.traffic import one_pass_stream_traffic

        tr = one_pass_stream_traffic(
            n_stages=max(T * rho, rho), n_frames=F, spec=spec, rho=rho,
            decision_depth=max(D * rho, rho), xla="static",
        )
        bytes_ = int(tr.total)
        depth = T + D  # forward tiles + flush traceback
        flops = acs_flops
    elif path == "time_parallel":
        tile = pick_transfer_tile(T, transfer_tile)
        n_tiles = max(-(-T // tile), 1)
        levels = max(int(math.ceil(math.log2(n_tiles))), 0) if (
            n_tiles > 1
        ) else 0
        tm = n_tiles * S * S * 4  # one f32 transfer matrix per tile
        bytes_ = int(
            T * F * B * mm                  # formation reads the blocks
            + (B + S) * S * R * mm
            + tm                            # formation writes matrices
            + 2 * tm * max(levels, 1)       # scan levels read+write
            + _two_pass_batch_bytes(T, F, S, R, B, W_bytes, mm)  # recovery
        )
        # formation folds the S-entry-state axis into the batch (§9)
        flops = acs_flops * (1.0 + S / max(F, 1)) + (
            2.0 * (S ** 3) * n_tiles * max(levels, 1)
        )
        depth = 3 * tile + levels
    elif path == "wava":
        # two wrap-around circulations of the dense two-pass decode (§7)
        bytes_ = 2 * _two_pass_batch_bytes(T, F, S, R, B, W_bytes, mm)
        flops = 2.0 * acs_flops
        depth = 2 * 2 * T
    else:  # batch / sharded (per-shard program == the dense batch)
        bytes_ = _two_pass_batch_bytes(T, F, S, R, B, W_bytes, mm)
        flops = acs_flops
        depth = 2 * T  # forward scan + traceback scan
    return DispatchProfile(
        path=path, f_cell=F, n_stages=int(n_stages),
        hbm_bytes=int(bytes_), flops=float(flops), depth=int(depth), hw=hw,
    )


def dispatch_profile(dec, path: str, f_cell: int, n_stages: int,
                     hw: Optional[HW] = None) -> DispatchProfile:
    """Profile of dispatching ``f_cell`` frames x ``n_stages`` stages of
    ``dec``'s code down the named route.  ``dec`` is a
    ``core.decoder.ViterbiDecoder``; unknown paths fall back to the
    dense-batch model (the engine's default route).  ``hw`` defaults to
    the peak-table entry of the process's first device (None when that
    device kind is not in the table)."""
    if path not in _PATHS:
        path = "batch"
    if hw is None:
        hw = _device_hw()
    return _profile_cached(*_profile_key(dec, path, f_cell, n_stages), hw)


@functools.lru_cache(maxsize=1)
def _device_hw() -> Optional[HW]:
    import jax

    return hw_for_device_kind(jax.devices()[0].device_kind)


def measured_depth(fn, *avals) -> int:
    """The measured counterpart of ``DispatchProfile.depth``: lower
    ``fn`` at the given abstract values and count loop trips with
    ``hlocount.total_trip_count`` (tests compare model vs measurement
    on small shapes; too slow for per-dispatch use)."""
    import jax

    from repro import hlocount

    text = jax.jit(fn).lower(*avals).compile().as_text()
    return hlocount.total_trip_count(text)
