"""Unified decoder front door (DESIGN.md §6).

``ViterbiDecoder`` owns the precompiled fused-ACS tables, the precision
policy and the kernel/XLA backend choice, and exposes every decode shape
the service needs from one object:

  * ``decode_batch``         — one-shot decode of independent frames
    (the paper's §IX workload, previously ``decode_frames``);
  * ``decode_stream_tiled``  — overlapping-window stream decode (paper
    §III tiling, previously ``tiled_decode_stream``): latency-optimal,
    but every window re-runs ACS on ``2*overlap`` warmup stages;
  * ``init_stream_state`` / ``decode_chunk`` / ``flush_stream`` —
    **stateful chunked streaming**: path metrics and a decision-depth
    survivor ring buffer are carried across chunks, so arbitrarily long
    streams decode incrementally with ZERO redundant ACS work (the
    tensor-core hot loop touches every stage exactly once) and emit
    delayed bit decisions that are bit-exact with full-sequence decode
    beyond the decision depth;
  * ``decode_sharded``       — the frame axis spread over every device
    via ``shard_map`` (repro.distributed.decoder): frames are
    embarrassingly parallel, W stays replicated.

The streaming mode is the classic decision-delay (truncated-traceback)
Viterbi: after consuming chunk stages [pos, pos+T), the decoder traces
back from the argmax state at the chunk front through the ring buffer
and commits the decisions that are now >= ``decision_depth`` stages old.
For k=7 codes a depth of a few hundred stages already makes survivor
paths merge with overwhelming probability; the default (5120 stages,
paper's "~5K" guidance) makes disagreement with full-sequence decode
unobservable at any operating SNR.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.metrics import default_registry
from repro.obs.trace import NullRecorder

from .trellis import AcsTables, CodeSpec, build_acs_tables
from .validate import (
    InvalidInputError,
    RenormGuard,
    batch_headroom_check,
    validate_llrs,
)
from .viterbi import (
    AcsPrecision,
    TiledDecoderConfig,
    blocks_from_llrs,
    decode_frames,
    forward_fused,
    init_metric,
    tiled_decode_streams,
    traceback,
)

from .kernel_geometry import (  # pallas-free §8/§9 geometry rules
    DEFAULT_BLOCK_FRAMES,
    one_pass_time_tile,
    ring_auto_packed,
    ring_dtype,
    ring_words,
    time_parallel_plan,
)

__all__ = [
    "StreamState",
    "ViterbiDecoder",
    "DEFAULT_DECISION_DEPTH",
    "InvalidInputError",  # re-export: the front door's typed rejection
]

# ~5K stages of decision delay (DESIGN.md §6): survivor merge is certain
# for any constraint length we serve, at ~decision_depth*S bytes of state.
DEFAULT_DECISION_DEPTH = 5120


def _host_arrays(xs) -> Tuple[int, int]:
    """(count, bytes) of the arrays in ``xs`` that live on the host:
    each is one host-to-device copy when handed to ``jnp``."""
    host = [np.asarray(x) for x in xs if not isinstance(x, jax.Array)]
    return len(host), sum(x.nbytes for x in host)


@dataclasses.dataclass(frozen=True)
class StreamState:
    """Carry of the chunked streaming decoder.

    lam  : (F, S) path metrics at the current stream front.
    hist : (D, F, S) int8 survivor ring (or (D, F, S//16) int32 packed),
           chronological — hist[i] is radix step ``pos - D + i``; entries
           for negative steps are zero filler, never used for committed
           decisions (the warmup region is sliced off host-side).
    pos  : host-side count of radix steps consumed so far.  Kept out of
           the jitted carry on purpose: chunk shapes are static, only the
           number of *valid* emitted bits depends on pos, and that slice
           happens outside jit.
    """

    lam: jnp.ndarray
    hist: jnp.ndarray
    pos: int

    @property
    def depth_steps(self) -> int:
        return self.hist.shape[0]

    @property
    def n_frames(self) -> int:
        return self.lam.shape[0]


@functools.partial(
    jax.jit,
    static_argnames=("tables", "precision", "use_kernel", "pack_survivors"),
)
def _chunk_step(
    hist: jnp.ndarray,
    lam: jnp.ndarray,
    blocks: jnp.ndarray,
    tables: AcsTables,
    precision: AcsPrecision,
    use_kernel: bool,
    pack_survivors: bool,
):
    """One streaming chunk: T new ACS steps + one delayed traceback.

    Returns (new_hist, new_lam, bits) with bits (F, T*rho) — the decisions
    for the T OLDEST steps in the ring window [pos-D, pos+T), i.e. steps
    [pos-D, pos+T-D), each committed with >= D stages of lookahead.
    """
    lam2, phis = forward_fused(
        blocks, lam, tables, precision, use_kernel, pack_survivors
    )
    full = jnp.concatenate([hist, phis], axis=0)  # (D+T, F, S)
    fs = jnp.argmax(lam2, axis=-1).astype(jnp.int32)
    bits = traceback(full, fs, tables)  # (F, (D+T)*rho)
    T = phis.shape[0]
    out = bits[:, : T * tables.rho]
    return full[full.shape[0] - hist.shape[0]:], lam2, out


@functools.partial(
    jax.jit,
    static_argnames=(
        "tables", "precision", "time_tile", "block_frames", "pack_survivors",
    ),
)
def _chunk_step_fused(
    hist: jnp.ndarray,
    lam: jnp.ndarray,
    blocks: jnp.ndarray,
    tables: AcsTables,
    precision: AcsPrecision,
    time_tile: int,
    block_frames: int,
    pack_survivors: bool,
):
    """``_chunk_step`` fused into the one-pass kernel (DESIGN.md §8): the
    survivor window stays in a VMEM ring and the delayed traceback runs
    inside the kernel, one commit per time tile instead of one per chunk.
    Same contract: (new_hist, new_lam, bits (F, T*rho)) for the T oldest
    steps of the window, each committed with >= D steps of lookahead."""
    from repro.kernels import ops as kernel_ops

    bits, lam2, hist2 = kernel_ops.viterbi_decode_fused(
        blocks,
        lam,
        hist,
        tables,
        precision,
        time_tile=time_tile,
        block_frames=block_frames,
        pack_survivors=pack_survivors,
    )
    return hist2, lam2, bits.T.astype(jnp.int32)


def _window_valid(pos: int, t_steps: int, depth_steps: int) -> int:
    """Number of the chunk window's T oldest steps that are genuinely
    emittable at stream position ``pos`` — the single emission rule
    shared by ``decode_chunk`` and the multi-session fused dispatch
    (``decode_chunk_multi``, DESIGN.md §10): the window covers steps
    [pos-D, pos+T-D); steps before the stream start are warmup filler."""
    return max(0, pos + t_steps - depth_steps) - max(0, pos - depth_steps)


@jax.jit
def _split_frames(lam: jnp.ndarray, hist: jnp.ndarray):
    """One-frame pieces of a fused group's carries, in ONE program
    (DESIGN.md §10): (lam (F, S), hist (D, F, W)) -> F pieces of each.
    Keyed on the shapes alone, so on the group's total frame count (the
    engine's rung), never on how many sessions share it."""
    F = lam.shape[0]
    return jnp.split(lam, F), jnp.split(hist, F, axis=1)


class GroupBits:
    """A fused group's window bits (F, n), on the device until the first
    ``host()`` call copies the whole array to the host, once."""

    __slots__ = ("device", "_host")

    def __init__(self, device):
        self.device = device
        self._host = None

    @property
    def on_host(self) -> bool:
        return self._host is not None

    def host(self) -> np.ndarray:
        if self._host is None:
            self._host = np.asarray(self.device)
        return self._host


class BitRows:
    """Rows [off, off + f) of a group's bits from column ``start``: one
    session's output of ``decode_chunk_multi``.  Array-like of shape
    (f, n - start); ``np.asarray`` copies the group to the host on the
    first row asked for (``GroupBits.host``) and returns a view of that
    copy, so every row of the group shares one host buffer."""

    __slots__ = ("bits", "off", "f", "start")

    def __init__(self, bits: GroupBits, off: int, f: int, start: int):
        self.bits, self.off, self.f, self.start = bits, off, f, start

    @property
    def shape(self) -> Tuple[int, int]:
        return self.f, self.bits.device.shape[1] - self.start

    def __array__(self, dtype=None, copy=None):
        rows = self.bits.host()[self.off : self.off + self.f, self.start:]
        if copy:
            return np.array(rows, dtype=dtype)
        return np.asarray(rows, dtype=dtype)


def to_host(outs) -> Tuple[list, int, int]:
    """``np.asarray`` of each of ``outs`` (``BitRows``, device arrays or
    host arrays): (host arrays, d2h_arrays, d2h_bytes), the device
    buffers this call copied to the host and their bytes — one per
    session group, however many of its rows ``outs`` holds."""
    n = nbytes = 0
    host = []
    for o in outs:
        src = o.bits if isinstance(o, BitRows) else o
        if isinstance(src, GroupBits):
            if not src.on_host:
                n, nbytes = n + 1, nbytes + src.device.nbytes
        elif isinstance(src, jax.Array):
            n, nbytes = n + 1, nbytes + src.nbytes
        host.append(np.asarray(o))
    return host, n, nbytes


@functools.partial(jax.jit, static_argnames=("tables", "final_state"))
def _flush_step(
    hist: jnp.ndarray,
    lam: jnp.ndarray,
    tables: AcsTables,
    final_state: Optional[int],
):
    """Commit the last D steps still in the ring (end of stream)."""
    if final_state is None:
        fs = jnp.argmax(lam, axis=-1).astype(jnp.int32)
    else:
        fs = jnp.full((lam.shape[0],), final_state, jnp.int32)
    return traceback(hist, fs, tables)  # (F, D*rho)


class ViterbiDecoder:
    """One front door for every decode scenario (DESIGN.md §6).

    Construct once per (code, radix, precision, backend) — the fused-ACS
    tables are built eagerly and every entry point reuses the same jitted
    computations (tables are hashed by identity, so one decoder instance
    never re-traces for a second call of the same shape).

    ``recorder`` (an ``obs.SpanRecorder``; default the no-op
    ``NullRecorder``) receives the host-side ``decoder.*`` spans of
    ``decode_batch`` and ``decode_chunk_multi`` (DESIGN.md §12);
    ``registry`` receives ``decoder_dispatch_total{path}`` — None
    means the process default, ``obs.default_registry()``, looked up at
    each dispatch so ``set_default_registry`` keeps working.
    """

    def __init__(
        self,
        spec: CodeSpec,
        rho: int = 2,
        precision: Optional[AcsPrecision] = None,
        use_kernel: bool = False,
        pack_survivors: bool = False,
        decision_depth: int = DEFAULT_DECISION_DEPTH,
        puncture=None,  # codes.PuncturePattern | None
        termination: str = "zero",
        one_pass: Optional[bool] = None,
        time_tile: Optional[int] = None,
        block_frames: Optional[int] = None,
        time_parallel: Optional[bool] = None,
        transfer_tile: Optional[int] = None,
        validate_inputs: bool = True,
        sanitize: bool = False,
        recorder=None,
        registry=None,
    ):
        if decision_depth % rho:
            raise ValueError(
                f"decision_depth={decision_depth} not divisible by rho={rho}"
            )
        if termination not in ("zero", "tailbiting"):
            raise ValueError(f"unknown termination {termination!r}")
        if puncture is not None and puncture.beta != spec.beta:
            raise ValueError(
                f"puncture beta={puncture.beta} != code beta={spec.beta}"
            )
        self.spec = spec
        self.rho = rho
        self.tables = build_acs_tables(spec, rho)
        self.precision = precision or AcsPrecision()
        self.use_kernel = use_kernel
        self.pack_survivors = pack_survivors
        self.puncture = puncture
        self.termination = termination
        # one-pass streaming (DESIGN.md §8): default on whenever the
        # Pallas backend is on — the streaming entry points then keep
        # survivors in the kernel's VMEM ring instead of round-tripping
        # the (T, F, S) phi tensor through HBM.  The exact batch and
        # tail-biting paths always stay two-pass (WAVA needs full phi).
        self.one_pass = use_kernel if one_pass is None else bool(one_pass)
        self.time_tile = time_tile
        self.block_frames = block_frames
        # time-parallel decode (DESIGN.md §9): None = auto-select per
        # call shape via kernel_geometry.time_parallel_plan (engages
        # only when frames-only batching underfills the device)
        self.time_parallel = time_parallel
        self.transfer_tile = transfer_tile
        # the survivors a kernel writes are ALWAYS bit-packed when the
        # state count allows it: the streaming ring (the paper's 32-bit
        # output compaction is part of the §8 ring design) and the
        # two-pass kernel's survivor tensor in HBM on the batch path (16
        # words per step at 256 states, not 256 bytes).  The tail-biting
        # and XLA paths keep packing opt-in via pack_survivors.
        self.ring_packed = (
            ring_auto_packed(spec.n_states, pack_survivors)
            if use_kernel or self.one_pass else pack_survivors
        )
        if puncture is not None:
            # erasure-aware depth accounting (DESIGN.md §7): punctured
            # stages carry fewer real LLRs, so survivor merge takes
            # ~expansion× more stages; stretch the decision delay to
            # keep the same information horizon, rounded to a rho grid.
            decision_depth = int(
                -(-int(decision_depth * puncture.expansion) // rho) * rho
            )
        self.decision_depth = decision_depth
        # §14 data-plane hardening: validate every host-side entry point
        # (strict raise, or clamp-and-count with sanitize=True), and for
        # no-renorm precisions attach the renorm-cadence guard — the
        # carry drifts monotonically without the per-step max
        # subtraction, and narrow carries (bf16) absorb increments long
        # before they wrap.  The guard observes the host-visible carry
        # between streaming chunks and renormalizes (shift-invariant for
        # traceback) before headroom runs out.
        self.validate_inputs = validate_inputs
        self.sanitize = sanitize
        self.sanitized_total = 0
        self.renorm_guard: Optional[RenormGuard] = (
            RenormGuard.for_precision(self.precision)
            if (validate_inputs and not self.precision.renorm) else None
        )
        self.recorder = recorder if recorder is not None else NullRecorder()
        self.registry = registry

    def _count_dispatch(self, path: str) -> None:
        """§12 path-selection counter.  Called at host-side dispatch
        boundaries only — never from inside a jitted function."""
        reg = self.registry if self.registry is not None else (
            default_registry()
        )
        reg.counter(
            "decoder_dispatch_total",
            "ViterbiDecoder dispatches by selected decode path",
        ).inc(1, path=path)

    @classmethod
    def from_standard(
        cls,
        name: str,
        rho: int = 2,
        precision: Optional[AcsPrecision] = None,
        use_kernel: bool = False,
        pack_survivors: bool = False,
        decision_depth: int = DEFAULT_DECISION_DEPTH,
        one_pass: Optional[bool] = None,
        time_tile: Optional[int] = None,
        block_frames: Optional[int] = None,
        time_parallel: Optional[bool] = None,
        transfer_tile: Optional[int] = None,
        validate_inputs: bool = True,
        sanitize: bool = False,
        recorder=None,
        registry=None,
    ) -> "ViterbiDecoder":
        """One front door for every deployed standard (DESIGN.md §7):
        resolves a ``repro.codes.registry`` entry — mother code, puncture
        pattern and termination — into a ready decoder, e.g.
        ``ViterbiDecoder.from_standard("wifi-11a-r34")`` or
        ``ViterbiDecoder.from_standard("lte-tbcc")``."""
        from repro.codes.registry import get_code

        code = get_code(name)
        return cls(
            spec=code.spec,
            rho=rho,
            precision=precision,
            use_kernel=use_kernel,
            pack_survivors=pack_survivors,
            decision_depth=decision_depth,
            puncture=code.puncture,
            termination=code.termination,
            one_pass=one_pass,
            time_tile=time_tile,
            block_frames=block_frames,
            time_parallel=time_parallel,
            transfer_tile=transfer_tile,
            validate_inputs=validate_inputs,
            sanitize=sanitize,
            recorder=recorder,
            registry=registry,
        )

    @classmethod
    def from_config(
        cls,
        vcfg,
        precision: Optional[AcsPrecision] = None,
        use_kernel: bool = False,
        decision_depth: Optional[int] = None,
    ) -> "ViterbiDecoder":
        """Build from a configs.viterbi_k7.ViterbiConfig (the single
        vcfg -> decoder mapping; serve/step.py delegates here).  A config
        naming a registry standard (``vcfg.code``) inherits its puncture
        pattern and termination.  Kernel geometry is not configured: the
        decoder derives it from each call's shape, as ``from_standard``
        does."""
        puncture, termination = None, "zero"
        code_name = getattr(vcfg, "code", None)
        if code_name:
            from repro.codes.registry import get_code

            code = get_code(code_name)
            if code.spec != vcfg.spec:
                raise ValueError(
                    f"config spec {vcfg.spec} != standard {code_name} "
                    f"spec {code.spec}"
                )
            puncture, termination = code.puncture, code.termination
        return cls(
            spec=vcfg.spec,
            rho=vcfg.rho,
            precision=precision or vcfg.precision,
            use_kernel=use_kernel,
            pack_survivors=getattr(vcfg, "pack_survivors", False),
            decision_depth=decision_depth or DEFAULT_DECISION_DEPTH,
            puncture=puncture,
            termination=termination,
            time_parallel=getattr(vcfg, "time_parallel", None),
        )

    # -- §14 input hardening ----------------------------------------------

    def _harden(self, llrs, where: str = "decoder"):
        """Validate (or sanitize) one LLR array at a host-side entry
        point.  Strict mode raises :class:`InvalidInputError` on
        NaN/Inf; ``sanitize=True`` clamps-and-counts instead (the counts
        reach ``decoder_input_sanitized_total`` and
        ``self.sanitized_total``).  No-op for jit tracers and when
        ``validate_inputs=False``."""
        if not self.validate_inputs:
            return llrs
        llrs, n_bad = validate_llrs(
            llrs, sanitize=self.sanitize, where=where
        )
        self.sanitized_total += n_bad
        return llrs

    # -- rate matching ----------------------------------------------------

    def depunctured(self, llrs: jnp.ndarray, stream: bool = False):
        """Re-insert zero-LLR erasures when this decoder is punctured.

        Punctured inputs are the SERIAL kept-LLR stream: (F, Lp) for
        batch entry points, (Lp,) for single-stream ones.  Already
        depunctured (..., n, beta) inputs pass through unchanged, so
        upstream stages may depuncture once themselves.
        """
        llrs = jnp.asarray(llrs)
        shaped_ndim = 2 if stream else 3
        if self.puncture is None or llrs.ndim == shaped_ndim:
            return llrs
        from repro.codes.puncture import depuncture

        return depuncture(llrs, self.puncture)

    # -- batch ------------------------------------------------------------

    def _time_parallel_tile(
        self, n_frames: int, t_steps: int, time_parallel: Optional[bool]
    ) -> Optional[int]:
        """Transfer tile for the §9 time-parallel path on this shape, or
        None to stay sequential — per-call override beats the decoder
        default, then the shared ``time_parallel_plan`` eligibility
        (tile grid + device underfill auto-select)."""
        resolved = (
            self.time_parallel if time_parallel is None else time_parallel
        )
        return time_parallel_plan(
            n_frames, t_steps, self.spec.n_states,
            resolved, self.transfer_tile,
        )

    def decode_batch(
        self,
        llrs: jnp.ndarray,
        initial_state: Optional[int] = 0,
        final_state: Optional[int] = None,
        termination: Optional[str] = None,
        time_parallel: Optional[bool] = None,
    ) -> jnp.ndarray:
        """One-shot decode of independent frames.

        llrs: (F, n, beta), or the serial punctured stream (F, Lp) when
        the decoder carries a puncture pattern (DESIGN.md §7).  With
        ``termination="tailbiting"`` (or a tail-biting standard) the
        frames decode via the wrap-around algorithm and
        initial/final_state are ignored (the boundary state is jointly
        estimated).  n not divisible by rho is zero-LLR padded internally
        (information-free) unless a final-state pin would land on the
        padding.

        ``time_parallel`` (None = decoder default, which defaults to
        auto) decodes via the §9 transfer-matrix associative scan —
        identical bits, O(tile + log2 tiles) sequential depth instead of
        n/rho — when the frame batch underfills the device (small-F /
        large-T serving) or on request.
        """
        term = termination or self.termination
        rec = self.recorder
        with rec.span("decoder.depuncture") as sp:
            if rec.enabled:
                n_h2d, h2d_bytes = _host_arrays([llrs])
                sp.set(h2d_arrays=n_h2d, h2d_bytes=h2d_bytes)
            llrs = self.depunctured(llrs)
        if term == "tailbiting":
            return self.decode_tailbiting(
                llrs, time_parallel=time_parallel
            )[0]
        # both checks read the LLRs back: device round trips
        with rec.span("decoder.validate"):
            llrs = self._harden(llrs)
            F, n, _ = llrs.shape
            if self.validate_inputs and not self.precision.renorm:
                batch_headroom_check(
                    self.precision,
                    -(-n // self.rho),
                    float(jnp.max(jnp.abs(llrs))) if n else 0.0,
                    self.rho,
                    llrs.shape[2],
                )
        with rec.span("decoder.launch") as sp:
            pad = (-n) % self.rho
            if pad:
                if final_state is not None:
                    raise ValueError(
                        f"final_state requires n divisible by "
                        f"rho={self.rho}; got n={n} (the pin would land "
                        f"on padded stages)"
                    )
                llrs = jnp.pad(llrs, ((0, 0), (0, pad), (0, 0)))
            tp_tile = self._time_parallel_tile(
                F, (n + pad) // self.rho, time_parallel
            )
            self._count_dispatch(
                "time_parallel" if tp_tile is not None else "batch"
            )
            if rec.enabled:
                self._tag_launch(sp, F, (n + pad) // self.rho,
                                 tp_tile is None)
            if tp_tile is not None:
                from .timeparallel import decode_time_parallel

                out = decode_time_parallel(
                    llrs,
                    self.spec,
                    rho=self.rho,
                    initial_state=initial_state,
                    final_state=final_state,
                    precision=self.precision,
                    transfer_tile=tp_tile,
                    use_kernel=self.use_kernel,
                    pack_survivors=self.pack_survivors,
                )
            else:
                out = decode_frames(
                    llrs,
                    self.spec,
                    rho=self.rho,
                    initial_state=initial_state,
                    final_state=final_state,
                    precision=self.precision,
                    use_kernel=self.use_kernel,
                    pack_survivors=self.ring_packed,
                )
            return out[:, :n] if pad else out

    def _tag_launch(self, span, n_frames: int, steps: int,
                    sequential: bool) -> None:
        """A frame batch's ``decoder.launch`` attributes: the trellis
        size, the radix steps after padding, and, where the two-pass
        kernel runs, the time tile it takes (its ``forward_plan``)."""
        span.set(n_states=self.spec.n_states, steps=steps)
        if sequential and self.use_kernel:
            from repro.kernels.viterbi_acs import forward_plan

            span.set(fwd_time_tile=forward_plan(
                n_frames, steps, self.spec.n_states, self.tables.llr_block,
                self.tables.n_slots, self.ring_packed,
                self.precision.matmul_dtype)[2])

    def decode_tailbiting(
        self,
        llrs: jnp.ndarray,
        max_iters: Optional[int] = None,
        time_parallel: Optional[bool] = None,
    ):
        """Wrap-around (WAVA) decode of tail-biting frames (DESIGN.md §7).

        llrs as in ``decode_batch``.  Returns (bits (F, n), converged
        (F,) bool).  Frame lengths not divisible by rho fall back to
        radix-2 tables — the circular trellis cannot be padded.  With
        ``time_parallel`` each WAVA circulation runs the §9 scan.
        """
        from repro.codes.tailbiting import DEFAULT_WAVA_ITERS, wava_decode

        llrs = self._harden(self.depunctured(llrs))
        F, n = llrs.shape[0], llrs.shape[1]
        tables = (
            self.tables if n % self.rho == 0
            else build_acs_tables(self.spec, 1)
        )
        tp_tile = self._time_parallel_tile(
            F, n // tables.rho, time_parallel
        )
        self._count_dispatch("wava")
        return wava_decode(
            llrs,
            tables,
            precision=self.precision,
            use_kernel=self.use_kernel,
            pack_survivors=self.pack_survivors,
            max_iters=max_iters or DEFAULT_WAVA_ITERS,
            time_parallel=tp_tile is not None,
            transfer_tile=tp_tile,
        )

    # -- soft output (DESIGN.md §15) --------------------------------------

    def decode_soft(
        self,
        llrs: jnp.ndarray,
        output: str = "llr",
        n_list: int = 4,
        initial_state: Optional[int] = 0,
        final_state: Optional[int] = None,
        termination: Optional[str] = None,
    ):
        """Soft-output decode (DESIGN.md §15).

        llrs as in ``decode_batch`` (punctured serial streams accepted —
        the re-inserted zero-LLR erasures are information-free in the
        log semiring too).  ``output`` selects:

          * ``"llr"``  — (F, n) f32 per-bit BCJR LLRs (positive = bit 0,
            the channel-LLR convention);
          * ``"bits"`` — (F, n) int32 MAP-per-bit hard decisions
            (``llr < 0``; may legitimately differ from the ML-sequence
            ``decode_batch`` decisions near 0 dB);
          * ``"list"`` — (bits (F, L, n) int32, metrics (F, L) f32)
            top-``n_list`` list-Viterbi paths, metric-sorted and
            distinct; L=1 is bit-exact with ``decode_batch``.

        Tail-biting frames route to the exact circular BCJR
        (llr/bits) or the WAVA list loop (list); initial/final_state
        are then ignored, like ``decode_batch``.
        """
        if output not in ("llr", "bits", "list"):
            raise ValueError(
                f"output must be 'llr', 'bits' or 'list', got {output!r}"
            )
        term = termination or self.termination
        llrs = self._harden(self.depunctured(llrs))
        F, n, _ = llrs.shape
        if self.validate_inputs and not self.precision.renorm:
            batch_headroom_check(
                self.precision,
                -(-n // self.rho),
                float(jnp.max(jnp.abs(llrs))) if n else 0.0,
                self.rho,
                llrs.shape[2],
            )
        if term == "tailbiting":
            tables = (
                self.tables if n % self.rho == 0
                else build_acs_tables(self.spec, 1)
            )
            if output == "list":
                from .soft import wava_list_decode

                self._count_dispatch("soft_list")
                bits, metrics, _ = wava_list_decode(
                    llrs, tables, n_list, self.precision
                )
                return bits, metrics
            from .soft import bcjr_circular_llrs

            self._count_dispatch("soft")
            out = bcjr_circular_llrs(
                llrs, tables, self.precision, use_kernel=self.use_kernel
            )
            return out if output == "llr" else (out < 0).astype(jnp.int32)
        pad = (-n) % self.rho
        if pad:
            if final_state is not None:
                raise ValueError(
                    f"final_state requires n divisible by rho={self.rho}; "
                    f"got n={n} (the pin would land on padded stages)"
                )
            llrs = jnp.pad(llrs, ((0, 0), (0, pad), (0, 0)))
        if output == "list":
            from .soft import list_decode

            self._count_dispatch("soft_list")
            bits, metrics = list_decode(
                llrs,
                self.spec,
                n_list=n_list,
                rho=self.rho,
                initial_state=initial_state,
                final_state=final_state,
                precision=self.precision,
            )
            return (bits[:, :, :n] if pad else bits), metrics
        from .soft import bcjr_llrs

        self._count_dispatch("soft")
        out = bcjr_llrs(
            llrs,
            self.spec,
            rho=self.rho,
            initial_state=initial_state,
            final_state=final_state,
            precision=self.precision,
            transfer_tile=self.transfer_tile,
            use_kernel=self.use_kernel,
        )
        out = out[:, :n] if pad else out
        return out if output == "llr" else (out < 0).astype(jnp.int32)

    # -- tiled stream (stateless, latency-optimal) ------------------------

    def default_tiled_config(
        self, base: Optional[TiledDecoderConfig] = None
    ) -> TiledDecoderConfig:
        """The tiling this decoder would pick by itself: ``base`` (or the
        library default), with the overlap stretched by the puncture
        expansion (erasure-aware accounting, DESIGN.md §7) and kept on
        the rho grid."""
        base = base or TiledDecoderConfig(rho=self.rho)
        if self.puncture is None:
            return base
        v = int(base.overlap * self.puncture.expansion)
        v += (-v) % self.rho  # keep the window on the rho grid
        return TiledDecoderConfig(
            frame_len=base.frame_len, overlap=v, rho=self.rho
        )

    def decode_stream_tiled(
        self,
        llrs: jnp.ndarray,
        cfg: Optional[TiledDecoderConfig] = None,
    ) -> jnp.ndarray:
        """Overlapping-window decode of one stream (paper §III): (n, beta),
        or the serial punctured (Lp,) stream for a punctured decoder.
        ``decode_streams_tiled`` on a batch of one."""
        return self.decode_streams_tiled(jnp.asarray(llrs)[None], cfg)[0]

    def decode_streams_tiled(
        self,
        llrs: jnp.ndarray,
        cfg: Optional[TiledDecoderConfig] = None,
    ) -> jnp.ndarray:
        """Overlapping-window decode of N streams (paper §III): (N, n,
        beta), or the serial punctured (N, Lp) streams for a punctured
        decoder -> (N, n).  The windows of all streams decode as one
        frame batch.

        When no cfg is given, a punctured decoder stretches the default
        overlap by the puncture expansion (erasure-aware accounting,
        DESIGN.md §7): depunctured stages carry fewer real LLRs, so the
        same survivor-merge confidence needs proportionally more stages.
        """
        if self.termination == "tailbiting":
            raise ValueError(
                "tiled stream decode assumes an open (non-circular) "
                "trellis; use decode_batch/decode_tailbiting per frame"
            )
        llrs = self._harden(self.depunctured(llrs))
        cfg = cfg or self.default_tiled_config()
        if cfg.rho != self.rho:
            raise ValueError(f"cfg.rho={cfg.rho} != decoder rho={self.rho}")
        self._count_dispatch("tiled")
        return tiled_decode_streams(
            llrs,
            self.spec,
            cfg,
            precision=self.precision,
            use_kernel=self.use_kernel,
            pack_survivors=self.pack_survivors,
            one_pass=self.one_pass,
            time_tile=self.time_tile,
            block_frames=self.block_frames,
            time_parallel=self.time_parallel,
            transfer_tile=self.transfer_tile,
        )

    # -- stateful chunked streaming (throughput-optimal) ------------------

    def init_stream_state(
        self,
        n_frames: int,
        initial_state: Optional[int] = None,
        decision_depth: Optional[int] = None,
    ) -> StreamState:
        """Fresh state for F parallel streams decoded chunk by chunk."""
        depth = decision_depth or self.decision_depth
        if depth % self.rho:
            raise ValueError(
                f"decision_depth={depth} not divisible by rho={self.rho}"
            )
        d_steps = depth // self.rho
        S = self.spec.n_states
        # lam stays f32 in the state (forward_fused casts to carry_dtype
        # internally and returns f32) so the jitted chunk signature is
        # stable across chunks for every precision policy
        lam = init_metric(n_frames, S, initial_state)
        hist = jnp.zeros(
            (d_steps, n_frames, ring_words(S, self.ring_packed)),
            ring_dtype(self.ring_packed),
        )
        return StreamState(lam=lam, hist=hist, pos=0)

    def _one_pass_tile(self, t_steps: int, d_steps: int) -> Optional[int]:
        """Time tile for the one-pass kernel on a (t_steps, d_steps)
        chunk, or None when the chunk should take the two-pass path —
        the shared ``one_pass_time_tile`` eligibility (same guard as the
        tiled window path): no usable common tile grid (e.g. a ragged
        remainder chunk coprime to the depth), a survivor ring beyond
        the VMEM budget (DESIGN.md §8 table), or unpackable packing."""
        if not self.one_pass:
            return None
        return one_pass_time_tile(
            d_steps,
            t_steps,
            self.spec.n_states,
            self.ring_packed,
            self.time_tile,
            self.block_frames,
            self.tables.llr_block,
            self.tables.n_slots,
            self.precision.matmul_dtype,
        )

    def decode_chunk(
        self, state: StreamState, llrs: jnp.ndarray
    ) -> Tuple[StreamState, jnp.ndarray]:
        """Consume one LLR chunk, emit the decisions that became final.

        llrs: (F, c, beta) with c divisible by rho.  Returns
        (new_state, bits) where bits is (F, m*rho) for the m chunk steps
        whose decisions now have >= decision_depth stages of lookahead —
        empty (F, 0) during warmup, (F, c) once pos >= decision_depth.
        Across decode_chunk calls plus flush_stream, every input stage is
        emitted exactly once, in order.

        With ``one_pass`` (default when ``use_kernel``) the chunk runs
        through the time-tiled kernel (DESIGN.md §8): the survivor window
        lives in a VMEM ring and the delayed traceback happens in-kernel,
        one commit per time tile — every decision still carries >= D
        stages of lookahead, so the full/streaming agreement guarantee is
        unchanged, and phi never touches HBM.
        """
        llrs = self._harden(llrs, where="stream")
        F, c, _ = llrs.shape
        if F != state.n_frames:
            raise ValueError(f"state has {state.n_frames} frames, got {F}")
        blocks = blocks_from_llrs(jnp.asarray(llrs), self.rho)
        hist, lam, bits = self._dispatch_chunk(state.hist, state.lam, blocks)
        T = c // self.rho
        lam = self._guard_carry(lam, state.pos + T, T)
        n_valid = _window_valid(state.pos, T, state.depth_steps)
        out = bits[:, (T - n_valid) * self.rho:] if n_valid else bits[:, :0]
        return StreamState(lam=lam, hist=hist, pos=state.pos + T), out

    def _guard_carry(self, lam, pos: int, t_chunk: int):
        """§14 renorm-cadence guard hook: between chunks the carry is
        host-visible, so for no-renorm precisions observe it on the
        guard's cadence and renormalize (per-frame max subtraction —
        shift-invariant for argmax/traceback) before the carry dtype
        runs out of headroom.  Inert for renorm=True precisions."""
        guard = self.renorm_guard
        if guard is None or not guard.due(pos, t_chunk):
            return lam
        lam, _ = guard.observe(lam, t_chunk=t_chunk)
        return lam

    def _dispatch_chunk(self, hist, lam, blocks, span=None):
        """One chunk window of ACS + delayed traceback on raw carries:
        (hist, lam, blocks) -> (hist', lam', window bits (F, T*rho)) for
        the T OLDEST window steps.  Picks the one-pass kernel or the
        two-pass XLA step by the shared §8 eligibility rule — the single
        dispatch point under ``decode_chunk`` and the engine's fused
        multi-session step (``decode_chunk_multi``, DESIGN.md §10).
        ``span``, the enclosing ``decoder.launch``, gets the one-pass
        tile and the ring steps walked per ACS step."""
        tt = self._one_pass_tile(blocks.shape[0], hist.shape[0])
        self._count_dispatch("chunk_one_pass" if tt else "chunk_two_pass")
        if tt:
            if span is not None and self.recorder.enabled:
                span.set(time_tile=tt,
                         walk_per_step=(hist.shape[0] + tt) // tt)
            return _chunk_step_fused(
                hist,
                lam,
                blocks,
                self.tables,
                self.precision,
                tt,
                self.block_frames or DEFAULT_BLOCK_FRAMES,
                self.ring_packed,
            )
        return _chunk_step(
            hist,
            lam,
            blocks,
            self.tables,
            self.precision,
            self.use_kernel,
            self.ring_packed,
        )

    def decode_chunk_multi(self, states, chunks):
        """Advance several INDEPENDENT streaming states in one fused
        dispatch (DESIGN.md §10) — the multi-tenant session step.

        ``states`` are StreamStates of this decoder (same decision
        depth); ``chunks`` the matching (f_i, c, beta) LLR chunks, all
        with the same step count c.  The states are stacked along the
        frame axis (the chunks in one host-to-device copy), run through ONE
        ``_dispatch_chunk`` (one jit entry per (depth, total F, c) shape
        — the engine pads total F to a cell rung), and split back by one
        program keyed on the same shape.  Sessions may sit at *different*
        stream positions: the delayed-decision window is sliced per
        state with the same emission rule as ``decode_chunk``, so each
        session's emitted bits are identical to driving it alone.

        Returns (new_states, outs), outs[i] a ``BitRows`` of shape
        (f_i, m_i*rho).  All of them are rows of the group's one bits
        array: the first ``np.asarray`` of any copies it to the host
        once (``to_host`` counts that copy), the others are views.
        """
        if not states:
            return [], []
        if len(states) != len(chunks):
            raise ValueError(
                f"{len(states)} states but {len(chunks)} chunks"
            )
        depths = {s.depth_steps for s in states}
        if len(depths) != 1:
            raise ValueError(f"mixed decision depths {sorted(depths)}")
        rec = self.recorder
        with rec.span("decoder.stack") as sp:
            # the chunks stack on the host and cross to the device as
            # ONE copy
            chunks = [np.asarray(ch) for ch in chunks]
            steps = {ch.shape[1] for ch in chunks}
            if len(steps) != 1:
                raise ValueError(f"mixed chunk lengths {sorted(steps)}")
            for s, ch in zip(states, chunks):
                if ch.shape[0] != s.n_frames:
                    raise ValueError(
                        f"state has {s.n_frames} frames, chunk "
                        f"{ch.shape[0]}"
                    )
            stacked = np.concatenate(chunks, axis=0)
            if rec.enabled:
                sp.set(h2d_arrays=1, h2d_bytes=stacked.nbytes)
            stacked = jnp.asarray(stacked)
            hist = jnp.concatenate([s.hist for s in states], axis=1)
            lam = jnp.concatenate([s.lam for s in states], axis=0)
        with rec.span("decoder.validate"):
            stacked = self._harden(stacked, where="stream")
        with rec.span("decoder.launch") as sp:
            blocks = blocks_from_llrs(stacked, self.rho)
            hist2, lam2, bits = self._dispatch_chunk(hist, lam, blocks, sp)
        T = steps.pop() // self.rho
        D = depths.pop()
        if self.renorm_guard is not None and any(
                self.renorm_guard.due(s.pos + T, T) for s in states):
            lam2, _ = self.renorm_guard.observe(lam2, t_chunk=T)
        new_states, outs, off = [], [], 0
        group_bits = GroupBits(bits)
        with rec.span("decoder.split") as sp:
            # one program splits the group's carries into one-frame
            # pieces; a state of several frames (the engine's pad,
            # multi-frame callers) is sliced alone.  The bits stay whole:
            # each state's output is rows of the group's one host copy,
            # its emission window (short in warm-up) a column offset
            ops = sliced = 0
            if any(s.n_frames == 1 for s in states):
                lams, hists = _split_frames(lam2, hist2)
                ops += 1
            for s in states:
                f = s.n_frames
                if f == 1:
                    lm, h = lams[off], hists[off]
                else:
                    lm = lam2[off : off + f]
                    h = hist2[:, off : off + f]
                    ops += 2
                    sliced += 1
                n_valid = _window_valid(s.pos, T, D)
                outs.append(
                    BitRows(group_bits, off, f, (T - n_valid) * self.rho)
                )
                new_states.append(StreamState(lam=lm, hist=h, pos=s.pos + T))
                off += f
            sp.set(split_ops=ops, sliced=sliced)
        return new_states, outs

    def flush_stream(
        self, state: StreamState, final_state: Optional[int] = None
    ) -> jnp.ndarray:
        """End of stream: commit the decisions still inside the ring.

        Returns (F, min(pos, depth)*rho) bits.  With ``final_state`` the
        traceback is pinned (tail-flushed streams); otherwise it starts
        from the per-frame argmax metric, exactly like decode_batch.
        """
        bits = _flush_step(state.hist, state.lam, self.tables, final_state)
        valid = min(state.pos, state.depth_steps)
        return bits[:, (state.depth_steps - valid) * self.rho:]

    def decode_stream_chunked(
        self,
        llrs: jnp.ndarray,
        chunk_len: int = 4096,
        initial_state: Optional[int] = None,
        final_state: Optional[int] = None,
        decision_depth: Optional[int] = None,
    ) -> jnp.ndarray:
        """Convenience driver: chunk (F, n, beta) streams through the
        stateful path and reassemble the full (F, n) decision array.

        The final chunk is the (smaller) remainder, so at most rho-1
        trailing stages are ever zero-LLR padded (a zero LLR carries no
        information); padded decisions are sliced off.  ``final_state``
        pins the traceback at the true last stage, so it is rejected
        when that stage would sit before padding (n not a multiple of
        rho) — pad or tail-flush the stream to a rho multiple first.

        A punctured decoder also accepts the serial kept-LLR streams
        (F, Lp): erasures are re-inserted up front — the decision depth
        was already stretched by the puncture expansion at construction
        (erasure-aware accounting, DESIGN.md §7) — and the depunctured
        stages flow through the unchanged chunk machinery.
        """
        if self.termination == "tailbiting":
            raise ValueError(
                "chunked streaming assumes an open trellis; tail-biting "
                "frames decode whole via decode_batch/decode_tailbiting"
            )
        llrs = self.depunctured(llrs)
        F, n, beta = llrs.shape
        c = chunk_len - (chunk_len % self.rho) or self.rho
        pad = (-n) % self.rho
        if pad and final_state is not None:
            raise ValueError(
                f"final_state requires n divisible by rho={self.rho}; "
                f"got n={n} (the pin would land on padded stages)"
            )
        state = self.init_stream_state(
            F, initial_state=initial_state, decision_depth=decision_depth
        )
        outs = []
        llrs = jnp.asarray(llrs)
        if pad:
            llrs = jnp.pad(llrs, ((0, 0), (0, pad), (0, 0)))
        for lo in range(0, n, c):
            state, bits = self.decode_chunk(state, llrs[:, lo : lo + c])
            outs.append(bits)
        outs.append(self.flush_stream(state, final_state=final_state))
        return jnp.concatenate(outs, axis=1)[:, :n]

    # -- sharded ----------------------------------------------------------

    def decode_sharded(
        self,
        llrs: jnp.ndarray,
        mesh=None,
        initial_state: Optional[int] = 0,
        final_state: Optional[int] = None,
    ) -> jnp.ndarray:
        """decode_batch with the frame axis sharded over devices
        (DESIGN.md §6; repro.distributed.decoder).  Punctured serial
        inputs are depunctured host-side first (the erasure-filled frames
        shard like any others); tail-biting is not yet sharded."""
        from repro.distributed.decoder import sharded_decode_frames

        if self.termination == "tailbiting":
            raise NotImplementedError(
                "sharded tail-biting decode not implemented; shard "
                "frames manually over decode_tailbiting"
            )
        self._count_dispatch("sharded")
        return sharded_decode_frames(
            self._harden(self.depunctured(llrs)),
            self.spec,
            rho=self.rho,
            mesh=mesh,
            initial_state=initial_state,
            final_state=final_state,
            precision=self.precision,
            use_kernel=self.use_kernel,
            pack_survivors=self.pack_survivors,
        )
