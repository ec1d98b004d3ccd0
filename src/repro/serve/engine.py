"""Multi-tenant serving engine: dynamic batch assembly over the
ViterbiDecoder front door (DESIGN.md §10).

Everything below the engine (fused one-pass kernel §8, time-parallel
scan §9, sharded streams §6, WAVA §7) decodes dense fixed-shape (F, T)
batches at peak rate; real traffic is the opposite — many concurrent
RAGGED requests, mixed codes, mixed latency/throughput SLOs.  The
``DecodeEngine`` is the layer that turns one into the other:

  * **cell bucketing** — each request is assigned a cell keyed by
    (code, SLO class, length rung): ragged lengths round up a
    power-of-two ladder (``kernel_geometry.pick_cell_length``), frame
    counts round up to a frame rung (``pick_cell_frames``), so the set
    of jitted decode programs stays logarithmic in the length spread.
    Padding is TRAILING ZERO LLRs — information-free stages (the §7
    erasure argument): the argmax-front traceback reaches a true-end
    state attaining the global-max metric, so the decoded prefix is
    bit-identical to decoding the unpadded frame.  Tail-biting cells
    are exact-length (the circular trellis cannot be padded; §7).
  * **batch assembly** — per-cell FIFO queues flush when ``max_batch``
    requests accumulate or the oldest request has waited
    ``max_wait[slo]`` (virtual-clock friendly: every entry point takes
    an explicit ``now``), with queue-depth backpressure past
    ``max_pending``.
  * **SLO -> path routing** (the §10 routing table): tail-biting codes
    -> WAVA; latency-class cells that underfill the device
    (``backend.device_underfill_rows``) -> §9 time-parallel decode;
    throughput-class long cells on a kernel-enabled engine -> the §8
    one-pass streaming path; cells that fill a provided device mesh ->
    §6 sharded frames; everything else -> dense two-pass batch decode.
    Every path is bit-identical to direct ``ViterbiDecoder`` decode
    under the code's framing contract: zero-terminated codes pin the
    INITIAL state to 0 (every frame starts there); frames the client
    declares ``flushed`` (they carry their zero tail) bucket into
    exact-length cells and pin the final end too; undeclared streams
    keep an argmax final end, where the §10 padding lemma holds for
    ragged lengths.  Tail-biting codes run WAVA.  Asserted per registry
    code in ``tests/test_engine.py``; the §11 BER farm gate caught the
    cost of the earlier unpinned (argmax-ends) contract on punctured
    rates.
  * **jit-fn cache** — decode callables are cached per
    (code, path, F rung, length rung); repeated same-cell batches hit
    the cache (and therefore jax's trace cache) instead of recompiling;
    ``stats()["jit_cache"]`` counts hits/misses/entries.
  * **sessions** — chunked-streaming tenants keep their survivor ring +
    metric carry (``StreamState``) in an LRU table; concurrent session
    chunks of one code fuse into ONE ``decode_chunk_multi`` dispatch
    even when sessions sit at different stream positions.  Table
    overflow evicts the least-recently-used session: its pending chunks
    are decoded, the ring is flushed, and the tail is retrievable via
    ``evicted_tail`` — so an evicted session's total output equals
    uninterrupted ``decode_stream_chunked`` on what it consumed.

  * **fault tolerance** (DESIGN.md §13) — every dispatch runs under a
    guard: injected or real faults (device failures, timeouts,
    stragglers past ``dispatch_timeout``, transient compile errors) are
    retried with bounded exponential backoff, then degraded down a
    per-path ladder (sharded -> batch, stream -> XLA chunked -> batch,
    time-parallel -> batch) whose every rung decodes identical bits;
    device failures shrink the mesh onto survivors
    (``distributed.decoder.replan_mesh``, fed by an optional
    ``HeartbeatMonitor``); requests that exhaust the ladder get a TYPED
    error on their ticket — the engine itself never crashes — and
    deadline-stamped requests are shed, not decoded late.  Session
    durability: ``checkpoint_dir`` periodically checkpoints the session
    table (``runtime.checkpoint.save_sessions``, manifest-last), and
    ``restore_sessions`` rebuilds it bit-identically after a crash;
    clients replay the bounded post-checkpoint window.

``launch/serve.py --service engine`` drives a synthetic multi-tenant
mix through this engine (``--chaos``/``--checkpoint-dir`` exercise the
§13 machinery); the on-chip benchmark (``bench/``, cells in
``BENCHMARK.json``) measures it.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import time
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.codes.puncture import depuncture_np
from repro.core.decoder import ViterbiDecoder, to_host
from repro.core.kernel_geometry import (
    ENGINE_MIN_CELL,
    pick_cell_frames,
    pick_cell_length,
    time_parallel_plan,
)
from repro.core.validate import InvalidInputError, validate_llrs
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NullRecorder, SpanRecorder
from repro.runtime.chaos import DeviceFailure, DispatchTimeout, InjectedFault
from repro.runtime.failure import QuarantineRecord, RetryPolicy
from repro.verify.scrub import SdcScrubber

__all__ = [
    "SLO_CLASSES",
    "DEFAULT_MAX_WAIT",
    "DEGRADATION_LADDER",
    "DecodeRequest",
    "Ticket",
    "DecodeEngine",
]

SLO_CLASSES = ("latency", "throughput", "soft")

# max batch-assembly wait per SLO class, seconds (DESIGN.md §10):
# latency-class cells flush an order of magnitude sooner than
# throughput-class cells trade wait for fill.  "soft" cells (§15
# BCJR soft output) batch like throughput traffic.
DEFAULT_MAX_WAIT = {"latency": 0.001, "throughput": 0.010, "soft": 0.010}

# throughput-class cells at or above this many radix steps route to the
# §8 one-pass streaming path when the engine's decoder is
# kernel-enabled; shorter frames stay on the dense two-pass batch
STREAM_MIN_STEPS = 4096

# the §13 degradation ladder: when a dispatch path keeps faulting past
# its retry budget, the cell falls to the next rung.  Every rung decodes
# bit-identical output (the §10 routing-equivalence contract), so
# degradation trades only throughput/latency, never correctness.
# "stream_xla" is the §8 one-pass kernel forced back onto the two-pass
# XLA chunked path (bit-exact by the kernel-parity gate); "batch" is the
# single-device dense decode every code supports.  WAVA, batch and
# session dispatches have no alternative implementation — they retry in
# place and then surface a typed per-ticket error.
DEGRADATION_LADDER = {
    "sharded": ("sharded", "batch"),
    "stream": ("stream", "stream_xla", "batch"),
    "time_parallel": ("time_parallel", "batch"),
    "wava": ("wava",),
    "batch": ("batch",),
    # §15 soft output has no bit-identical alternative implementation
    # (real-valued LLRs, no routing-equivalence contract with the hard
    # paths) — like WAVA it retries in place
    "soft": ("soft",),
}


@dataclasses.dataclass(frozen=True)
class DecodeRequest:
    """One tenant request: ragged LLRs + registry code + SLO class.

    ``llrs`` is (n, beta) shaped stages for unpunctured / tail-biting
    codes, or the 1-D serial kept-LLR stream (Lp,) for punctured codes
    (the §7 front-door convention, per frame).

    ``flushed`` declares the §7 framing: the frame's last stage leaves
    the encoder at state 0 (it carries its k-1 zero tail).  Flushed
    frames bucket into their own EXACT-LENGTH cells (like tail-biting
    — a final pin must land on the true last stage; through pad stages
    it stops pinning anything) and decode with both trellis ends
    pinned.  Leave False for streams of unknown framing (length-rung
    cells, argmax final end, the §10 padding lemma).
    """

    llrs: np.ndarray
    code: str = "ccsds-k7"
    slo: str = "throughput"
    flushed: bool = False
    # §13 deadline-aware shedding: a request whose engine clock passes
    # ``deadline`` before its cell dispatches is rejected with a typed
    # ``deadline_exceeded`` error instead of being decoded late (None =
    # never expires)
    deadline: Optional[float] = None


@dataclasses.dataclass
class Ticket:
    """Engine-side handle for a submitted request (or session chunk).

    ``bits`` is filled (np.int32, message bits) when the batch the
    request rode in decodes; ``dropped`` marks backpressure rejects.
    ``error`` is the §13 typed failure result (``deadline_exceeded``,
    or ``decode_failed:<ExceptionType>`` after the retry budget and the
    degradation ladder are both exhausted) — a ticket always ends done
    with bits, done with an error, or dropped; never silently lost.
    ``retries`` counts the dispatch retries its batch absorbed.
    """

    id: int
    code: str
    slo: str
    submitted: float
    n_out: int
    done: bool = False
    dropped: bool = False
    bits: Optional[np.ndarray] = None
    # §15 soft ("soft" SLO class) dispatches also fill ``llrs`` with
    # the per-bit BCJR posteriors (np.float32); ``bits`` then carries
    # their hard signs so downstream consumers need not branch
    llrs: Optional[np.ndarray] = None
    completed: Optional[float] = None
    cell: Optional[Tuple] = None
    path: Optional[str] = None
    error: Optional[str] = None
    retries: int = 0
    deadline: Optional[float] = None

    @property
    def sojourn(self) -> Optional[float]:
        return None if self.completed is None else (
            self.completed - self.submitted
        )


@dataclasses.dataclass
class _Session:
    """LRU-table entry of one chunked-streaming tenant (DESIGN.md §10)."""

    sid: str
    code: str
    state: object  # core.decoder.StreamState
    pending: collections.deque  # of (Ticket, shaped (1, c, beta) chunk)
    last_used: float
    consumed_steps: int = 0


class DecodeEngine:
    """Multi-tenant decode engine with dynamic batch assembly
    (DESIGN.md §10).  See the module docstring for the design; the
    operator-facing walkthrough lives in README "Serving".

    Parameters
    ----------
    max_batch        : frame cap per assembled batch (and frame-rung cap).
    max_wait         : per-SLO assembly deadline, seconds (virtual or
                       wall — whatever clock ``now`` arguments carry).
    max_pending      : queue-depth backpressure bound; past it ``submit``
                       marks tickets ``dropped`` instead of queueing.
    use_kernel       : thread the Pallas backend into every decoder
                       (enables the §8 one-pass route for throughput
                       traffic).
    precision        : AcsPrecision shared by all per-code decoders.
    decision_depth   : streaming decision depth for sessions (stretched
                       per code by the §7 puncture expansion).
    session_capacity : LRU session-table bound; overflow evicts+flushes.
    mesh             : optional device mesh — cells whose frame rung
                       fills it dispatch onto §6 ``sharded_decode_frames``
                       (``distributed.decoder.engine_dispatch_ready``).
    underfill_rows   : override of ``backend.device_underfill_rows()``
                       for the §9 latency-route eligibility (tests /
                       capacity planning; None = probe the backend).
    min_cell         : bottom rung of the length ladder.
    registry         : ``obs.MetricsRegistry`` backing all counters and
                       ``stats()`` (DESIGN.md §12).  None builds a
                       private real registry — the registry is always
                       real because it IS the stats() store.
    recorder         : ``obs.SpanRecorder`` for the request-lifecycle
                       spans (enqueue -> assemble -> jit lookup ->
                       dispatch -> device wait -> emit).  None installs
                       the zero-cost ``NullRecorder``.
    chaos            : optional ``runtime.chaos.ChaosInjector`` — called
                       before every dispatch; injects the §13 fault
                       schedule (tests/CI/benches; None in production).
    retry            : ``runtime.failure.RetryPolicy`` (or an int
                       max-retries shorthand) bounding per-rung dispatch
                       retries; None = the default policy.
    dispatch_timeout : straggler promotion threshold, seconds — injected
                       slow-host delays at/above it count as timeouts.
    monitor          : optional ``runtime.failure.HeartbeatMonitor``;
                       every poll, hosts it declares failed are removed
                       from the mesh (host ids map 1:1 onto device ids).
    checkpoint_dir   : session-durability directory (DESIGN.md §13);
                       ``checkpoint_sessions``/``restore_sessions`` and
                       the periodic ``checkpoint_interval`` writer use
                       it.  None disables session checkpointing.
    checkpoint_interval : engine-clock seconds between automatic
                       session-table checkpoints during poll (None =
                       only explicit ``checkpoint_sessions`` calls).
    scrub            : online SDC scrubber (DESIGN.md §14) — a
                       ``verify.scrub.SdcScrubber``, a float sample
                       rate shorthand, or None/0.0 (disabled: the
                       engine makes NO extra calls and its output is
                       bit-identical to a pre-scrubber engine).
                       Sampled batch dispatches get a re-encode
                       syndrome check per frame; flags are confirmed by
                       a shadow re-decode on an independent ladder rung,
                       and confirmed corruption fails the frame's
                       ticket with ``sdc_detected`` and quarantines the
                       attributed device through ``replan_mesh``.
                       Session dispatches are not scrubbed (carry-state
                       chunks have no per-frame re-encode framing).
    sanitize         : clamp-and-count mode for ``submit`` input
                       hardening: NaN -> 0.0 (erasure), +/-Inf and
                       out-of-range samples -> clamped, counted into
                       ``decoder_input_sanitized_total``.  False
                       (default) rejects non-finite input with a typed
                       per-ticket ``invalid_input:non_finite`` error.
    """

    def __init__(
        self,
        max_batch: int = 64,
        max_wait: Optional[Dict[str, float]] = None,
        max_pending: int = 4096,
        use_kernel: bool = False,
        precision=None,
        decision_depth: Optional[int] = None,
        session_capacity: int = 128,
        mesh=None,
        underfill_rows: Optional[int] = None,
        min_cell: int = ENGINE_MIN_CELL,
        registry: Optional[MetricsRegistry] = None,
        recorder: Optional[SpanRecorder] = None,
        chaos=None,
        retry=None,
        dispatch_timeout: Optional[float] = None,
        monitor=None,
        checkpoint_dir=None,
        checkpoint_interval: Optional[float] = None,
        scrub=None,
        sanitize: bool = False,
    ):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        self.max_batch = max_batch
        self.max_wait = dict(DEFAULT_MAX_WAIT, **(max_wait or {}))
        self.max_pending = max_pending
        self.use_kernel = use_kernel
        self.precision = precision
        self.decision_depth = decision_depth
        self.session_capacity = session_capacity
        self.mesh = mesh
        self.underfill_rows = underfill_rows
        self.min_cell = min_cell
        self.chaos = chaos
        if isinstance(retry, int):
            retry = RetryPolicy(max_retries=retry)
        self.retry = retry if retry is not None else RetryPolicy()
        self.dispatch_timeout = dispatch_timeout
        self.monitor = monitor
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_interval = checkpoint_interval
        if scrub is None:
            scrub = SdcScrubber(rate=0.0)
        elif isinstance(scrub, (int, float)):
            scrub = SdcScrubber(rate=float(scrub))
        self.scrub = scrub
        self.sanitize = bool(sanitize)
        self._quarantined: set = set()
        # §14 post-mortem trail: one QuarantineRecord per device, with
        # the cell/path/frame evidence the quarantine was based on
        self.quarantine_log: List[QuarantineRecord] = []
        self._last_ckpt: Optional[float] = None
        self._ckpt_steps = itertools.count()
        self._failed_devices: set = set()
        self._decoders: Dict[str, ViterbiDecoder] = {}
        self._xla_decoders: Dict[str, ViterbiDecoder] = {}
        self._queues: Dict[Tuple, collections.deque] = {}
        self._fns: Dict[Tuple, object] = {}
        self._sessions: "collections.OrderedDict[str, _Session]" = (
            collections.OrderedDict()
        )
        self._evicted: "collections.OrderedDict[str, np.ndarray]" = (
            collections.OrderedDict()
        )
        self._ids = itertools.count()
        self._sids = itertools.count()
        # histories are bounded (DESIGN.md §10, §12): a long-running
        # engine must not grow state per request — the sojourn
        # histograms keep a 4096-observation exact window, batch_log
        # the most recent batches, and parked eviction tails expire
        # oldest-first if never read
        self.batch_log: "collections.deque[dict]" = collections.deque(
            maxlen=1024
        )
        self._done_buffer: List[Ticket] = []  # completed out of band
        # text of the most recent untyped dispatch errors (stats())
        self.error_log: "collections.deque[str]" = collections.deque(
            maxlen=64
        )
        # §12 accounting: every counter lives in the registry (stats()
        # reads it back), spans go through the recorder (no-op default)
        self.registry = registry if registry is not None else MetricsRegistry()
        self.recorder = recorder
        r = self.registry
        self._m_requests = r.counter(
            "engine_requests_total",
            "requests by lifecycle event (submitted/completed/rejected)",
        )
        self._m_batches = r.counter(
            "engine_batches_total",
            "dispatched batches per (code, path, f, t) cell",
        )
        self._m_frames = r.counter(
            "engine_frames_total",
            "frames per dispatched cell, kind=real|pad",
        )
        self._m_elems = r.counter(
            "engine_llr_elems_total",
            "LLR elements moved per batch, kind=real|pad",
        )
        self._m_erasures = r.counter(
            "engine_erasures_total",
            "zero LLRs re-inserted into punctured session chunks, by code",
        )
        self._m_sessions = r.counter(
            "engine_sessions_total",
            "session lifecycle events (opened/closed/evicted; closed "
            "includes forced closes by eviction)",
        )
        self._m_jit = r.counter(
            "engine_jit_cache_total", "jit-fn cache lookups, event=hit|miss"
        )
        self._m_queue = r.gauge(
            "engine_queue_depth", "requests + session chunks waiting"
        )
        self._m_open_sessions = r.gauge(
            "engine_open_sessions", "sessions currently in the LRU table"
        )
        self._m_jit_entries = r.gauge(
            "engine_jit_cache_entries", "cached decode callables"
        )
        self._m_sojourn = r.histogram(
            "engine_sojourn_seconds",
            "submit -> complete sojourn per SLO class (engine clock)",
            window=4096,
        )
        self._m_dispatch = r.histogram(
            "engine_dispatch_seconds",
            "dispatch + device wait wall time per (code, path, f, t) "
            "cell (recorded only while tracing is enabled)",
        )
        # §13 fault-tolerance accounting
        self._m_faults = r.counter(
            "engine_faults_total",
            "dispatch faults observed, by kind (device_failure/timeout/"
            "slow/compile_error/error) and path",
        )
        self._m_retries = r.counter(
            "engine_retries_total",
            "dispatch retries by path (bounded per ladder rung)",
        )
        self._m_backoff = r.counter(
            "engine_backoff_seconds_total",
            "exponential-backoff budget accounted before retries "
            "(virtual: recorded, not slept, on the engine clock)",
        )
        self._m_degraded = r.counter(
            "engine_degraded_total",
            "degradation-ladder reroutes, labeled from -> to",
        )
        self._m_failover = r.counter(
            "engine_failover_total",
            "device failures absorbed by mesh re-planning",
        )
        self._m_ckpt = r.counter(
            "engine_checkpoints_total", "session-table checkpoints written"
        )
        # §14 data-integrity accounting
        self._m_scrub = r.counter(
            "engine_scrub_total",
            "SDC-scrubber events (sampled/frames/syndrome_flag/shadow/"
            "confirmed/false_alarm)",
        )
        self._m_quarantine = r.counter(
            "engine_quarantined_total",
            "devices quarantined after confirmed silent data corruption",
        )
        self._m_sanitized = r.counter(
            "decoder_input_sanitized_total",
            "input LLR samples repaired at the engine front door, by "
            "reason (nan/clamped)",
        )

    @property
    def recorder(self) -> SpanRecorder:
        return self._recorder

    @recorder.setter
    def recorder(self, recorder: Optional[SpanRecorder]) -> None:
        """The span recorder (None = the no-op ``NullRecorder``); a new
        one reaches the decoders already built too, whose
        ``decoder.*`` spans nest under the engine's."""
        self._recorder = recorder if recorder is not None else NullRecorder()
        for dec in (*self._decoders.values(), *self._xla_decoders.values()):
            dec.recorder = self._recorder

    # -- decoders / jit-fn cache ------------------------------------------

    def _decoder(self, code: str) -> ViterbiDecoder:
        """One ViterbiDecoder per registry code, built lazily and shared
        by every cell of that code — tables are hashed by identity
        (§6), so sharing the instance is what makes repeated same-cell
        batches hit the jax trace cache."""
        if code not in self._decoders:
            kw = {}
            if self.decision_depth is not None:
                kw["decision_depth"] = self.decision_depth
            self._decoders[code] = ViterbiDecoder.from_standard(
                code,
                precision=self.precision,
                use_kernel=self.use_kernel,
                recorder=self.recorder,
                registry=self.registry,
                **kw,
            )
        return self._decoders[code]

    def _xla_decoder(self, code: str) -> ViterbiDecoder:
        """Non-kernel twin of ``_decoder(code)`` backing the §13
        degraded "stream_xla" rung: identical code tables and decision
        depth, Pallas backend off — the two-pass XLA chunked path is
        bit-exact to the one-pass kernel (the kernel-parity gate), so
        falling here after kernel compile faults changes nothing but
        speed."""
        if code not in self._xla_decoders:
            kw = {}
            if self.decision_depth is not None:
                kw["decision_depth"] = self.decision_depth
            self._xla_decoders[code] = ViterbiDecoder.from_standard(
                code,
                precision=self.precision,
                use_kernel=False,
                recorder=self.recorder,
                registry=self.registry,
                **kw,
            )
        return self._xla_decoders[code]

    def _underfill(self) -> int:
        if self.underfill_rows is not None:
            return self.underfill_rows
        from repro.core.backend import device_underfill_rows

        return device_underfill_rows()

    def _pick_path(
        self, code: str, slo: str, f_cell: int, n_stages: int
    ) -> str:
        """The §10 SLO -> decode-path routing table, in code order."""
        dec = self._decoder(code)
        steps = -(-n_stages // dec.rho)
        if slo == "soft":
            # §15 soft output routes unconditionally — decode_soft picks
            # the circular (tail-biting) vs open BCJR formulation itself
            return "soft"
        if dec.termination == "tailbiting":
            return "wava"
        if slo == "latency":
            tile = time_parallel_plan(
                f_cell,
                steps,
                dec.spec.n_states,
                None,
                dec.transfer_tile,
                underfill_rows=self._underfill(),
            )
            if tile is not None:
                return "time_parallel"
        if slo == "throughput" and dec.one_pass and steps >= STREAM_MIN_STEPS:
            return "stream"
        if self.mesh is not None:
            from repro.distributed.decoder import engine_dispatch_ready

            if engine_dispatch_ready(f_cell, self.mesh):
                return "sharded"
        return "batch"

    def _decode_fn(self, code: str, path: str, f_cell: int, l_cell: int,
                   flushed: bool = False):
        """Cached decode callable per (code, path, F rung, length rung,
        flushed) — the jit-cache key of DESIGN.md §10.  One engine-level
        entry maps onto one traced program shape, so the hit/miss
        counters are the recompile accounting the tests assert on."""
        key = (code, path, f_cell, l_cell, flushed)
        if key in self._fns:
            self._m_jit.inc(1, event="hit")
            return self._fns[key]
        self._m_jit.inc(1, event="miss")
        dec = self._decoder(code)
        # zero-terminated frames always START at state 0 (the §7 framing
        # contract), so whole-frame decodes pin the initial state; the
        # final end is pinned only for cells of declared-flushed frames
        # (DecodeRequest.flushed) — for streams of unknown framing it
        # stays argmax, where the padding lemma (DESIGN.md §10) holds
        fin = 0 if flushed else None
        if path == "wava":
            fn = lambda llrs: dec.decode_tailbiting(llrs)[0]  # noqa: E731
        elif path == "soft":
            fn = lambda llrs: dec.decode_soft(  # noqa: E731
                llrs, output="llr", initial_state=0, final_state=fin,
            )
        elif path == "time_parallel":
            fn = lambda llrs: dec.decode_batch(  # noqa: E731
                llrs, initial_state=0, final_state=fin,
                time_parallel=True,
            )
        elif path == "stream":
            fn = lambda llrs: dec.decode_stream_chunked(  # noqa: E731
                llrs, initial_state=0, final_state=fin
            )
        elif path == "stream_xla":
            xdec = self._xla_decoder(code)
            fn = lambda llrs: xdec.decode_stream_chunked(  # noqa: E731
                llrs, initial_state=0, final_state=fin
            )
        elif path == "sharded":
            fn = lambda llrs: dec.decode_sharded(  # noqa: E731
                llrs, mesh=self.mesh, initial_state=0, final_state=fin
            )
        else:
            fn = lambda llrs: dec.decode_batch(  # noqa: E731
                llrs, initial_state=0, final_state=fin,
                time_parallel=False,
            )
        self._fns[key] = fn
        self._m_jit_entries.set(len(self._fns))
        return fn

    # -- request intake ----------------------------------------------------

    def _validate(self, req: DecodeRequest):
        """-> (llrs np.f32, n_stages, serial, l_input) or raises.

        §14 input hardening happens here: non-finite samples raise a
        typed ``InvalidInputError(reason="non_finite")`` (``submit``
        converts it to a per-ticket ``invalid_input:non_finite`` error
        so one poisoned tenant cannot fail its batchmates), or — with
        ``sanitize=True`` — are clamped and counted into
        ``decoder_input_sanitized_total`` on the engine registry."""
        from repro.codes.registry import get_code

        code = get_code(req.code)
        if req.slo not in SLO_CLASSES:
            raise ValueError(
                f"unknown SLO class {req.slo!r}; known: {SLO_CLASSES}"
            )
        llrs = np.asarray(req.llrs, np.float32)
        if code.puncture is not None:
            if llrs.ndim != 1:
                raise ValueError(
                    f"{req.code} is punctured: requests carry the serial "
                    f"kept-LLR stream (Lp,), got shape {llrs.shape}"
                )
            llrs, _ = validate_llrs(
                llrs, sanitize=self.sanitize, where="engine",
                registry=self.registry,
            )
            n_stages = code.puncture.stages_for(llrs.shape[0])
            return llrs, n_stages, True, llrs.shape[0]
        if llrs.ndim != 2 or llrs.shape[1] != code.spec.beta:
            raise ValueError(
                f"{req.code} requests carry (n, beta={code.spec.beta}) "
                f"shaped LLRs, got shape {llrs.shape}"
            )
        llrs, _ = validate_llrs(
            llrs, sanitize=self.sanitize, where="engine",
            registry=self.registry,
        )
        return llrs, llrs.shape[0], False, llrs.shape[0]

    def _cell_length(self, req_code, serial: bool, exact: bool,
                     l_input: int) -> int:
        """Length rung of the cell (DESIGN.md §10 bucketing rules):
        exact-length cells — tail-biting frames (circular trellis: a
        pad stage would join the wrap-around path) and declared-flushed
        frames (the final pin must land on the TRUE last stage; through
        pad stages every state reaches the pin for free and it stops
        pinning anything) — keep l_input; punctured serial lengths
        round to whole pattern periods so the padded stream depunctures
        cleanly; everything else rides the ladder as-is."""
        if exact:
            return l_input
        mult = req_code.puncture.n_kept if serial else 1
        return pick_cell_length(l_input, self.min_cell, mult)

    def submit(self, req: DecodeRequest, now: Optional[float] = None
               ) -> Ticket:
        """Enqueue one request; returns its Ticket (``dropped=True``
        under backpressure).  ``now`` is the submission timestamp —
        pass a virtual clock for deterministic tests/benches."""
        with self.recorder.span("engine.submit"):
            return self._submit(req, now)

    def _submit(self, req: DecodeRequest, now: Optional[float]) -> Ticket:
        from repro.codes.registry import get_code

        now = time.monotonic() if now is None else now
        try:
            llrs, n_stages, serial, l_input = self._validate(req)
        except InvalidInputError as e:
            # §14: a malformed payload fails ITS OWN ticket — shape
            # misuse still raises (caller bug), but non-finite data is
            # a data-plane condition any tenant can hit at runtime
            ticket = Ticket(
                id=next(self._ids),
                code=req.code,
                slo=req.slo,
                submitted=now,
                n_out=0,
            )
            ticket.done = True
            ticket.error = f"invalid_input:{e.reason}"
            ticket.completed = now
            self._m_requests.inc(1, event="invalid", slo=req.slo)
            return ticket
        code = get_code(req.code)
        tb = code.termination == "tailbiting"
        dec = self._decoder(req.code)
        # the flushed declaration is honored only where a final pin is
        # well-defined: zero-terminated code, frame stages on a radix
        # boundary (a pin cannot land mid-step)
        flushed = (
            req.flushed and not tb and n_stages % dec.rho == 0
        )
        l_cell = self._cell_length(code, serial, tb or flushed, l_input)
        ticket = Ticket(
            id=next(self._ids),
            code=req.code,
            slo=req.slo,
            submitted=now,
            n_out=n_stages,
            deadline=req.deadline,
        )
        if req.deadline is not None and now > req.deadline:
            # §13 deadline shedding at the door: already expired
            ticket.done = True
            ticket.error = "deadline_exceeded"
            ticket.completed = now
            self._m_requests.inc(1, event="expired", slo=req.slo)
            return ticket
        if self.queue_depth() >= self.max_pending:
            ticket.dropped = True
            self._m_requests.inc(1, event="rejected", slo=req.slo)
            return ticket
        key = (
            req.code, req.slo, l_cell,
            "tb" if tb else ("flushed" if flushed else "open"),
        )
        self._queues.setdefault(key, collections.deque()).append(
            (ticket, llrs)
        )
        self._m_requests.inc(1, event="submitted", slo=req.slo)
        self.recorder.event(
            "engine.enqueue", ticket=ticket.id, code=req.code,
            slo=req.slo, t_cell=l_cell, n_stages=n_stages, now=now,
        )
        return ticket

    def queue_depth(self) -> int:
        """Requests + session chunks currently waiting (the
        backpressure signal)."""
        return sum(len(q) for q in self._queues.values()) + sum(
            len(s.pending) for s in self._sessions.values()
        )

    # -- batch assembly + decode ------------------------------------------

    def poll(self, now: Optional[float] = None) -> List[Ticket]:
        """Assemble and decode every batch that is due at ``now`` (full
        cells, or cells whose oldest request exceeded the SLO's
        max-wait), plus all pending session chunks.  Returns the
        tickets completed by this call, in completion order (plus any
        completed out of band by close_session/eviction since the last
        poll)."""
        return self._run_due(now, drain=False)

    def drain(self, now: Optional[float] = None) -> List[Ticket]:
        """Graceful drain: decode everything still queued — partial
        cells included — and all pending session chunks.  Sessions stay
        open (close them via ``close_session``)."""
        return self._run_due(now, drain=True)

    def _run_due(self, now: Optional[float], drain: bool) -> List[Ticket]:
        """``poll`` (due cells only) or ``drain`` (every cell), in one
        ``engine.poll`` span."""
        now = time.monotonic() if now is None else now
        rec = self.recorder
        with rec.span("engine.poll") as sp:
            n0 = self._m_batches.total() if rec.enabled else 0
            self._check_hosts(now)
            done, self._done_buffer = self._done_buffer, []
            for key in sorted(self._queues):
                q = self._queues[key]
                while q and (
                    drain
                    or len(q) >= self.max_batch
                    or now - q[0][0].submitted >= self.max_wait[key[1]]
                ):
                    done.extend(self._run_batch(key, q, now))
            done.extend(self._run_sessions(now))
            self._maybe_checkpoint(now)
            if rec.enabled:
                sp.set(n_batches=int(self._m_batches.total() - n0),
                       n_done=len(done))
        return done

    def _run_batch(self, key, q, now: float) -> List[Ticket]:
        code_name, slo, l_cell, kind = key
        rec = self.recorder
        with rec.span(
            "engine.batch", code=code_name, slo=slo, t=l_cell, kind=kind,
            now=now,
        ) as bsp:
            k = min(len(q), self.max_batch)
            entries, shed = [], []
            for _ in range(k):
                ticket, llrs = q.popleft()
                if ticket.deadline is not None and now > ticket.deadline:
                    # §13 deadline shedding: expired while queued —
                    # typed error, never decoded late
                    ticket.done = True
                    ticket.error = "deadline_exceeded"
                    ticket.completed = now
                    self._m_requests.inc(1, event="expired", slo=slo)
                    shed.append(ticket)
                else:
                    entries.append((ticket, llrs))
            if not entries:
                bsp.set(n_real=0, shed=len(shed))
                return shed
            k = len(entries)
            f_cell = pick_cell_frames(k, self.max_batch)
            dec = self._decoder(code_name)
            serial = dec.puncture is not None
            with rec.span("engine.assemble", n_real=k, f=f_cell) as asp:
                shape = (f_cell, l_cell) if serial else (
                    f_cell, l_cell, dec.spec.beta
                )
                dense = np.zeros(shape, np.float32)
                real_elems = 0
                for i, (_, llrs) in enumerate(entries):
                    dense[i, : llrs.shape[0]] = llrs
                    real_elems += llrs.size
                if rec.enabled:
                    asp.set(real_elems=real_elems, cell_elems=dense.size)
            n_stages = (
                dec.puncture.stages_for(l_cell) if serial else l_cell
            )
            path = self._pick_path(code_name, slo, f_cell, n_stages)
            bsp.set(path=path, f=f_cell, n_real=k)
            with rec.span("engine.jit_lookup", path=path):
                fn = self._decode_fn(
                    code_name, path, f_cell, l_cell,
                    flushed=(kind == "flushed"),
                )
            with rec.span(
                "engine.dispatch", code=code_name, path=path,
                f=f_cell, t=l_cell,
            ) as dsp:
                if rec.enabled:
                    dsp.set(h2d_arrays=1, h2d_bytes=dense.nbytes)
                try:
                    path, out, retries = self._dispatch_with_faults(
                        code_name, fn, path, f_cell, l_cell,
                        kind == "flushed", jnp.asarray(dense), now, dsp,
                    )
                except Exception as e:  # noqa: BLE001 — §13: ladder
                    # exhausted; riders get typed errors, engine lives
                    return shed + self._fail_tickets(
                        [t for t, _ in entries], e, slo, now
                    )
                with rec.span("engine.device_wait") as wsp:
                    (bits,), n, nbytes = to_host([out])
                    wsp.set(d2h_arrays=n, d2h_bytes=nbytes)
                if self.chaos is not None and path != "soft":
                    # armed bit_flip events corrupt the decoded bits
                    # AFTER the dispatch — silent by definition; only
                    # the §14 scrubber below can catch it
                    bits, sdc_device = self.chaos.corrupt(bits)
                else:
                    sdc_device = None
                if rec.enabled:
                    self._m_dispatch.observe(
                        rec.clock() - dsp.t0,
                        code=code_name, path=path, f=f_cell, t=l_cell,
                    )
            corrupt_ids: set = set()
            # §15 soft output is real-valued — no bit-identical shadow
            # rung exists, so the §14 scrubber has nothing to vote
            # against and soft dispatches are never sampled
            if path != "soft" and self.scrub.enabled and self.scrub.sample():
                with rec.span("engine.scrub", n=k, path=path):
                    corrupt_ids = self._scrub_dispatch(
                        code_name, path, f_cell, l_cell,
                        kind == "flushed", entries, bits, dense,
                        sdc_device, now,
                    )
            with rec.span("engine.emit", n=k):
                for i, (ticket, _) in enumerate(entries):
                    if i in corrupt_ids:
                        ticket.error = "sdc_detected"
                    elif path == "soft":
                        ticket.llrs = (
                            bits[i, : ticket.n_out].astype(np.float32)
                        )
                        ticket.bits = (ticket.llrs < 0).astype(np.int32)
                    else:
                        ticket.bits = (
                            bits[i, : ticket.n_out].astype(np.int32)
                        )
                    ticket.done = True
                    ticket.completed = now
                    ticket.cell = (code_name, slo, l_cell, f_cell)
                    ticket.path = path
                    ticket.retries = retries
                    self._m_sojourn.observe(now - ticket.submitted, slo=slo)
        cl = dict(code=code_name, path=path, f=f_cell, t=l_cell)
        self._m_requests.inc(k - len(corrupt_ids), event="completed", slo=slo)
        if corrupt_ids:
            self._m_requests.inc(len(corrupt_ids), event="sdc", slo=slo)
        self._m_batches.inc(1, slo=slo, **cl)
        self._m_frames.inc(k, kind="real", **cl)
        self._m_frames.inc(f_cell - k, kind="pad", **cl)
        cell_elems = int(np.prod(shape))
        self._m_elems.inc(real_elems, kind="real")
        self._m_elems.inc(cell_elems - real_elems, kind="pad")
        self.batch_log.append(
            dict(
                cell=(code_name, slo, l_cell),
                f_cell=f_cell,
                n_real=k,
                path=path,
                tickets=[t.id for t, _ in entries],
                wait=now - entries[0][0].submitted,
            )
        )
        return shed + [t for t, _ in entries]

    # -- fault handling (DESIGN.md §13) -----------------------------------

    def _inject(self, code: str, path: str):
        """Chaos hook: called immediately before every dispatch attempt
        (retries and degraded re-dispatches included).  Raises the
        injected typed fault, or promotes an injected straggler delay
        at/above ``dispatch_timeout`` into a ``DispatchTimeout``;
        shorter delays are absorbed (counted, not raised)."""
        if self.chaos is None:
            return
        delay = self.chaos.on_dispatch(code, path)
        if delay:
            self._m_faults.inc(1, kind="slow", path=path)
            if (
                self.dispatch_timeout is not None
                and delay >= self.dispatch_timeout
            ):
                raise DispatchTimeout(
                    f"straggler delay {delay:.3f}s >= dispatch_timeout "
                    f"{self.dispatch_timeout:.3f}s"
                )

    def _dispatch_with_faults(
        self, code: str, fn, path: str, f_cell: int, l_cell: int,
        flushed: bool, arr, now: float, dsp,
    ):
        """Run one assembled cell through the §13 retry + degradation
        machinery; returns ``(final_path, out, retries)`` or re-raises
        once every rung of the ladder has exhausted its retry budget.

        Correctness under retry/degradation is free: decode is pure
        (the cell's LLRs are immutable and no engine state was updated
        yet), and every ladder rung is bit-identical by the §10 routing
        contract — so a retried or degraded dispatch emits exactly the
        bits the first attempt would have.

        Only the typed faults (``InjectedFault``: device failures,
        dispatch timeouts, transient compile errors) are retried or
        degraded; any other exception is recorded and re-raised at once,
        and its riders fail with a typed ``decode_failed`` error."""
        ladder = DEGRADATION_LADDER.get(path, (path,))
        rung, attempt, retries = 0, 0, 0
        while True:
            try:
                self._inject(code, path)
                return path, fn(arr), retries
            except InjectedFault as e:
                kind = e.kind
                if kind != "slow":  # slow already counted by _inject
                    self._m_faults.inc(1, kind=kind, path=path)
                self.recorder.event(
                    "engine.fault", kind=kind, path=path, error=str(e),
                    now=now,
                )
                if dsp is not None:
                    dsp.set(fault=kind)
                degrade_now = False
                if isinstance(e, DeviceFailure):
                    alive = self._handle_device_failure(e.device, now)
                    if path == "sharded":
                        from repro.distributed.decoder import (
                            engine_dispatch_ready,
                        )

                        # retry on the survivor mesh only if the cell
                        # still fills it; otherwise fall to batch
                        degrade_now = not (
                            alive
                            and engine_dispatch_ready(f_cell, self.mesh)
                        )
                if not degrade_now and attempt < self.retry.max_retries:
                    self._m_retries.inc(1, path=path)
                    self._m_backoff.inc(
                        self.retry.backoff(attempt), path=path
                    )
                    attempt += 1
                    retries += 1
                    continue
                if rung + 1 < len(ladder):
                    nxt = ladder[rung + 1]
                    self._m_degraded.inc(1, **{"from": path, "to": nxt})
                    self.recorder.event(
                        "engine.degrade", now=now,
                        **{"from": path, "to": nxt},
                    )
                    rung += 1
                    attempt = 0
                    path = nxt
                    fn = self._decode_fn(
                        code, path, f_cell, l_cell, flushed=flushed
                    )
                    continue
                e.engine_retries = retries  # rides to _fail_tickets
                raise
            except Exception as e:
                # a real error (a Mosaic compile failure, a shape bug) is
                # never retried or degraded past: that would hide it
                # behind a slower rung and end the run green
                self._record_error(e, path, retries, now, dsp)
                raise

    def _record_error(self, e: Exception, path: str, retries: int,
                      now: float, dsp) -> None:
        """Account an untyped dispatch exception: counted as a fault of
        kind "error", its text kept in ``error_log`` (``stats()``), and
        the riders failed by the caller with a typed error."""
        self._m_faults.inc(1, kind="error", path=path)
        self.error_log.append(f"{path}: {type(e).__name__}: {e}")
        self.recorder.event(
            "engine.fault", kind="error", path=path, error=str(e), now=now
        )
        if dsp is not None:
            dsp.set(fault="error")
        e.engine_retries = retries

    # -- online SDC scrubbing (DESIGN.md §14) -----------------------------

    def _scrub_dispatch(
        self, code_name: str, path: str, f_cell: int, l_cell: int,
        flushed: bool, entries, bits: np.ndarray, dense: np.ndarray,
        sdc_device, now: float,
    ) -> set:
        """Scrub one sampled batch dispatch; returns the entry indices
        confirmed corrupt (their tickets get ``sdc_detected``).

        Stage 1 re-encodes every real frame's decoded bits and tests
        the syndrome against the frame's own submitted LLRs
        (``verify.scrub.syndrome_check``).  Stage 2 confirms any flag
        by re-decoding the WHOLE cell once on an independent rung of
        the §13 ladder (``SHADOW_RUNG``) and comparing bit-exactly —
        the §10 routing contract makes rungs bit-identical on clean
        hardware, so a shadow mismatch is corruption, not noise, and a
        shadow match demotes the flag to a counted false alarm.
        Confirmed corruption quarantines the attributed device through
        the §13 ``replan_mesh`` failover machinery."""
        from repro.codes.registry import get_code

        code = get_code(code_name)
        flagged = []
        for i, (ticket, llrs) in enumerate(entries):
            v = self.scrub.check_frame(bits[i, : ticket.n_out], llrs, code)
            self._m_scrub.inc(1, event="frames")
            if v.flagged:
                flagged.append(i)
                self._m_scrub.inc(1, event="syndrome_flag")
        self._m_scrub.inc(1, event="sampled")
        if not flagged or not self.scrub.shadow:
            return set()
        # stage 2: one shadow re-decode of the whole cell, off the
        # chaos/retry path (a plain dispatch — the scrubber must not
        # consume the fault schedule's attempt indices)
        shadow_path = self.scrub.shadow_path(path)
        self.scrub.counts["shadow_dispatches"] += 1
        self._m_scrub.inc(1, event="shadow", path=shadow_path)
        try:
            fn = self._decode_fn(
                code_name, shadow_path, f_cell, l_cell, flushed=flushed
            )
            shadow_bits = np.asarray(fn(jnp.asarray(dense)))
        except Exception as e:  # noqa: BLE001 — shadow rung unavailable
            # cannot confirm: demote to false alarms rather than fail
            # tickets on unconfirmed suspicion
            self.recorder.event(
                "engine.scrub_shadow_failed", error=repr(e), now=now
            )
            self.scrub.counts["false_alarms"] += len(flagged)
            self._m_scrub.inc(len(flagged), event="false_alarm")
            return set()
        confirmed = set()
        for i in flagged:
            n_out = entries[i][0].n_out
            if np.array_equal(bits[i, :n_out], shadow_bits[i, :n_out]):
                self.scrub.counts["false_alarms"] += 1
                self._m_scrub.inc(1, event="false_alarm")
            else:
                confirmed.add(i)
                self.scrub.counts["confirmed"] += 1
                self._m_scrub.inc(1, event="confirmed")
        if confirmed:
            self.recorder.event(
                "engine.sdc_confirmed", n=len(confirmed), code=code_name,
                path=path, device=sdc_device, now=now,
            )
            if sdc_device is not None and sdc_device not in self._quarantined:
                # quarantine = §13 failover with a §14 cause: the
                # device leaves the mesh and the plan shrinks onto
                # survivors
                self._quarantined.add(int(sdc_device))
                self.quarantine_log.append(QuarantineRecord(
                    device=int(sdc_device), at=now, code=code_name,
                    path=path, frames_confirmed=len(confirmed),
                ))
                self._m_quarantine.inc(1)
                self._handle_device_failure(sdc_device, now)
        return confirmed

    def _fail_tickets(self, tickets, exc, slo: str, now: float):
        """Retry budget + ladder exhausted: every rider gets a TYPED
        error result (never a silent drop); the engine keeps serving."""
        err = f"decode_failed:{type(exc).__name__}"
        for t in tickets:
            t.done = True
            t.error = err
            t.retries = getattr(exc, "engine_retries", 0)
            t.completed = now
        self._m_requests.inc(len(tickets), event="failed", slo=slo)
        self.recorder.event(
            "engine.batch_failed", n=len(tickets), error=repr(exc), now=now
        )
        return tickets

    def _handle_device_failure(self, device, now: float) -> bool:
        """Remove a failed device and re-plan the mesh onto survivors
        (``distributed.decoder.replan_mesh`` — the ElasticPlanner
        largest-power-of-two rule).  Returns True when a non-empty mesh
        survives.  Cached sharded decode fns late-bind ``self.mesh``,
        so they dispatch onto the shrunken mesh without invalidation."""
        if device is not None:
            self._failed_devices.add(int(device))
        self._m_failover.inc(1)
        n_dev = 0
        if self.mesh is not None:
            from repro.distributed.decoder import replan_mesh

            self.mesh = replan_mesh(self.mesh, self._failed_devices)
            n_dev = 0 if self.mesh is None else int(self.mesh.devices.size)
        self.recorder.event(
            "engine.failover", device=device, devices=n_dev, now=now
        )
        return self.mesh is not None

    def _check_hosts(self, now: float):
        """HeartbeatMonitor integration: hosts silent past the monitor
        timeout map 1:1 onto mesh device ids and are failed over exactly
        like an in-dispatch ``DeviceFailure``."""
        if self.monitor is None:
            return
        for h in self.monitor.failed(now):
            if h not in self._failed_devices:
                self._handle_device_failure(h, now)

    # -- sessions (stateful chunked streaming, DESIGN.md §10) -------------

    def open_session(
        self,
        code: str = "ccsds-k7",
        sid: Optional[str] = None,
        now: Optional[float] = None,
    ) -> str:
        """Register a chunked-streaming tenant; returns its session id.
        Overflowing ``session_capacity`` evicts (flushes) the
        least-recently-used session first."""
        now = time.monotonic() if now is None else now
        dec = self._decoder(code)  # validates the code name
        sid = sid if sid is not None else f"s{next(self._sids)}"
        if sid in self._sessions:
            raise ValueError(f"session {sid!r} already open")
        while len(self._sessions) >= self.session_capacity:
            self._evict_lru(now)
        self._sessions[sid] = _Session(
            sid=sid,
            code=code,
            state=dec.init_stream_state(1, initial_state=None),
            pending=collections.deque(),
            last_used=now,
        )
        self._m_sessions.inc(1, event="opened")
        self._m_open_sessions.set(len(self._sessions))
        return sid

    def _shape_chunk(self, code: str, llrs: np.ndarray):
        """One session chunk -> shaped (1, c, beta) stages, a numpy
        array.  Punctured sessions submit serial kept-LLR chunks in whole
        pattern periods (so per-chunk depuncturing equals whole-stream
        depuncturing), depunctured here on the host: the chunk reaches
        the device once, in ``decode_chunk_multi``'s stacked copy.
        Stage counts must sit on the rho grid (ring steps are radix)."""
        dec = self._decoder(code)
        llrs = np.asarray(llrs, np.float32)
        pat = dec.puncture
        if pat is not None:
            if llrs.ndim != 1:
                raise ValueError(
                    "punctured sessions take serial (Lp,) chunks, got "
                    f"shape {llrs.shape}"
                )
            lp = llrs.shape[0]
            if lp % pat.n_kept:
                raise ValueError(
                    f"serial session chunks must be whole puncture "
                    f"periods ({pat.n_kept} kept LLRs); got {lp}"
                )
            erased = lp // pat.n_kept * pat.period * pat.beta - lp
            with self.recorder.span("engine.depuncture", kept=lp,
                                    erased=erased):
                shaped = depuncture_np(llrs[None], pat)
            self._m_erasures.inc(erased, code=code)
        else:
            if llrs.ndim != 2 or llrs.shape[1] != dec.spec.beta:
                raise ValueError(
                    f"session chunks are (c, beta={dec.spec.beta}) "
                    f"stages, got shape {llrs.shape}"
                )
            shaped = llrs[None]
        if shaped.shape[1] % dec.rho:
            raise ValueError(
                f"chunk stage count {shaped.shape[1]} not divisible by "
                f"rho={dec.rho}"
            )
        return shaped

    def submit_chunk(
        self, sid: str, llrs: np.ndarray, now: Optional[float] = None
    ) -> Ticket:
        """Queue one LLR chunk on a session; the ticket completes (with
        the bits that became final) at the next poll/drain."""
        now = time.monotonic() if now is None else now
        with self.recorder.span("engine.submit"):
            sess = self._sessions[sid]
            shaped = self._shape_chunk(sess.code, llrs)
            ticket = Ticket(
                id=next(self._ids),
                code=sess.code,
                slo="throughput",
                submitted=now,
                n_out=-1,  # emission depends on stream position
            )
            if self.queue_depth() >= self.max_pending:
                ticket.dropped = True
                self._m_requests.inc(1, event="rejected", slo="throughput")
                return ticket
            sess.pending.append((ticket, shaped))
            self._sessions.move_to_end(sid)
            sess.last_used = now
            self._m_requests.inc(1, event="submitted", slo="throughput")
            return ticket

    def _run_sessions(self, now: float) -> List[Ticket]:
        """Drain pending session chunks, one chunk per session per
        round, rounds grouped by (code, chunk steps) into fused
        ``decode_chunk_multi`` dispatches of at most ``max_batch``
        sessions each — sessions at different stream positions batch
        together (the per-state emission slice keeps each
        bit-identical to a solo drive).

        A group whose dispatch fails PERMANENTLY (retry budget spent)
        has its head chunks requeued and its sessions stalled for the
        rest of this poll — the chunks retry at the next poll, so a
        session never loses a chunk to a fault (§13: sessions have no
        degraded rung; deferral is the fallback)."""
        done: List[Ticket] = []
        stalled: set = set()
        while True:
            groups: Dict[Tuple, List[_Session]] = {}
            for sid in sorted(self._sessions):
                sess = self._sessions[sid]
                if sess.pending and sid not in stalled:
                    key = (sess.code, sess.pending[0][1].shape[1])
                    groups.setdefault(key, []).append(sess)
            if not groups:
                return done
            for (code_name, c), sessions in sorted(groups.items()):
                for lo in range(0, len(sessions), self.max_batch):
                    batch = sessions[lo: lo + self.max_batch]
                    out, ok = self._dispatch_session_group(
                        code_name, c, batch, now,
                    )
                    done.extend(out)
                    if not ok:
                        stalled.update(s.sid for s in batch)

    def _dispatch_session_group(
        self, code_name: str, c: int, sessions: List[_Session], now: float,
        abandon_on_failure: bool = False,
    ) -> Tuple[List[Ticket], bool]:
        """One fused dispatch of <= max_batch sessions' head chunks.

        Returns ``(completed tickets, ok)``.  Dispatch faults retry
        under the §13 budget; ``decode_chunk_multi`` is functional
        (session states are reassigned only AFTER a successful decode),
        so a retry re-runs on untouched carries and stays bit-exact.  On
        permanent failure ``ok`` is False and the popped head chunks are
        requeued at their sessions' heads (deferred to the next poll) —
        unless ``abandon_on_failure`` (the close/eviction path, which
        cannot defer): then each chunk's ticket gets a typed error."""
        dec = self._decoder(code_name)
        rec = self.recorder
        with rec.span(
            "engine.batch", code=code_name, slo="throughput", t=c,
            kind="session", path="session", now=now,
        ):
            tickets, chunks, states = [], [], []
            k = len(sessions)
            f_cell = pick_cell_frames(k, self.max_batch)
            with rec.span("engine.assemble", n_real=k, f=f_cell) as asp:
                real_elems = 0
                for sess in sessions:
                    ticket, shaped = sess.pending.popleft()
                    tickets.append(ticket)
                    chunks.append(shaped)
                    states.append(sess.state)
                    real_elems += shaped.size
                if f_cell > k:  # pad with throwaway zero states
                    states.append(dec.init_stream_state(f_cell - k))
                    chunks.append(
                        np.zeros((f_cell - k, c, dec.spec.beta), np.float32)
                    )
                if rec.enabled:
                    asp.set(real_elems=real_elems,
                            cell_elems=f_cell * c * dec.spec.beta)
            key = (code_name, "session", f_cell, c)
            with rec.span("engine.jit_lookup", path="session"):
                if key in self._fns:
                    self._m_jit.inc(1, event="hit")
                else:
                    self._m_jit.inc(1, event="miss")
                    self._fns[key] = dec.decode_chunk_multi
                    self._m_jit_entries.set(len(self._fns))
            with rec.span(
                "engine.dispatch", code=code_name, path="session",
                f=f_cell, t=c,
            ) as dsp:
                attempt = retries = 0
                while True:
                    try:
                        self._inject(code_name, "session")
                        new_states, outs = self._fns[key](states, chunks)
                        break
                    except InjectedFault as e:
                        kind = e.kind
                        if kind != "slow":
                            self._m_faults.inc(1, kind=kind, path="session")
                        self.recorder.event(
                            "engine.fault", kind=kind, path="session",
                            error=str(e), now=now,
                        )
                        dsp.set(fault=kind)
                        if isinstance(e, DeviceFailure):
                            self._handle_device_failure(e.device, now)
                        if attempt < self.retry.max_retries:
                            self._m_retries.inc(1, path="session")
                            self._m_backoff.inc(
                                self.retry.backoff(attempt), path="session"
                            )
                            attempt += 1
                            retries += 1
                            continue
                        # permanent: states untouched (functional
                        # dispatch) — defer or abandon, never corrupt
                        e.engine_retries = retries
                        return self._session_dispatch_failed(
                            sessions, tickets, chunks, e, now,
                            abandon_on_failure,
                        ), False
                    except Exception as e:
                        # untyped: fail the chunks now — requeueing a
                        # deterministic error would only repeat it
                        self._record_error(e, "session", retries, now, dsp)
                        return self._session_dispatch_failed(
                            sessions, tickets, chunks, e, now, True,
                        ), False
                with rec.span("engine.device_wait") as wsp:
                    # the group's bits cross in one copy; each session's
                    # rows are views of it
                    outs, n, nbytes = to_host(outs)
                    wsp.set(d2h_arrays=n, d2h_bytes=nbytes)
                if self.chaos is not None and outs:
                    # fire any armed bit_flip here so corruption never
                    # leaks onto a later unrelated dispatch; sessions
                    # are outside the scrubber's coverage (DESIGN §14)
                    outs[0], _ = self.chaos.corrupt(outs[0])
                if rec.enabled:
                    self._m_dispatch.observe(
                        rec.clock() - dsp.t0,
                        code=code_name, path="session", f=f_cell, t=c,
                    )
            done: List[Ticket] = []
            with rec.span("engine.emit", n=k):
                for sess, ticket, state, out in zip(
                    sessions, tickets, new_states, outs
                ):
                    sess.state = state
                    sess.consumed_steps += c
                    ticket.bits = np.asarray(out[0]).astype(np.int32)
                    ticket.n_out = ticket.bits.shape[0]
                    ticket.done = True
                    ticket.completed = now
                    ticket.path = "session"
                    ticket.retries = retries
                    done.append(ticket)
                    self._m_sojourn.observe(
                        now - ticket.submitted, slo="throughput"
                    )
        cl = dict(code=code_name, path="session", f=f_cell, t=c)
        self._m_requests.inc(k, event="completed", slo="throughput")
        self._m_batches.inc(1, slo="throughput", **cl)
        self._m_frames.inc(k, kind="real", **cl)
        self._m_frames.inc(f_cell - k, kind="pad", **cl)
        self._m_elems.inc(k * c * dec.spec.beta, kind="real")
        self._m_elems.inc((f_cell - k) * c * dec.spec.beta, kind="pad")
        self.batch_log.append(
            dict(
                cell=(code_name, "session", c),
                f_cell=f_cell,
                n_real=k,
                path="session",
                tickets=[t.id for t in tickets],
                wait=0.0,
            )
        )
        return done, True

    def _session_dispatch_failed(
        self, sessions, tickets, chunks, exc, now: float,
        abandon: bool,
    ) -> List[Ticket]:
        """Permanent session-group dispatch failure (§13).  Requeue the
        popped head chunks (default — they retry next poll, the session
        loses nothing) or, on the close/eviction path, abandon them
        with typed per-ticket errors (``chunks`` may carry a trailing
        padding entry; ``tickets`` is the real count)."""
        if abandon:
            return self._fail_tickets(tickets, exc, "throughput", now)
        for sess, ticket, shaped in zip(sessions, tickets, chunks):
            sess.pending.appendleft((ticket, shaped))
        self.recorder.event(
            "engine.session_deferred", n=len(tickets), error=repr(exc),
            now=now,
        )
        return []

    def close_session(
        self, sid: str, now: Optional[float] = None
    ) -> np.ndarray:
        """Finish a session: decode its pending chunks (solo — other
        sessions' queues are untouched), flush the survivor ring,
        remove it.  Returns the tail bits (the decisions still inside
        the decision-depth window).  Chunk tickets completed here are
        also delivered by the NEXT poll/drain, so the poll contract
        ("every completed ticket appears in exactly one return list")
        holds across out-of-band closes and evictions."""
        now = time.monotonic() if now is None else now
        sess = self._sessions[sid]
        while sess.pending:  # decode in order, this session only
            out, _ok = self._dispatch_session_group(
                sess.code, sess.pending[0][1].shape[1], [sess], now,
                abandon_on_failure=True,  # a close cannot defer (§13)
            )
            self._done_buffer.extend(out)
        dec = self._decoder(sess.code)
        tail = np.asarray(dec.flush_stream(sess.state))[0].astype(np.int32)
        del self._sessions[sid]
        self._m_sessions.inc(1, event="closed")
        self._m_open_sessions.set(len(self._sessions))
        return tail

    def _evict_lru(self, now: float):
        """Session-table overflow (DESIGN.md §10): flush the
        least-recently-used session exactly as close_session would —
        eviction is a forced close, so evicted tenants lose no bits —
        and park the tail in ``evicted_tail``."""
        sid = next(iter(self._sessions))
        self._evicted[sid] = self.close_session(sid, now)
        while len(self._evicted) > 64:  # bounded: unread tails expire
            self._evicted.popitem(last=False)
        # ``closed`` (monotonic, Prometheus semantics) already counted
        # the forced close above; ``evicted`` marks it as such
        self._m_sessions.inc(1, event="evicted")

    def evicted_tail(self, sid: str) -> np.ndarray:
        """Tail bits of an evicted session (kept until read once)."""
        return self._evicted.pop(sid)

    # -- session durability (DESIGN.md §13) -------------------------------

    def checkpoint_sessions(self, now: Optional[float] = None):
        """Write the whole session table to ``checkpoint_dir`` via
        ``runtime.checkpoint.save_sessions`` (arrays in npz, scalars in
        the manifest, manifest written LAST — a crash mid-write leaves a
        torn step that restore skips).  The FULL ``StreamState`` is
        persisted (path metrics, survivor ring, stream position), so a
        restore resumes the exact carry — recovery is bit-identical by
        construction, no warmup re-decode needed; clients only replay
        chunks submitted after the checkpoint (a window bounded by
        ``checkpoint_interval``).  Returns the step path, or None when
        checkpointing is disabled."""
        if self.checkpoint_dir is None:
            return None
        now = time.monotonic() if now is None else now
        from repro.runtime import checkpoint as ckpt

        records = {
            sid: {
                "lam": np.asarray(s.state.lam),
                "hist": np.asarray(s.state.hist),
                "pos": int(s.state.pos),
                "code": s.code,
                "consumed": int(s.consumed_steps),
            }
            for sid, s in self._sessions.items()
        }
        step = next(self._ckpt_steps)
        path = ckpt.save_sessions(
            self.checkpoint_dir, step, records, extra={"now": now}
        )
        self._last_ckpt = now
        self._m_ckpt.inc(1)
        self.recorder.event(
            "engine.checkpoint", step=step, sessions=len(records), now=now
        )
        return path

    def _maybe_checkpoint(self, now: float):
        """Periodic session-table checkpoint on the engine clock."""
        if self.checkpoint_dir is None or self.checkpoint_interval is None:
            return
        if (
            self._last_ckpt is None
            or now - self._last_ckpt >= self.checkpoint_interval
        ):
            self.checkpoint_sessions(now)

    def restore_sessions(
        self, ckpt_dir=None, now: Optional[float] = None
    ) -> Dict[str, int]:
        """Failover entry point: rebuild the session table from the
        latest COMPLETE checkpoint in ``ckpt_dir`` (default: this
        engine's ``checkpoint_dir``).  Returns ``{sid: consumed
        stages}`` — the stream position each client replays its feed
        from.  The restored carry equals the checkpointed carry exactly
        (full ``StreamState``), and chunk decode is deterministic, so
        replayed chunks re-emit byte-for-byte the bits the lost engine
        emitted after the checkpoint: delivery is idempotent and the
        total recovered output is bit-identical to uninterrupted
        ``decode_stream_chunked`` (asserted in tests/test_chaos.py and
        the chaos-smoke CI gate)."""
        from repro.core.decoder import StreamState
        from repro.runtime import checkpoint as ckpt

        now = time.monotonic() if now is None else now
        step, records, _extra = ckpt.load_sessions(
            ckpt_dir if ckpt_dir is not None else self.checkpoint_dir
        )
        resume: Dict[str, int] = {}
        for sid, recd in records.items():
            if sid in self._sessions:
                raise ValueError(f"session {sid!r} already open")
            self._decoder(recd["code"])  # validates the code name
            self._sessions[sid] = _Session(
                sid=sid,
                code=recd["code"],
                state=StreamState(
                    lam=jnp.asarray(recd["lam"]),
                    hist=jnp.asarray(recd["hist"]),
                    pos=int(recd["pos"]),
                ),
                pending=collections.deque(),
                last_used=now,
                consumed_steps=int(recd["consumed"]),
            )
            self._m_sessions.inc(1, event="restored")
            resume[sid] = int(recd["consumed"])
        self._m_open_sessions.set(len(self._sessions))
        if records:
            self.recorder.event(
                "engine.restore", step=step, sessions=len(records), now=now
            )
        return resume

    # -- convenience / stats ----------------------------------------------

    def decode(
        self, requests: List[DecodeRequest], now: float = 0.0
    ) -> List[np.ndarray]:
        """Submit + drain in one call; returns bits per request, in
        request order (the batch-oriented test/offline entry point)."""
        tickets = [self.submit(r, now=now) for r in requests]
        self.drain(now=now)
        if any(t.dropped for t in tickets):
            raise RuntimeError("backpressure drop inside decode()")
        errs = sorted({t.error for t in tickets if t.error})
        if errs:
            raise RuntimeError(f"typed errors inside decode(): {errs}")
        return [t.bits for t in tickets]

    def stats(self) -> dict:
        """Operator counters (schema documented in DESIGN.md §10).

        Since §12 every value is read back from ``self.registry`` —
        same keys, same numbers (the sojourn histograms keep a
        4096-observation exact window, so p50/p99 match the pre-§12
        deque percentiles exactly)."""
        real_frames = self._m_frames.total(kind="real")
        cell_frames = real_frames + self._m_frames.total(kind="pad")
        real_elems = self._m_elems.total(kind="real")
        cell_elems = real_elems + self._m_elems.total(kind="pad")
        lat = {}
        for slo in SLO_CLASSES:
            n = self._m_sojourn.count(slo=slo)
            if n:
                lat[slo] = {
                    "n": int(min(n, 4096)),  # the exact-window bound
                    "p50": float(self._m_sojourn.quantile(0.50, slo=slo)),
                    "p99": float(self._m_sojourn.quantile(0.99, slo=slo)),
                }
        paths: Dict[str, int] = {}
        for lbl, v in self._m_batches.series():
            p = lbl.get("path", "?")
            paths[p] = paths.get(p, 0) + int(v)
        faults: Dict[str, int] = {}
        for lbl, v in self._m_faults.series():
            kd = lbl.get("kind", "?")
            faults[kd] = faults.get(kd, 0) + int(v)
        qd = self.queue_depth()
        self._m_queue.set(qd)
        self._m_open_sessions.set(len(self._sessions))
        return {
            "submitted": int(self._m_requests.total(event="submitted")),
            "completed": int(self._m_requests.total(event="completed")),
            "rejected": int(self._m_requests.total(event="rejected")),
            "batches": int(self._m_batches.total()),
            "queue_depth": qd,
            "sessions": len(self._sessions),
            "sessions_evicted": int(
                self._m_sessions.value(event="evicted")
            ),
            "paths": paths,
            "occupancy": (
                real_frames / cell_frames if cell_frames else 0.0
            ),
            "padding_waste": (
                1.0 - real_elems / cell_elems if cell_elems else 0.0
            ),
            "jit_cache": {
                "hits": int(self._m_jit.value(event="hit")),
                "misses": int(self._m_jit.value(event="miss")),
                "entries": len(self._fns),
            },
            "latency": lat,
            # §13 fault-tolerance block (all zero on a healthy run)
            "faults": faults,
            "retries": int(self._m_retries.total()),
            "degraded": int(self._m_degraded.total()),
            "failovers": int(self._m_failover.total()),
            "expired": int(self._m_requests.total(event="expired")),
            "failed": int(self._m_requests.total(event="failed")),
            "errors": list(self.error_log),
            "checkpoints": int(self._m_ckpt.total()),
            # §14 data-integrity block (additive; zero/empty when the
            # scrubber is disabled and inputs are clean)
            "scrub": self.scrub.stats(),
            "quarantined": sorted(self._quarantined),
            "invalid": int(self._m_requests.total(event="invalid")),
            "sanitized": int(self._m_sanitized.total()),
        }
