"""Sharded Monte-Carlo BER farm (DESIGN.md §11).

``BerFarm`` fans a (registry code × Eb/N0 × decode path) grid out over
the device mesh.  Every grid point draws its frames from the
deterministic per-batch key schedule of ``codes.simulate.batch_keys``:
batch ``b`` of a point is the same noise realization no matter which
shard decodes it, or which DECODE PATH consumes it — so the sharded
farm's aggregate error counts equal the single-device counts exactly
(integer sums of identical per-batch counts), and path-vs-reference
comparisons (repro.verify.gate) happen at matched noise.

Execution shapes:

  * **jit paths** (``reference``, ``time_parallel``) — the whole point
    runs as one ``lax.scan`` over batch keys (generate -> encode ->
    AWGN -> decode -> count, a streaming integer reducer with a
    constant working set); with a mesh, the scan runs per shard under
    ``shard_map`` with the key axis sharded, one (2,) int32 count
    vector per device coming home.
  * **host paths** (``kernel`` one-pass streaming §8, ``engine``
    routing §10, ``sharded`` §6) — drivers with Python-level control
    flow iterate the SAME key schedule batch by batch; counts
    accumulate in Python ints (unbounded, exact).

Totals are Python ints everywhere above the per-scan int32 partials, so
a nightly million-frame grid cannot overflow.  Each point reports
Wilson/Clopper-Pearson confidence intervals through
``repro.core.ber.estimate_ber`` — a zero-error cell reports its
one-sided upper bound, never 0.0.

CLI (the CI ``ber-gate`` job; exits 1 on any gate failure)::

    PYTHONPATH=src python -m repro.verify.farm            # smoke grid
    PYTHONPATH=src python -m repro.verify.farm --full     # nightly grid
    PYTHONPATH=src python -m repro.verify.farm --frames 1000000 --full
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.codes.registry import StandardCode, get_code
from repro.codes.simulate import batch_keys, count_errors, sim_frame_batch
from repro.core.ber import DEFAULT_CONFIDENCE, BerEstimate, estimate_ber
from repro.core.decoder import ViterbiDecoder

__all__ = ["PATHS", "FarmPoint", "BerFarm", "farm_to_json", "main"]

# decode paths the farm can measure; "reference" is the gate's baseline
PATHS = ("reference", "kernel", "time_parallel", "engine", "sharded")
_JIT_PATHS = frozenset({"reference", "time_parallel"})

# streaming decision depth of the kernel path's decoder (stages): one of
# the statistical knobs the farm exists to price — deliberately far
# below the 5120-stage serving default so the farm would CATCH a depth
# regression, while >= 70 constraint lengths keeps it clean at any
# operating SNR
KERNEL_DECISION_DEPTH = 512


@dataclasses.dataclass(frozen=True)
class FarmPoint:
    """Aggregated counts of one (code, Eb/N0, path) grid cell."""

    code: str
    path: str
    ebn0_db: float
    n_frames: int
    frame_bits: int  # message bits per frame
    n_bits: int      # total message bits scored ( = n_frames * frame_bits)
    bit_errors: int
    frame_errors: int
    confidence: float = DEFAULT_CONFIDENCE
    seconds: float = dataclasses.field(default=0.0, compare=False)

    def estimate(self, method: str = "clopper-pearson") -> BerEstimate:
        """Confidence-bounded BER of this cell (DESIGN.md §11)."""
        return estimate_ber(
            self.bit_errors, self.n_bits,
            confidence=self.confidence, method=method,
        )

    @property
    def fer(self) -> float:
        return self.frame_errors / max(self.n_frames, 1)


def _message_bits(code: StandardCode, frame_budget: int) -> int:
    """Message bits per frame for a transmit budget of ``frame_budget``
    trellis stages: tail-biting frames spend every stage on message
    bits; zero-terminated codes spend k-1 on the flush tail.  A
    power-of-two budget keeps every code on the same stage count —
    power-of-two transfer tiles for the §9 path, exact engine cell
    rungs for the §10 path."""
    if frame_budget % 2:
        raise ValueError(f"frame_budget must be even, got {frame_budget}")
    if code.termination == "tailbiting":
        return frame_budget
    n = frame_budget - (code.spec.k - 1)
    if n <= 0:
        raise ValueError(
            f"frame_budget={frame_budget} cannot fit the k-1="
            f"{code.spec.k - 1} tail of {code.name}"
        )
    return n


class BerFarm:
    """The sharded Monte-Carlo farm (DESIGN.md §11; module docstring).

    Parameters
    ----------
    codes            : registry code names of the grid.
    ebn0_dbs         : Eb/N0 grid points, dB (calibrated per EFFECTIVE
                       rate, so punctured codes are honest).
    paths            : decode paths to measure (subset of ``PATHS``).
    frames_per_point : frames per grid cell (rounded up to whole
                       batches, and to whole per-shard batch counts
                       when a mesh is given — the ACTUAL count is in
                       each FarmPoint).
    frame_budget     : transmit stages per frame (message bits =
                       budget - (k-1) for zero-terminated codes).
    batch_frames     : frames per Monte-Carlo batch (the scan step).
    mesh             : optional 1-D ``jax.sharding.Mesh`` — jit paths
                       shard the batch-key axis across it.
    scan_chunk       : max batches per device scan; whole-point counts
                       accumulate across chunks in Python ints.
    recorder         : optional ``obs.SpanRecorder`` — each grid point
                       runs inside a ``farm.point`` span that emits
                       ``farm.progress`` events per scan chunk
                       (frames/s, errors so far, Wilson CI width); the
                       zero-cost ``NullRecorder`` by default
                       (DESIGN.md §12).
    """

    def __init__(
        self,
        codes: Sequence[str],
        ebn0_dbs: Sequence[float],
        paths: Sequence[str] = ("reference",),
        frames_per_point: int = 1024,
        frame_budget: int = 256,
        batch_frames: int = 32,
        seed: int = 0,
        confidence: float = DEFAULT_CONFIDENCE,
        mesh=None,
        axis: str = "shards",
        kernel_decision_depth: int = KERNEL_DECISION_DEPTH,
        scan_chunk: int = 4096,
        recorder=None,
    ):
        from repro.obs.trace import NullRecorder

        self.recorder = recorder if recorder is not None else NullRecorder()
        unknown = [p for p in paths if p not in PATHS]
        if unknown:
            raise ValueError(f"unknown decode paths {unknown}; known {PATHS}")
        self.codes = [get_code(c).name for c in codes]  # validate names
        self.ebn0_dbs = [float(e) for e in ebn0_dbs]
        self.paths = tuple(paths)
        self.frame_budget = int(frame_budget)
        self.batch_frames = int(batch_frames)
        self.seed = int(seed)
        self.confidence = float(confidence)
        self.mesh = mesh
        self.axis = axis
        self.kernel_decision_depth = int(kernel_decision_depth)
        n_shards = 1 if mesh is None else mesh.shape[axis]
        n_batches = -(-int(frames_per_point) // self.batch_frames)
        self.n_batches = -(-n_batches // n_shards) * n_shards
        self.scan_chunk = -(-int(scan_chunk) // n_shards) * n_shards
        self._decoders: Dict[Tuple[str, str], object] = {}
        self._engine = None

    # -- decode-path factory ----------------------------------------------

    def _decoder(self, code_name: str, path: str) -> ViterbiDecoder:
        key = (code_name, path)
        if key not in self._decoders:
            kw = {}
            if path == "kernel":
                kw = dict(
                    use_kernel=True,
                    decision_depth=self.kernel_decision_depth,
                )
            elif path == "time_parallel":
                kw = dict(time_parallel=True)
            self._decoders[key] = ViterbiDecoder.from_standard(
                code_name, **kw
            )
        return self._decoders[key]

    def _engine_obj(self):
        if self._engine is None:
            from repro.serve.engine import DecodeEngine

            self._engine = DecodeEngine(max_batch=self.batch_frames)
        return self._engine

    def decode_fn(self, code_name: str, path: str):
        """(F, n, beta) | serial (F, Lp) llrs -> (F, >= message bits)
        decoded bits, on the named path.  Zero-terminated paths pin both
        trellis ends (the tx chain flushed to state 0); the engine path
        keeps its own §10 contract (argmax at both ends)."""
        code = get_code(code_name)
        tailbiting = code.termination == "tailbiting"
        if path == "engine":
            from repro.serve.engine import DecodeRequest

            engine = self._engine_obj()

            def engine_fn(llrs):
                arr = np.asarray(llrs)
                # farm frames carry their zero tail (sim_frame_batch ->
                # tx_frames), so declare the §10 flushed framing
                reqs = [
                    DecodeRequest(
                        llrs=arr[i], code=code_name,
                        flushed=not tailbiting,
                    )
                    for i in range(arr.shape[0])
                ]
                return jnp.asarray(np.stack(engine.decode(reqs)))

            return engine_fn
        dec = self._decoder(code_name, path)
        if tailbiting:
            if path == "sharded":
                raise ValueError(
                    f"{code_name}: sharded tail-biting decode is not "
                    "implemented (DESIGN.md §6) — drop 'sharded' from "
                    "the farm paths for tail-biting codes"
                )
            if path == "time_parallel":
                return lambda llrs: dec.decode_tailbiting(
                    llrs, time_parallel=True
                )[0]
            return lambda llrs: dec.decode_tailbiting(llrs)[0]
        if path == "kernel":
            return lambda llrs: dec.decode_stream_chunked(
                llrs, initial_state=0, final_state=0
            )
        if path == "sharded":
            return lambda llrs: dec.decode_sharded(
                llrs, initial_state=0, final_state=0
            )
        if path == "time_parallel":
            return lambda llrs: dec.decode_batch(
                llrs, initial_state=0, final_state=0, time_parallel=True
            )
        return lambda llrs: dec.decode_batch(
            llrs, initial_state=0, final_state=0, time_parallel=False
        )

    # -- point runners -----------------------------------------------------

    def _counts_jit(self, decode, code, n_msg, ebn0_db, keys):
        """One sharded scan over ``keys``: per-shard streaming int32
        reduction, host-summed to Python ints."""
        bf = self.batch_frames

        def body(carry, key):
            bits, llrs = sim_frame_batch(
                key, code, bf, n_msg, ebn0_db, rho=2
            )
            be, fe = count_errors(decode(llrs), bits)
            return (carry[0] + be, carry[1] + fe), None

        def local(keys_loc):
            tot, _ = jax.lax.scan(
                body, (jnp.int32(0), jnp.int32(0)), keys_loc
            )
            return jnp.stack(tot)[None]  # (1, 2) per shard

        if self.mesh is None:
            out = np.asarray(jax.jit(local)(keys))
        else:
            fn = jax.jit(
                jax.shard_map(
                    local, mesh=self.mesh,
                    in_specs=P(self.axis), out_specs=P(self.axis),
                    check_vma=False,
                )
            )
            out = np.asarray(fn(keys))
        return int(out[:, 0].sum()), int(out[:, 1].sum())

    def _counts_host(self, decode, code, n_msg, ebn0_db, keys):
        """Host-driver paths: same key schedule, batch-by-batch."""
        bf = self.batch_frames

        def sim(key):
            return sim_frame_batch(key, code, bf, n_msg, ebn0_db, rho=2)

        sim = jax.jit(sim)
        be = fe = 0
        for i in range(keys.shape[0]):
            bits, llrs = sim(keys[i])
            b, f = count_errors(decode(llrs), bits)
            be += int(b)
            fe += int(f)
        return be, fe

    def run_point(self, code_name: str, ebn0_db: float, path: str
                  ) -> FarmPoint:
        """Measure one grid cell; the unit the grid loop and the tests
        share."""
        code = get_code(code_name)
        n_msg = _message_bits(code, self.frame_budget)
        decode = self.decode_fn(code_name, path)
        keys = batch_keys(self.seed, code_name, ebn0_db, self.n_batches)
        runner = self._counts_jit if path in _JIT_PATHS else (
            self._counts_host
        )
        t0 = time.perf_counter()
        be = fe = 0
        with self.recorder.span(
            "farm.point", code=code_name, path=path, ebn0_db=float(ebn0_db),
            n_frames=self.n_batches * self.batch_frames, frame_bits=n_msg,
        ) as sp:
            for lo in range(0, self.n_batches, self.scan_chunk):
                b, f = runner(
                    decode, code, n_msg, ebn0_db,
                    keys[lo: lo + self.scan_chunk],
                )
                be += b
                fe += f
                frames = min(
                    lo + self.scan_chunk, self.n_batches
                ) * self.batch_frames
                elapsed = time.perf_counter() - t0
                est = estimate_ber(
                    be, frames * n_msg,
                    confidence=self.confidence, method="wilson",
                )
                sp.event(
                    "farm.progress",
                    frames=frames,
                    frames_per_s=frames / elapsed if elapsed > 0 else 0.0,
                    bit_errors=be,
                    frame_errors=fe,
                    ber=est.ber,
                    wilson_ci_width=est.ci_hi - est.ci_lo,
                )
            sp.set(bit_errors=be, frame_errors=fe)
        dt = time.perf_counter() - t0
        n_frames = self.n_batches * self.batch_frames
        return FarmPoint(
            code=code_name, path=path, ebn0_db=float(ebn0_db),
            n_frames=n_frames, frame_bits=n_msg,
            n_bits=n_frames * n_msg,
            bit_errors=be, frame_errors=fe,
            confidence=self.confidence, seconds=dt,
        )

    def run(self, progress=None) -> List[FarmPoint]:
        """The full grid, reference path first (so gate pairing always
        finds its baseline).  ``progress`` is an optional callable fed
        each finished FarmPoint (the CLI prints rows live with it)."""
        ordered = sorted(self.paths, key=lambda p: p != "reference")
        points = []
        for path in ordered:
            for code_name in self.codes:
                for ebn0_db in self.ebn0_dbs:
                    p = self.run_point(code_name, ebn0_db, path)
                    if progress is not None:
                        progress(p)
                    points.append(p)
        return points


# ---------------------------------------------------------------------------
# Serialization + CLI (the CI ber-gate job)
# ---------------------------------------------------------------------------

def farm_to_json(points: Sequence[FarmPoint], verdicts=None) -> dict:
    """Counts, CIs and gate verdicts as one JSON-able trajectory object
    (schema documented in docs/BENCHMARKS.md)."""
    rows = []
    for p in points:
        est = p.estimate()
        rows.append(
            {
                "code": p.code, "path": p.path, "ebn0_db": p.ebn0_db,
                "n_frames": p.n_frames, "frame_bits": p.frame_bits,
                "n_bits": p.n_bits, "bit_errors": p.bit_errors,
                "frame_errors": p.frame_errors, "fer": p.fer,
                "ber": est.ber, "ci_lo": est.ci_lo, "ci_hi": est.ci_hi,
                "confidence": est.confidence, "method": est.method,
                "upper_bound": est.upper_bound, "seconds": p.seconds,
            }
        )
    out = {"points": rows}
    if verdicts is not None:
        out["gate"] = [
            {
                "code": v.code, "path": v.path, "ebn0_db": v.ebn0_db,
                "passed": v.passed, "reason": v.reason,
            }
            for v in verdicts
        ]
        out["all_pass"] = all(v.passed for v in verdicts)
    return out


def _point_row(p: FarmPoint) -> str:
    est = p.estimate()
    return (
        f"{p.code}/{p.path}@ebn0={p.ebn0_db:g} "
        f"ber={est.ber:.3e} ci=[{est.ci_lo:.3e},{est.ci_hi:.3e}] "
        f"errors={p.bit_errors}/{p.n_bits}"
        f"{' (upper bound)' if est.upper_bound else ''} "
        f"fer={p.fer:.3e} [{p.seconds:.1f}s]"
    )


def main(argv=None) -> int:
    """The ber-gate CLI: smoke grid by default (CI-sized, minutes on a
    small CPU host), ``--full`` for the nightly grid — scale ``--frames``
    up for millions-of-frames runs."""
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--full", action="store_true",
                    help="nightly grid: all farm codes + engine path")
    ap.add_argument("--codes", default=None,
                    help="comma-separated registry codes (overrides grid)")
    ap.add_argument("--ebn0", default=None,
                    help="comma-separated Eb/N0 points, dB")
    ap.add_argument("--paths", default=None,
                    help=f"comma-separated decode paths from {PATHS}")
    ap.add_argument("--frames", type=int, default=None,
                    help="frames per grid point")
    ap.add_argument("--frame-budget", type=int, default=256,
                    help="transmit stages per frame")
    ap.add_argument("--batch-frames", type=int, default=16,
                    help="frames per Monte-Carlo batch")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--confidence", type=float, default=DEFAULT_CONFIDENCE)
    ap.add_argument("--out", default=None,
                    help="write the JSON trajectory artifact here")
    ap.add_argument(
        "--progress", action="store_true",
        help="emit per-point farm.point spans with farm.progress "
        "events (frames/s, errors so far, Wilson CI width) to the "
        "--trace-out JSONL (DESIGN.md §12)",
    )
    ap.add_argument(
        "--trace-out", default="experiments/obs/farm.jsonl",
        help="JSONL file the --progress span events append to",
    )
    args = ap.parse_args(argv)

    if args.full:
        codes = "ccsds-k7,wifi-11a-r34,lte-tbcc,gsm-cs1"
        paths = "reference,kernel,time_parallel,engine"
        frames = 4096
    else:
        codes = "ccsds-k7,wifi-11a-r34"
        paths = "reference,kernel,time_parallel"
        frames = 32
    ebn0 = args.ebn0 or "2,4,6"
    recorder = None
    if args.progress:
        from repro.obs import JsonlSink, SpanRecorder

        recorder = SpanRecorder(sink=JsonlSink(args.trace_out))
    farm = BerFarm(
        codes=(args.codes or codes).split(","),
        ebn0_dbs=[float(e) for e in ebn0.split(",")],
        paths=tuple((args.paths or paths).split(",")),
        frames_per_point=args.frames or frames,
        frame_budget=args.frame_budget,
        batch_frames=args.batch_frames,
        seed=args.seed,
        confidence=args.confidence,
        recorder=recorder,
    )
    print(
        f"ber-farm: {len(farm.codes)} codes x {len(farm.ebn0_dbs)} Eb/N0 "
        f"x {len(farm.paths)} paths, "
        f"{farm.n_batches * farm.batch_frames} frames/point"
    )
    points = farm.run(progress=lambda p: print(_point_row(p), flush=True))

    from .gate import run_gate

    verdicts = run_gate(points)
    failed = [v for v in verdicts if not v.passed]
    for v in verdicts:
        print(f"gate {'PASS' if v.passed else 'FAIL'} {v.label}: {v.reason}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(farm_to_json(points, verdicts), f, indent=2)
        print(f"wrote {args.out}")
    if recorder is not None:
        recorder.close()
        print(f"progress spans -> {args.trace_out}")
    print(
        f"ber-gate: {len(verdicts) - len(failed)}/{len(verdicts)} pass"
    )
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
