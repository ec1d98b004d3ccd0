"""Benchmark orchestrator — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--only <suite>]

Prints ``name,us_per_call,derived`` CSV and, per executed suite, writes a
``BENCH_<suite>.json`` artifact (scenario -> rows with tokens/s, bytes
accessed where the suite measures them, and the tuned kernel configs) so
the perf trajectory is tracked across PRs:
  * bench_throughput — Table I (precision combos, decode throughput)
                       + serving-mode matrix (tiled/chunked/sharded/batch)
  * bench_ber        — Fig. 13 (BER vs Eb/N0 per precision, + hard/soft)
                       + the §11 Monte-Carlo farm: CI-bounded BER per
                       (code, Eb/N0, decode path) cell with the
                       statistical regression gate verdict
  * standards        — the code×rate grid (DESIGN.md §7): throughput +
                       BER rows for every registry standard (punctured
                       802.11a/DVB-S rates, LTE tail-biting WAVA, GSM)
  * bench_radix      — §V/§VIII-C (radix-2 vs radix-4 Q counts & timing)
  * bench_soft       — §15 soft-output cost: hard Viterbi vs BCJR LLRs
                       (XLA + Pallas log semiring) vs list-Viterbi vs
                       WAVA/circular-BCJR, with soft/hard cost ratios
  * bench_kernel     — Pallas ACS kernels vs oracle + survivor packing
                       + the one-pass HBM bytes-accessed report (§8)
  * bench_latency    — §9 single-stream latency: sequential scan vs
                       time-parallel (wall, HLO depth, modeled device
                       latency) over F x T
  * bench_engine     — §10 multi-tenant engine offered-load sweep:
                       p50/p99 virtual sojourn per SLO class, batch
                       occupancy + padding waste per load point
  * bench_chaos      — §13 fault-tolerance replay: the engine workload
                       under a deterministic kill schedule (device
                       failures, timeouts, stragglers, compile flakes)
                       with session checkpoint/failover — occupancy
                       ratio vs the no-chaos baseline, retry/failover
                       totals, recovered-session bit-exactness count
  * bench_scrub      — §14 SDC-scrubber cost + efficacy: engine replay
                       occupancy/wall ratios vs the no-scrub baseline
                       (the occ_ratio >= 0.9 @ rate 0.1 gate), seeded
                       bit_flip detection counts, per-frame syndrome
                       check cost
  * roofline_report  — §Roofline summary from the dry-run artifacts

Artifact schemas (column meanings, units, regeneration commands) are
documented in docs/BENCHMARKS.md.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import re
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]

_MBPS = re.compile(r"([0-9.]+)Mb/s")
# §V/§VIII-C radix-suite columns: the paper's Q tensor-op counts, the
# sequential-steps-per-frame analogue and the fused matmul dims — these
# rows previously reached the artifact with no lifted fields at all, so
# the radix trajectory was unrecorded
_Q = re.compile(r"Q=([0-9.]+)")
_STEPS = re.compile(r"steps=([0-9]+)")
_MATMUL = re.compile(r"matmul=([0-9]+)x([0-9]+)x([0-9]+)")
# §15 soft-suite column: per-variant cost ratio vs the hard baseline
_XHARD = re.compile(r"([0-9.]+)x-hard")
_BYTES = re.compile(r"bytes=([0-9]+)")
_MODELED = re.compile(r"modeled=([0-9.]+)us")
_DEPTH = re.compile(r"depth=([0-9]+)(?:->([0-9]+))?")
_SPEEDUP = re.compile(r"([0-9.]+)x-modeled")
_OCCUPANCY = re.compile(r"occupancy=([0-9.]+)")
_WASTE = re.compile(r"waste=([0-9.]+)")
_HIT_RATE = re.compile(r"hit_rate=([0-9.]+)")
_CELLS = re.compile(r"cells=([0-9]+)")
_P50 = re.compile(r"p50=([0-9.]+)ms")
_P99 = re.compile(r"p99=([0-9.]+)ms")
# §11 farm-suite columns: Clopper-Pearson CI bounds, raw integer
# counts, and the regression-gate verdict per (code, path, Eb/N0) cell
_BER = re.compile(r"ber=([0-9.e+-]+)")
_CI_LO = re.compile(r"lo=([0-9.e+-]+)")
_CI_HI = re.compile(r"hi=([0-9.e+-]+)")
_ERRORS = re.compile(r"errors=([0-9]+)")
_BITS = re.compile(r"bits=([0-9]+)")
_GATE = re.compile(r"gate=(pass|fail|ref)")
# §13 chaos-suite columns: post-failover occupancy ratio vs the
# no-chaos baseline (the >= 0.8 acceptance gate), injected-fault /
# retry / failover totals and the recovered-session bit-exactness count
_OCC_RATIO = re.compile(r"occ_ratio=([0-9.]+)")
_FAULTS = re.compile(r"faults=([0-9]+)")
_RETRIES = re.compile(r"retries=([0-9]+)")
_FAILOVERS = re.compile(r"failovers=([0-9]+)")
_RECOVERED = re.compile(r"recovered=([0-9]+)/([0-9]+)")
# §14 scrub-suite columns: wall-clock ratio vs the no-scrub baseline,
# corrupted-frames-detected counts, scrubber flag/false-alarm totals
_WALL_RATIO = re.compile(r"wall_ratio=([0-9.]+)")
_DETECTED = re.compile(r"detected=([0-9]+)/([0-9]+)")
_FALSE_ALARMS = re.compile(r"false_alarms=([0-9]+)")
_QUARANTINED = re.compile(r"quarantined=([0-9]+)")


def _artifact_rows(rows):
    """CSV rows -> JSON rows, lifting tokens/s, bytes and the latency
    suite's modeled/depth fields out of the derived column where a
    suite reports them."""
    out = []
    for name, us, derived in rows:
        row = {
            "name": str(name),
            "us_per_call": float(us),
            "derived": str(derived),
        }
        m = _MBPS.search(row["derived"])
        if m:  # decoded message bits per second == tokens/s for a decoder
            row["tokens_per_s"] = float(m.group(1)) * 1e6
        m = _BYTES.search(row["derived"])
        if m:
            row["bytes_accessed"] = int(m.group(1))
        m = _MODELED.search(row["derived"])
        if m:
            row["modeled_us"] = float(m.group(1))
        m = _DEPTH.search(row["derived"])
        if m:
            if m.group(2):  # "depth=A->B" on speedup summary rows
                row["seq_depth"] = int(m.group(1))
                row["tp_depth"] = int(m.group(2))
            else:  # a single row's own dependency depth
                row["depth"] = int(m.group(1))
        m = _SPEEDUP.search(row["derived"])
        if m:
            row["speedup_modeled"] = float(m.group(1))
        m = _Q.search(row["derived"])
        if m:  # paper §V/§VIII tensor ops per stage (16x16 fragments)
            row["q_per_stage"] = float(m.group(1))
        m = _STEPS.search(row["derived"])
        if m:
            row["seq_steps"] = int(m.group(1))
        m = _MATMUL.search(row["derived"])
        if m:
            row["matmul_m"] = int(m.group(1))
            row["matmul_k"] = int(m.group(2))
            row["matmul_n"] = int(m.group(3))
        m = _XHARD.search(row["derived"])
        if m:
            row["vs_hard_ratio"] = float(m.group(1))
        # §10 engine-suite columns: occupancy/waste per load point and
        # per-SLO virtual p50/p99 sojourn in milliseconds
        m = _OCCUPANCY.search(row["derived"])
        if m:
            row["occupancy"] = float(m.group(1))
        m = _WASTE.search(row["derived"])
        if m:
            row["padding_waste"] = float(m.group(1))
        m = _HIT_RATE.search(row["derived"])
        if m:  # §12 registry snapshot: jit-cache hit rate of the replay
            row["jit_hit_rate"] = float(m.group(1))
        m = _CELLS.search(row["derived"])
        if m:  # distinct (code, path, f, t) cells the registry saw
            row["cells"] = int(m.group(1))
        m = _P50.search(row["derived"])
        if m:
            row["p50_ms"] = float(m.group(1))
        m = _P99.search(row["derived"])
        if m:
            row["p99_ms"] = float(m.group(1))
        m = _BER.search(row["derived"])
        if m:
            row["ber"] = float(m.group(1))
        m = _CI_LO.search(row["derived"])
        if m:
            row["ci_lo"] = float(m.group(1))
        m = _CI_HI.search(row["derived"])
        if m:
            row["ci_hi"] = float(m.group(1))
        m = _ERRORS.search(row["derived"])
        if m:
            row["bit_errors"] = int(m.group(1))
        m = _BITS.search(row["derived"])
        if m:
            row["n_bits"] = int(m.group(1))
        m = _GATE.search(row["derived"])
        if m:
            row["gate"] = m.group(1)
        m = _OCC_RATIO.search(row["derived"])
        if m:
            row["occupancy_ratio"] = float(m.group(1))
        m = _FAULTS.search(row["derived"])
        if m:
            row["faults_injected"] = int(m.group(1))
        m = _RETRIES.search(row["derived"])
        if m:
            row["retries"] = int(m.group(1))
        m = _FAILOVERS.search(row["derived"])
        if m:
            row["failovers"] = int(m.group(1))
        m = _RECOVERED.search(row["derived"])
        if m:
            row["sessions_recovered"] = int(m.group(1))
            row["sessions_total"] = int(m.group(2))
        m = _WALL_RATIO.search(row["derived"])
        if m:
            row["wall_ratio"] = float(m.group(1))
        m = _DETECTED.search(row["derived"])
        if m:
            row["frames_detected"] = int(m.group(1))
            row["frames_corrupted"] = int(m.group(2))
        m = _FALSE_ALARMS.search(row["derived"])
        if m:
            row["false_alarms"] = int(m.group(1))
        m = _QUARANTINED.search(row["derived"])
        if m:
            row["devices_quarantined"] = int(m.group(1))
        if ";upper" in row["derived"]:
            row["upper_bound"] = True
        out.append(row)
    return out


def _run_meta() -> dict:
    """Provenance stamp shared by every BENCH_*.json artifact (schema in
    docs/BENCHMARKS.md): git SHA, ISO-8601 UTC timestamp, backend,
    platform and device count — so cross-PR perf trajectories know
    exactly which commit and host produced each point."""
    import datetime
    import platform
    import subprocess

    import jax

    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=REPO,
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or None
    except Exception:  # noqa: BLE001 — no git / not a checkout
        sha = None
    return {
        "git_sha": sha,
        "timestamp": datetime.datetime.now(
            datetime.timezone.utc
        ).isoformat(timespec="seconds"),
        "backend": jax.default_backend(),
        "platform": platform.platform(),
        "python": platform.python_version(),
        "device_count": jax.device_count(),
    }


def _write_artifact(suite: str, rows, fast: bool, out_dir: pathlib.Path):
    import jax

    from repro.configs import viterbi_k7 as vit

    artifact = {
        "suite": suite,
        "fast": fast,
        "backend": jax.default_backend(),
        "meta": _run_meta(),
        "kernel_configs": {
            name: {
                "block_frames": kc.block_frames,
                "time_tile": kc.time_tile,
                "pack_survivors": kc.pack_survivors,
                "matmul_dtype": kc.matmul_dtype,
                "transfer_tile": kc.transfer_tile,
            }
            for name, kc in vit.KERNEL_CONFIGS.items()
        },
        "rows": _artifact_rows(rows),
    }
    path = out_dir / f"BENCH_{suite}.json"
    path.write_text(json.dumps(artifact, indent=2))
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="smaller workloads")
    ap.add_argument("--only", default=None)
    ap.add_argument(
        "--out-dir", default=str(REPO),
        help="where BENCH_<suite>.json artifacts land (default: repo root)",
    )
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    from benchmarks import (
        bench_ber,
        bench_chaos,
        bench_engine,
        bench_kernel,
        bench_latency,
        bench_radix,
        bench_scrub,
        bench_soft,
        bench_throughput,
        roofline_report,
    )

    suites = {
        "throughput": lambda: bench_throughput.bench(
            n_frames=512 if args.fast else 2048,
            n_stages=64 if args.fast else 128,
        ),
        "ber": lambda: bench_ber.bench(
            ebn0_dbs=(3.0, 4.0) if args.fast else (2.0, 3.0, 4.0),
            n_bits=50_000 if args.fast else 400_000,
        ) + bench_ber.bench_farm(
            codes=("ccsds-k7", "lte-tbcc") if args.fast else (
                "ccsds-k7", "wifi-11a-r34", "lte-tbcc", "gsm-cs1"
            ),
            ebn0_dbs=(3.0, 6.0) if args.fast else (3.0, 4.5, 6.0),
            paths=("reference", "kernel", "time_parallel") if args.fast
            else ("reference", "kernel", "time_parallel", "engine"),
            frames_per_point=32 if args.fast else 128,
        ),
        "standards": lambda: bench_throughput.bench_standards(
            n_frames=8 if args.fast else 64,
            n_bits=256 if args.fast else 1024,
        ) + bench_ber.bench_standards(
            ebn0_dbs=(6.0,) if args.fast else (4.0, 6.0),
            n_bits=4_000 if args.fast else 40_000,
        ),
        "radix": lambda: bench_radix.bench(
            n_frames=256 if args.fast else 1024,
            n_stages=128 if args.fast else 256,
        ),
        "soft": lambda: bench_soft.bench(
            n_frames=64 if args.fast else 256,
            n_stages=128 if args.fast else 512,
        ),
        "kernel": lambda: bench_kernel.bench(
            n_frames=128 if args.fast else 512,
            n_stages=32 if args.fast else 64,
        ),
        "latency": lambda: bench_latency.bench(
            t_stages=(1 << 13, 1 << 15) if args.fast else (1 << 16, 1 << 19),
            n_frames=(1, 4) if args.fast else (1, 4, 16),
        ),
        "engine": lambda: bench_engine.bench(
            n_requests=240 if args.fast else 600,
            base_len=256 if args.fast else 512,
            max_batch=16 if args.fast else 32,
        ),
        "chaos": lambda: bench_chaos.bench(
            n_requests=120 if args.fast else 240,
            base_len=256,
            max_batch=16,
            n_chunks=3 if args.fast else 4,
        ),
        "scrub": lambda: bench_scrub.bench(
            n_requests=120 if args.fast else 240,
            base_len=256,
            max_batch=16,
            n_frames=8 if args.fast else 16,
        ),
        "roofline": roofline_report.bench,
    }
    out_dir = pathlib.Path(args.out_dir)
    print("name,us_per_call,derived")
    failed = 0
    for name, fn in suites.items():
        if args.only and name != args.only:
            continue
        try:
            rows = list(fn())
            for row in rows:
                print(",".join(str(x) for x in row))
                sys.stdout.flush()
        except Exception as e:  # noqa: BLE001
            failed += 1
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}")
            continue
        try:  # artifact I/O must not report a green suite as failed
            path = _write_artifact(name, rows, args.fast, out_dir)
            print(f"# wrote {path}")
        except Exception as e:  # noqa: BLE001
            print(f"# artifact write failed for {name}: {e}")
    if failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
