"""Table I analog: decoder throughput per precision combination, plus the
serving-scenario matrix of the unified decoder front door.

Reproduces: paper Table I (precision sweep {C, channel} x {single, half},
reported in Gb/s on a V100) — here {carry, channel} x {f32, bf16} on the
tensor-ACS forward — and extends it with one row per decode scenario
(tiled / chunked-streaming / sharded / batch, DESIGN.md §6) and one row
per deployed standard (the code×rate grid, DESIGN.md §7: punctured
802.11a/DVB-S rates, LTE tail-biting WAVA, GSM).  Invocation:

    PYTHONPATH=src python -m benchmarks.bench_throughput
    PYTHONPATH=src python -m benchmarks.run --only throughput

CPU wall-times are NOT TPU predictions — the derived column reports
measured CPU Mb/s plus the v5e roofline-projected Gb/s from the dry-run
(experiments/dryrun), which is the deployable number.  The sharded row
uses every visible device (set
XLA_FLAGS=--xla_force_host_platform_device_count=N for a CPU demo).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import CODE_K7_CCSDS, AcsPrecision, TiledDecoderConfig
from repro.core.decoder import ViterbiDecoder
from repro.core.trellis import build_acs_tables
from repro.core.viterbi import blocks_from_llrs, forward_fused, init_metric

# row names come from AcsPrecision.label() so every knob that changes
# the compiled program (incl. split_dot) gets its own BENCH json row
COMBOS = [
    (p.label(), p)
    for p in (
        AcsPrecision(),
        AcsPrecision(matmul_dtype=jnp.bfloat16, channel_dtype=jnp.bfloat16),
        AcsPrecision(carry_dtype=jnp.bfloat16),
        AcsPrecision(matmul_dtype=jnp.bfloat16, carry_dtype=jnp.bfloat16,
                     channel_dtype=jnp.bfloat16),
        # §Perf C5: bf16 branch metrics + f32 metric routing — labelled
        # distinctly from the plain bf16 matmul row above
        AcsPrecision(matmul_dtype=jnp.bfloat16, channel_dtype=jnp.bfloat16,
                     split_dot=True),
    )
]


def bench_modes(
    n_streams: int = 16, stream_len: int = 4096, iters: int = 3
):
    """One row per decode scenario of the ViterbiDecoder front door
    (DESIGN.md §6): tiled windows, stateful chunked streaming, sharded
    multi-device, and one-shot batch — same code, same LLRs."""
    spec = CODE_K7_CCSDS
    key = jax.random.PRNGKey(1)
    llrs = jax.random.normal(key, (n_streams, stream_len, spec.beta))
    # validate_inputs is a host-side front-door check (§14) — it cannot
    # run under the jit wrappers below (traced bool), and benchmark
    # inputs are finite by construction
    decoder = ViterbiDecoder(
        spec, decision_depth=1024, validate_inputs=False
    )
    tcfg = TiledDecoderConfig()

    def run_tiled():
        return decoder.decode_streams_tiled(llrs, tcfg)

    def run_chunked():
        return decoder.decode_stream_chunked(
            llrs, chunk_len=1024, initial_state=None
        )

    def run_batch():
        return decoder.decode_batch(llrs, None, None)

    def run_sharded():
        from repro.distributed.decoder import sharded_decode_streams

        return sharded_decode_streams(llrs, spec, cfg=tcfg)

    n_dev = len(jax.devices())
    modes = [
        ("mode/tiled", jax.jit(run_tiled), ""),
        ("mode/chunked-streaming", run_chunked, ""),
        ("mode/batch", jax.jit(run_batch), ""),
        (f"mode/sharded-{n_dev}dev", run_sharded, f"{n_dev}dev"),
    ]
    rows = []
    decoded_bits = n_streams * stream_len
    for name, fn, note in modes:
        fn().block_until_ready()  # compile
        t0 = time.perf_counter()
        for _ in range(iters):
            fn().block_until_ready()
        dt = (time.perf_counter() - t0) / iters
        mbps = decoded_bits / dt / 1e6
        extra = f";{note}" if note else ""
        rows.append((name, dt * 1e6, f"{mbps:.1f}Mb/s-cpu{extra}"))
    return rows


def bench_standards(
    n_frames: int = 64, n_bits: int = 1024, iters: int = 3,
    grid=None, use_kernel: bool = False,
):
    """The code×rate grid (DESIGN.md §7): one row per deployed standard,
    decode_batch through ``ViterbiDecoder.from_standard`` — punctured
    rates decode the serial kept-LLR stream, tail-biting rows run the
    full WAVA circulations.  Mb/s counts MESSAGE bits."""
    import zlib

    import numpy as np

    from repro.codes import (
        REGISTRY, encode_standard, standard_llrs, tx_frames,
    )

    grid = grid or sorted(REGISTRY)
    rows = []
    for name in grid:
        code = REGISTRY[name]
        decoder = ViterbiDecoder.from_standard(name, use_kernel=use_kernel)
        # crc32, not hash(): stable across processes (PYTHONHASHSEED)
        key = jax.random.PRNGKey(zlib.crc32(name.encode()))
        kb, kn = jax.random.split(key)
        n = n_bits - (n_bits % decoder.rho)
        bits = jax.random.bernoulli(kb, 0.5, (n_frames, n)).astype(jnp.int32)
        llrs = standard_llrs(
            kn, encode_standard(tx_frames(bits, code, decoder.rho), code),
            6.0, code,
        )

        fn = jax.jit(lambda x, d=decoder: d.decode_batch(x))
        out = fn(llrs)
        out.block_until_ready()  # compile
        err = float((np.asarray(out)[:, :n] != np.asarray(bits)).mean())
        t0 = time.perf_counter()
        for _ in range(iters):
            fn(llrs).block_until_ready()
        dt = (time.perf_counter() - t0) / iters
        mbps = n_frames * n / dt / 1e6
        term = "tb" if code.termination == "tailbiting" else "zt"
        rows.append((
            f"std/{name}",
            dt * 1e6,
            f"{mbps:.1f}Mb/s-cpu;r={code.rate:.2f};{term};ber6dB={err:.1e}",
        ))
    return rows


def bench(n_frames: int = 2048, n_stages: int = 128, iters: int = 5):
    """Returns list of (name, us_per_call, derived) rows."""
    spec = CODE_K7_CCSDS
    tables = build_acs_tables(spec, rho=2)
    key = jax.random.PRNGKey(0)
    llrs = jax.random.normal(key, (n_frames, n_stages, spec.beta))
    rows = []
    decoded_bits = n_frames * n_stages
    for name, prec in COMBOS:
        blocks = blocks_from_llrs(
            llrs.astype(prec.channel_dtype).astype(jnp.float32), 2
        )
        lam0 = init_metric(n_frames, spec.n_states, None)

        def run():
            lam, phis = forward_fused(blocks, lam0, tables, prec)
            return lam.block_until_ready()

        run()  # compile
        t0 = time.perf_counter()
        for _ in range(iters):
            run()
        dt = (time.perf_counter() - t0) / iters
        mbps = decoded_bits / dt / 1e6
        rows.append(
            (f"tableI/{name}", dt * 1e6, f"{mbps:.1f}Mb/s-cpu")
        )
    rows += bench_modes(
        n_streams=max(4, n_frames // 128), stream_len=n_stages * 32
    )
    return rows


if __name__ == "__main__":
    for r in bench() + bench_standards():
        print(",".join(str(x) for x in r))
