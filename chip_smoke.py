#!/usr/bin/env python3
"""Chip smoke test: the decoder's main path, once, at full size, on a TPU.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py             # phases a-d on one chip
    python chip_smoke.py --chips 4   # the multi-chip phase only

One process does everything; it starts no child process.  Phases, each
at the size users run:

  a. batch decode through the two-pass ACS kernel — ccsds-k7, 512
     streams x 65536 stages at Eb/N0 = 4 dB from a seeded ChannelStream:
     bit-identical to the XLA decode, BER <= 1e-3, two frames equal to
     the scalar reference decoder (core/viterbi_ref.py);
  b. chunked streaming through the one-pass kernel, driven by
     ``repro.launch.serve``'s viterbi service (``--use-kernel --mode
     chunked``) at the default decision depth: equal to the XLA chunked
     path, and no chunk took the two-pass fallback;
  c. time-parallel decode through the transfer-matrix kernel, 1 and 4
     frames x 65536 stages: equal to the sequential decode;
  d. the multi-tenant DecodeEngine with kernels on: stream, batch,
     time-parallel, WAVA and soft cells, every ticket equal to a direct
     decode, no fault and no degradation.

With ``--chips 4`` only the multi-chip phase runs: frame-sharded batch
decode, sharded one-pass stream decode and time-sharded decode on a
4-chip mesh, each equal to the single-device decode, with every output
shard on its own chip.

In every kernel phase the lowered decode must hold a ``tpu_custom_call``
(Mosaic ran; no interpret mode).  Each phase prints one line of labelled
observations (shape, compile seconds, wall seconds, check result).  The
last line of standard output is the JSON result; a failed check exits
non-zero without printing it, and so does a machine without a TPU (no
CPU fallback) or a directory without the repo's ``src/``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

SPEC_NAME = "ccsds-k7"
EBN0_DB = 4.0
MAX_BER = 1e-3


class SmokeFailure(AssertionError):
    """A check of the smoke test failed."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _ready(x):
    import jax

    return jax.block_until_ready(x)


def _compile(fn, *args, require_mosaic: bool):
    """jit + lower + compile ``fn`` at ``args``; checks the lowered text
    for the Mosaic custom call.  Returns (compiled, compile seconds)."""
    import jax

    t0 = time.perf_counter()
    lowered = jax.jit(fn).lower(*args)
    if require_mosaic:
        check(
            "tpu_custom_call" in lowered.as_text(),
            "lowered decode holds no tpu_custom_call (kernel not on Mosaic)",
        )
    compiled = lowered.compile()
    return compiled, time.perf_counter() - t0


def _timed(fn, *args):
    t0 = time.perf_counter()
    out = _ready(fn(*args))
    return out, time.perf_counter() - t0


def _report(phase: str, what: str, shape: str, compile_s: float,
            wall_s: float, checks: str) -> None:
    print(
        f"phase {phase} ({what}): shape={shape} compile_s={compile_s:.3f} "
        f"wall_s={wall_s:.3f} check=ok [{checks}]",
        flush=True,
    )


def _channel(n_frames: int, n_stages: int, seed: int):
    """(bits (F, n), llrs (F, n, beta)) generated on the device."""
    from repro.core import CODE_K7_CCSDS
    from repro.data.pipeline import ChannelStream

    src = ChannelStream(
        spec=CODE_K7_CCSDS, n_streams=n_frames, stream_len=n_stages,
        ebn0_db=EBN0_DB, seed=seed,
    )
    return src.batch_at(0)


def phase_batch(n_frames=512, n_stages=65536, n_ref_frames=2, seed=0,
                require_mosaic=True):
    """a. ``ViterbiDecoder(use_kernel=True).decode_batch`` (two-pass)."""
    import numpy as np

    from repro.core import ViterbiDecoder
    from repro.core.viterbi_ref import viterbi_decode_ref

    bits, llrs = _ready(_channel(n_frames, n_stages, seed))
    dec_k = ViterbiDecoder.from_standard(SPEC_NAME, use_kernel=True)
    dec_x = ViterbiDecoder.from_standard(SPEC_NAME)
    run_k, compile_s = _compile(
        lambda x: dec_k.decode_batch(x, initial_state=0, time_parallel=False),
        llrs, require_mosaic=require_mosaic,
    )
    run_x, _ = _compile(
        lambda x: dec_x.decode_batch(x, initial_state=0, time_parallel=False),
        llrs, require_mosaic=False,
    )
    out_k, wall_s = _timed(run_k, llrs)
    out_x = _ready(run_x(llrs))
    out_k, out_x, bits = map(np.asarray, (out_k, out_x, bits))
    n_diff = int((out_k != out_x).sum())
    check(n_diff == 0, f"kernel decode differs from XLA in {n_diff} bits")
    ber = float((out_k != bits).mean())
    check(ber <= MAX_BER, f"BER {ber:.3e} > {MAX_BER}")
    llrs_np = np.asarray(llrs)
    for f in range(n_ref_frames):
        ref = viterbi_decode_ref(llrs_np[f], dec_k.spec)
        check(np.array_equal(out_k[f], ref),
              f"frame {f} differs from viterbi_ref")
    _report(
        "a", "batch, two-pass kernel", f"{n_frames}x{n_stages}",
        compile_s, wall_s,
        f"== XLA decode; BER={ber:.3e}; {n_ref_frames} frames == viterbi_ref",
    )
    return dict(ber=ber)


def phase_chunked(n_streams=512, stream_len=65536, chunk_len=4096,
                  decision_depth=None, require_mosaic=True):
    """b. ``repro.launch.serve`` viterbi service, ``--mode chunked``."""
    import numpy as np

    from repro.core.decoder import StreamState
    from repro.launch.serve import build_parser, viterbi_service
    from repro.obs import MetricsRegistry, set_default_registry
    from repro.serve.step import make_viterbi_decoder

    argv = [
        "--service", "viterbi", "--mode", "chunked",
        "--streams", str(n_streams), "--stream-len", str(stream_len),
        "--chunk-len", str(chunk_len), "--ebn0", str(EBN0_DB),
    ]
    if decision_depth is not None:
        argv += ["--decision-depth", str(decision_depth)]
    args_k = build_parser().parse_args(argv + ["--use-kernel"])
    args_x = build_parser().parse_args(argv)
    run_k, src = viterbi_service(args_k)
    run_x, _ = viterbi_service(args_x)
    bits, llrs = _ready(src.batch_at(0))

    # the Mosaic check and compile time of the chunk program the
    # service's decoder dispatches (same config, same depth)
    from repro.configs.viterbi_k7 import CONFIG

    dec = make_viterbi_decoder(
        CONFIG, use_kernel=True, decision_depth=decision_depth
    )
    state = dec.init_stream_state(n_streams)
    chunk = llrs[:, :chunk_len]
    _, compile_s = _compile(  # a chunk past the warmup: it emits bits
        lambda lam, hist, x: dec.decode_chunk(
            StreamState(lam=lam, hist=hist, pos=hist.shape[0]), x
        )[1],
        state.lam, state.hist, chunk, require_mosaic=require_mosaic,
    )

    reg = MetricsRegistry()
    prev = set_default_registry(reg)
    try:
        _ready(run_k(llrs))  # warm: compiles the chunk programs
        out_k, wall_s = _timed(run_k, llrs)
    finally:
        set_default_registry(prev)
    out_x = _ready(run_x(llrs))
    dispatch = reg.counter("decoder_dispatch_total")
    two_pass = dispatch.value(path="chunk_two_pass")
    one_pass = dispatch.value(path="chunk_one_pass")
    check(two_pass == 0, f"{two_pass:.0f} chunks took the two-pass path")
    check(one_pass > 0, "no chunk took the one-pass kernel")
    out_k, out_x, bits = map(np.asarray, (out_k, out_x, bits))
    n_diff = int((out_k != out_x).sum())
    check(n_diff == 0, f"one-pass stream differs from XLA in {n_diff} bits")
    ber = float((out_k != bits).mean())
    check(ber <= MAX_BER, f"BER {ber:.3e} > {MAX_BER}")
    _report(
        "b", "chunked stream, one-pass kernel",
        f"{n_streams}x{stream_len} chunk={chunk_len} "
        f"depth={dec.decision_depth}",
        compile_s, wall_s,
        f"== XLA chunked; BER={ber:.3e}; chunk_one_pass={one_pass:.0f} "
        f"chunk_two_pass=0",
    )
    return dict(ber=ber, one_pass=one_pass)


def phase_time_parallel(frame_counts=(1, 4), n_stages=65536, seed=2,
                        require_mosaic=True):
    """c. time-parallel decode through ``transfer_matrix_pallas``."""
    import jax
    import numpy as np

    from repro.core import ViterbiDecoder

    dec_k = ViterbiDecoder.from_standard(SPEC_NAME, use_kernel=True)
    dec_x = ViterbiDecoder.from_standard(SPEC_NAME)
    run_seq = jax.jit(
        lambda x: dec_x.decode_batch(x, initial_state=0, time_parallel=False)
    )
    for n_frames in frame_counts:
        bits, llrs = _ready(_channel(n_frames, n_stages, seed + n_frames))
        run_tp, compile_s = _compile(
            lambda x: dec_k.decode_batch(
                x, initial_state=0, time_parallel=True
            ),
            llrs, require_mosaic=require_mosaic,
        )
        out_tp, wall_s = _timed(run_tp, llrs)
        out_seq = _ready(run_seq(llrs))
        out_tp, out_seq, bits = map(np.asarray, (out_tp, out_seq, bits))
        n_diff = int((out_tp != out_seq).sum())
        check(n_diff == 0,
              f"time-parallel differs from sequential in {n_diff} bits")
        ber = float((out_tp != bits).mean())
        check(ber <= MAX_BER, f"BER {ber:.3e} > {MAX_BER}")
        _report(
            "c", "time-parallel, transfer-matrix kernel",
            f"{n_frames}x{n_stages}", compile_s, wall_s,
            f"== sequential decode; BER={ber:.3e}",
        )


# the engine's tenants: (route they must take, registry code, SLO class)
ENGINE_TENANTS = (
    ("stream", SPEC_NAME, "throughput"),
    ("batch", SPEC_NAME, "throughput"),
    ("time_parallel", "wifi-11a-r34", "latency"),
    ("wava", "lte-tbcc", "latency"),
    ("soft", SPEC_NAME, "soft"),
)


def _engine_requests(counts, lengths, seed):
    """(route, DecodeRequest) pairs through each standard's tx chain."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.codes import encode_standard, get_code, standard_llrs
    from repro.serve.engine import DecodeRequest

    reqs = []
    for t, (path, code_name, slo) in enumerate(ENGINE_TENANTS):
        code = get_code(code_name)
        rng = np.random.default_rng(seed + t)
        bits = jnp.asarray(
            rng.integers(0, 2, (counts[path], lengths[path])), jnp.int32
        )
        llrs = np.asarray(standard_llrs(
            jax.random.PRNGKey(seed + t), encode_standard(bits, code),
            EBN0_DB, code,
        ))
        reqs += [
            (path, DecodeRequest(llrs=row, code=code_name, slo=slo))
            for row in llrs
        ]
    return reqs


def _direct_fn(path: str, code_name: str, use_kernel: bool):
    """The engine's decode contract for one request, run directly:
    zero-terminated frames start at state 0 with an argmax final end,
    tail-biting frames run WAVA, soft requests get BCJR LLRs."""
    from repro.core import ViterbiDecoder

    dec = ViterbiDecoder.from_standard(code_name, use_kernel=use_kernel)

    def fn(llrs):
        x = llrs[None]
        if path == "wava":
            return dec.decode_tailbiting(x)[0][0]
        if path == "soft":
            return dec.decode_soft(x, output="llr", initial_state=0)[0]
        if path == "stream":
            return dec.decode_stream_chunked(x, initial_state=0)[0]
        return dec.decode_batch(
            x, initial_state=0, time_parallel=(path == "time_parallel")
        )[0]

    return fn


def phase_engine(counts=None, lengths=None, max_batch=16, seed=7,
                 require_mosaic=True):
    """d. ``DecodeEngine(use_kernel=True)`` with mixed tenants."""
    import jax
    import numpy as np

    from repro.serve.engine import STREAM_MIN_STEPS, DecodeEngine

    counts = counts or dict(
        stream=16, batch=16, time_parallel=16, wava=16, soft=8
    )
    lengths = lengths or dict(
        stream=4 * STREAM_MIN_STEPS, batch=2048, time_parallel=1536,
        wava=1024, soft=2048,
    )
    reqs = _engine_requests(counts, lengths, seed)
    first = {}
    for path, req in reqs:
        first.setdefault(path, req)
    compile_s = 0.0
    for path, req in first.items():  # every kernel route lowers to Mosaic
        _, dt = _compile(
            _direct_fn(path, req.code, use_kernel=True), req.llrs,
            require_mosaic=require_mosaic,
        )
        compile_s += dt
    # pin the accelerator's idle-row budget (backend.py) so latency
    # cells route to time_parallel on every backend
    engine = DecodeEngine(use_kernel=True, max_batch=max_batch,
                          underfill_rows=1024)
    t0 = time.perf_counter()
    tickets = [engine.submit(r, now=0.0) for _, r in reqs]
    engine.drain(now=0.0)
    wall_s = time.perf_counter() - t0
    s = engine.stats()
    check(not s["errors"], f"engine errors: {s['errors']}")
    check(sum(s["faults"].values()) == 0, f"engine faults: {s['faults']}")
    check(s["degraded"] == 0, f"engine degraded {s['degraded']} cells")
    want = {path for path, _, _ in ENGINE_TENANTS}
    check(want <= set(s["paths"]),
          f"engine paths {s['paths']} miss some of {sorted(want)}")
    refs = {
        (path, req.code): jax.jit(_direct_fn(path, req.code, False))
        for path, req in first.items()
    }
    for (path, req), t in zip(reqs, tickets):
        check(t.done and t.error is None and not t.dropped,
              f"ticket {t.id} ({path}) not completed: {t.error}")
        check(t.path == path, f"ticket {t.id} took {t.path}, not {path}")
        ref = np.asarray(refs[path, req.code](req.llrs))
        if path == "soft":
            check(np.array_equal(t.bits, (ref < 0).astype(np.int32)),
                  f"soft ticket {t.id}: signs differ from direct BCJR")
            check(np.allclose(t.llrs, ref, rtol=1e-4, atol=1e-3),
                  f"soft ticket {t.id}: LLRs differ from direct BCJR")
        else:
            check(np.array_equal(t.bits, ref),
                  f"ticket {t.id} ({path}) differs from direct decode")
    _report(
        "d", "DecodeEngine, kernels on",
        ", ".join(f"{p}={counts[p]}x{lengths[p]}" for p in counts),
        compile_s, wall_s,
        f"every ticket == direct XLA decode; paths={s['paths']}; "
        f"faults=0 degraded=0",
    )
    return s


def _on_distinct_devices(out, n_devices: int, what: str) -> None:
    devs = {shard.device for shard in out.addressable_shards}
    check(len(devs) == n_devices,
          f"{what}: output shards on {len(devs)} device(s), want "
          f"{n_devices}: {sorted(str(d) for d in devs)}")


def phase_multichip(n_devices=4, n_frames=512, n_stages=65536,
                    n_streams=256, stream_len=65536, tp_frames=4,
                    seed=11, require_mosaic=True):
    """Frame-sharded, stream-sharded and time-sharded decode on an
    ``n_devices`` mesh, each against the single-device decode."""
    import jax
    import numpy as np

    from repro.core import TiledDecoderConfig, ViterbiDecoder
    from repro.distributed.decoder import (
        frame_mesh,
        sharded_decode_streams,
        sharded_decode_time_parallel,
    )

    check(len(jax.devices()) >= n_devices,
          f"{len(jax.devices())} devices, the phase needs {n_devices}")
    dec = ViterbiDecoder.from_standard(SPEC_NAME, use_kernel=True)
    spec = dec.spec

    # frame-sharded batch decode (ViterbiDecoder.decode_sharded)
    mesh = frame_mesh(n_devices)
    _, llrs = _ready(_channel(n_frames, n_stages, seed))
    run, compile_s = _compile(
        lambda x: dec.decode_sharded(x, mesh=mesh), llrs,
        require_mosaic=require_mosaic,
    )
    out, wall_s = _timed(run, llrs)
    _on_distinct_devices(out, n_devices, "decode_sharded")
    one = jax.jit(lambda x: dec.decode_batch(x, time_parallel=False))
    check(np.array_equal(np.asarray(out), np.asarray(_ready(one(llrs)))),
          "decode_sharded differs from the single-device decode")
    _report("4x", f"decode_sharded, {n_devices} chips",
            f"{n_frames}x{n_stages}", compile_s, wall_s,
            "== single device; one shard per chip")

    # stream-sharded one-pass tiled decode
    cfg = TiledDecoderConfig()
    _, streams = _ready(_channel(n_streams, stream_len, seed + 1))
    streams_on = {}
    for n_dev in (n_devices, 1):
        m = frame_mesh(n_dev)
        run, compile_s = _compile(
            lambda x, m=m: sharded_decode_streams(
                x, spec, cfg=cfg, mesh=m, use_kernel=True, one_pass=True
            ),
            streams, require_mosaic=require_mosaic,
        )
        streams_on[n_dev], dt = _timed(run, streams)
        if n_dev == n_devices:
            wall_s, c_s = dt, compile_s
    _on_distinct_devices(streams_on[n_devices], n_devices,
                         "sharded_decode_streams")
    check(np.array_equal(np.asarray(streams_on[n_devices]),
                         np.asarray(streams_on[1])),
          "sharded one-pass streams differ from the single-device decode")
    _report("4x", f"sharded_decode_streams(one_pass), {n_devices} chips",
            f"{n_streams}x{stream_len}", c_s, wall_s,
            "== single device; one shard per chip")

    # time-sharded decode (transfer-matrix tiles over the mesh)
    tmesh = frame_mesh(n_devices, axis="tiles")
    _, tp_llrs = _ready(_channel(tp_frames, n_stages, seed + 2))
    run, compile_s = _compile(
        lambda x: sharded_decode_time_parallel(
            x, spec, mesh=tmesh, use_kernel=True
        ),
        tp_llrs, require_mosaic=require_mosaic,
    )
    out, wall_s = _timed(run, tp_llrs)
    _on_distinct_devices(out, n_devices, "sharded_decode_time_parallel")
    seq = jax.jit(lambda x: dec.decode_batch(
        x, initial_state=None, time_parallel=False
    ))
    check(np.array_equal(np.asarray(out), np.asarray(_ready(seq(tp_llrs)))),
          "time-sharded decode differs from the single-device decode")
    _report("4x", f"sharded_decode_time_parallel, {n_devices} chips",
            f"{tp_frames}x{n_stages}", compile_s, wall_s,
            "== single device; one shard per chip")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="1: phases a-d on one chip (default); 4: only the "
        "multi-chip phase, on a 4-chip mesh",
    )
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: JAX found no TPU (first device: platform="
            f"{dev.platform!r}, kind={dev.device_kind!r}); there is no "
            f"CPU fallback",
            file=sys.stderr,
        )
        return 2
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}",
          flush=True)
    if args.chips == 4:
        phases = [lambda: phase_multichip(n_devices=4)]
    else:
        phases = [phase_batch, phase_chunked, phase_time_parallel,
                  phase_engine]
    failed = 0
    for phase in phases:  # every phase runs; any failure fails the run
        try:
            phase()
        except Exception as e:  # noqa: BLE001 — reported, then exit 1
            failed += 1
            kind = "check failed" if isinstance(e, SmokeFailure) else "error"
            print(f"chip_smoke: {kind}: {type(e).__name__}: {e}",
                  file=sys.stderr, flush=True)
            if not isinstance(e, SmokeFailure):
                traceback.print_exc()
    if failed:
        return 1
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
