"""Serving launcher.

Three services:
  * ``--service viterbi`` — the paper's workload: batched tensor-ACS
    decode of LLR streams through the unified ViterbiDecoder front door
    (DESIGN.md §6; optimized §Perf C4b config via --optimized).
    ``--code`` picks any registry standard (DESIGN.md §7): punctured
    rates (wifi-11a-r34, dvb-s-r78, ...) serve the serial kept-LLR
    stream; tail-biting codes (lte-tbcc) decode whole frames via WAVA.
    ``--mode`` selects the decode scenario (decision table: README
    "Serving"):
      - tiled   (default) stateless overlapping-window decode (§III);
      - chunked stateful streaming — path metrics + survivor ring carried
        across --chunk-len chunks, zero redundant ACS work;
      - sharded streams sharded over every visible device via shard_map
        (run under XLA_FLAGS=--xla_force_host_platform_device_count=N to
        demo on CPU);
      - batch   one truncated-Viterbi frame per stream;
      - time_parallel — §9 associative-scan decode of whole streams
        (the single-stream latency path; identical bits, log-depth
        dependency chain instead of T-linear).
    ``--use-kernel`` runs the Pallas backend: streaming modes (tiled /
    chunked / sharded) then take the one-pass time-tiled ACS+traceback
    kernel (DESIGN.md §8) — survivors stay in a VMEM ring, no phi
    round-trip through HBM.
  * ``--service engine`` — the multi-tenant serving engine
    (DESIGN.md §10): ragged mixed-code requests bucketed into padded
    (F, T) cells, assembled under --max-wait-ms/--streams, routed per
    SLO class (--slo latency|throughput|mixed), with queue-depth /
    backpressure stats and a graceful drain at the end.
  * ``--service lm --arch <id>`` — LM prefill + decode loop on the
    reduced config (CPU demo of the production serve path).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def _viterbi_run_fn(vcfg, args):
    """Build run(llrs) -> bits for the selected --mode."""
    from repro.serve.step import make_viterbi_decoder, make_viterbi_serve_step

    use_kernel = getattr(args, "use_kernel", False)
    if args.mode in ("tiled", "batch"):
        return jax.jit(
            make_viterbi_serve_step(
                vcfg, use_kernel=use_kernel, mode=args.mode
            )
        )
    if args.mode == "chunked":
        decoder = make_viterbi_decoder(
            vcfg, use_kernel=use_kernel, decision_depth=args.decision_depth
        )

        def run(llrs):
            return decoder.decode_stream_chunked(
                llrs, chunk_len=args.chunk_len, initial_state=None
            )

        return run
    if args.mode == "time_parallel":
        # §9 associative-scan decode of each whole stream: identical
        # bits, sequential depth 3*tile + log2(tiles) instead of T
        decoder = make_viterbi_decoder(vcfg, use_kernel=use_kernel)

        def run(llrs):
            return decoder.decode_batch(
                llrs, initial_state=None, final_state=None,
                time_parallel=True,
            )

        return run
    if args.mode == "sharded":
        from repro.distributed.decoder import sharded_decode_streams

        decoder = make_viterbi_decoder(vcfg, use_kernel=use_kernel)

        def run(llrs):
            # punctured streams: erasures re-inserted host-side, then the
            # depunctured streams shard like any others (DESIGN.md §7)
            llrs = decoder.depunctured(llrs)
            return sharded_decode_streams(
                llrs,
                vcfg.spec,
                cfg=decoder.default_tiled_config(vcfg.tiled),
                precision=vcfg.precision,
                pack_survivors=vcfg.pack_survivors,
                use_kernel=use_kernel,
                one_pass=use_kernel,
            )

        return run
    raise ValueError(f"unknown --mode {args.mode!r}")


def viterbi_service(args):
    """The ``--service viterbi`` path up to the first decode: (run, src),
    where ``run(llrs) -> bits`` decodes one batch in the selected
    ``--mode`` and ``src`` is the seeded ``ChannelStream`` that feeds it
    (``src.batch_at(i) -> (bits, llrs)``)."""
    import dataclasses

    from repro.codes.registry import get_code
    from repro.configs.viterbi_k7 import (
        CONFIG, CONFIG_OPTIMIZED, config_for_standard,
    )
    from repro.data.pipeline import ChannelStream

    if args.code != "ccsds-k7":
        # any registry standard behind the same front door (DESIGN.md §7)
        vcfg = config_for_standard(args.code)
        if args.optimized:
            # apply exactly CONFIG -> CONFIG_OPTIMIZED's tuning deltas so
            # a retuned optimized config carries over to every standard
            vcfg = dataclasses.replace(vcfg, **{
                f.name: getattr(CONFIG_OPTIMIZED, f.name)
                for f in dataclasses.fields(CONFIG_OPTIMIZED)
                if f.name not in ("name", "family", "spec", "code")
                and getattr(CONFIG_OPTIMIZED, f.name)
                != getattr(CONFIG, f.name)
            })
        if get_code(args.code).termination == "tailbiting":
            args.mode = "batch"  # WAVA decodes frames whole
    else:
        vcfg = CONFIG_OPTIMIZED if args.optimized else CONFIG
    vcfg = dataclasses.replace(
        vcfg, stream_len=args.stream_len, batch_streams=args.streams
    )
    run = _viterbi_run_fn(vcfg, args)
    src = ChannelStream(
        spec=vcfg.spec, n_streams=args.streams,
        stream_len=args.stream_len, ebn0_db=args.ebn0,
        code=args.code,
    )
    return run, src


def serve_viterbi(args):
    run, src = viterbi_service(args)
    bits, llrs = src.batch_at(0)
    run(llrs).block_until_ready()  # compile
    total = err = 0
    t0 = time.perf_counter()
    for i in range(args.batches):
        bits, llrs = src.batch_at(i)
        out = run(llrs)
        out.block_until_ready()
        err += int((np.asarray(out) != np.asarray(bits)).sum())
        total += bits.size
    dt = time.perf_counter() - t0
    tag = f"viterbi-{args.mode}" + ("-opt" if args.optimized else "")
    print(
        f"[{tag}] {total} bits in "
        f"{dt:.2f}s = {total/dt/1e6:.2f} Mb/s "
        f"({len(jax.devices())} dev), BER={err/total:.3e}"
    )


def serve_engine(args):
    """Multi-tenant engine demo (DESIGN.md §10): a synthetic ragged
    mixed-code/mixed-SLO workload submitted against a virtual clock,
    polled tick by tick, then gracefully drained — prints decode
    throughput, BER, queue depth / backpressure and the engine's
    occupancy / padding-waste / jit-cache counters.

    With ``--metrics-jsonl PATH`` the run records the §12 observability
    feed: request-lifecycle spans and a final metrics snapshot go to
    PATH (render it with ``python -m repro.obs.top --jsonl PATH``), and
    the drain prints the port-less Prometheus text dump.

    With ``--chaos SCHEDULE.json`` the replay runs under the §13
    fault-injection harness (the JSON is a ``runtime.chaos``
    ``ChaosSchedule``); ``--checkpoint-dir DIR`` enables periodic
    session-table checkpointing, and the graceful drain then writes a
    final session checkpoint and reports the failover stats (faults,
    retries, degradations, failovers, expired/failed requests)."""
    from repro.codes import encode_standard, get_code, standard_llrs
    from repro.obs import Observability, set_default_registry
    from repro.serve.step import make_decode_engine

    if args.slo == "mixed":
        tenants = [
            ("ccsds-k7", "throughput"),
            (args.code if args.code != "ccsds-k7" else "wifi-11a-r34",
             "latency"),
            ("lte-tbcc", "latency"),
        ]
    else:
        tenants = [(args.code, args.slo)]
    obs = Observability(
        enabled=args.metrics_jsonl is not None, jsonl=args.metrics_jsonl
    )
    prev_reg = set_default_registry(obs.registry)  # decoder path counters
    chaos = None
    if args.chaos is not None:
        from repro.runtime.chaos import ChaosInjector, ChaosSchedule

        chaos = ChaosInjector(ChaosSchedule.from_file(args.chaos))
    engine = make_decode_engine(
        use_kernel=args.use_kernel,
        max_batch=args.streams,
        max_wait={"latency": args.max_wait_ms / 4e3,
                  "throughput": args.max_wait_ms / 1e3},
        registry=obs.registry,
        recorder=obs.recorder,
        chaos=chaos,
        dispatch_timeout=0.1,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_interval=(
            None if args.checkpoint_dir is None else args.max_wait_ms / 1e3
        ),
        scrub=args.scrub_rate,
    )
    rng = np.random.default_rng(0)
    lens = [args.stream_len // 4, args.stream_len // 3, args.stream_len // 2]
    reqs = []  # (arrival, request, true bits)
    for b in range(args.batches * args.streams):
        code_name, slo = tenants[b % len(tenants)]
        code = get_code(code_name)
        n = 128 if code.termination == "tailbiting" else lens[b % len(lens)]
        bits = jnp.asarray(rng.integers(0, 2, (1, n)), jnp.int32)
        llrs = standard_llrs(
            jax.random.PRNGKey(b), encode_standard(bits, code),
            args.ebn0, code,
        )
        from repro.serve.engine import DecodeRequest

        reqs.append((
            b * 1e-4,  # 10k offered req/s of virtual load
            DecodeRequest(llrs=np.asarray(llrs)[0], code=code_name, slo=slo),
            np.asarray(bits)[0],
        ))
    t0 = time.perf_counter()
    tickets, peak_q = [], 0
    tick = args.max_wait_ms / 4e3
    now, i = 0.0, 0
    while i < len(reqs) or engine.queue_depth():
        while i < len(reqs) and reqs[i][0] <= now:
            tickets.append(engine.submit(reqs[i][1], now=now))
            i += 1
        engine.poll(now=now)
        peak_q = max(peak_q, engine.queue_depth())
        now += tick
    engine.drain(now=now)  # graceful drain: flush partial cells
    final_ckpt = engine.checkpoint_sessions(now=now)  # §13 drain contract
    dt = time.perf_counter() - t0
    total = err = dropped = errored = 0
    for (_, _, bits), t in zip(reqs, tickets):
        if t.dropped:  # backpressure sheds, it doesn't corrupt BER
            dropped += 1
            continue
        if t.error is not None:  # §13 typed errors (never silent drops)
            errored += 1
            continue
        total += bits.size
        err += int((t.bits != bits).sum())
    s = engine.stats()
    lat = {k: f"p50={v['p50']*1e3:.2f}ms/p99={v['p99']*1e3:.2f}ms"
           for k, v in s["latency"].items()}
    print(
        f"[engine] {total} bits in {dt:.2f}s = {total/dt/1e6:.2f} Mb/s, "
        f"BER={err/max(total,1):.3e}\n"
        f"[engine] batches={s['batches']} occupancy={s['occupancy']:.2f} "
        f"padding_waste={s['padding_waste']:.2f} paths={s['paths']}\n"
        f"[engine] peak_queue={peak_q} rejected={s['rejected']} "
        f"dropped={dropped} jit_cache={s['jit_cache']} "
        f"latency(virtual)={lat}"
    )
    if args.chaos is not None or args.checkpoint_dir is not None:
        # the §13 failover report of the graceful drain
        print(
            f"[engine] faults={s['faults']} retries={s['retries']} "
            f"degraded={s['degraded']} failovers={s['failovers']} "
            f"expired={s['expired']} failed={errored} "
            f"checkpoints={s['checkpoints']}"
        )
    if args.scrub_rate > 0:
        # the §14 data-integrity quarantine summary of the drain
        sc = s["scrub"]
        print(
            f"[engine] scrub rate={sc['rate']} sampled={sc['sampled']} "
            f"frames={sc['frames']} flags={sc['syndrome_flags']} "
            f"confirmed={sc['confirmed']} "
            f"false_alarms={sc['false_alarms']} "
            f"quarantined={s['quarantined']} sanitized={s['sanitized']}"
        )
        if final_ckpt is not None:
            print(f"[engine] final session checkpoint -> {final_ckpt}")
    if args.metrics_jsonl is not None:
        # the §12 port-less drain dump: no metrics port to scrape, so
        # the Prometheus text goes to stdout and the JSONL gets a final
        # metrics snapshot line
        obs.close()
        print(engine.registry.render_prometheus(), end="")
        print(f"[engine] spans+metrics -> {args.metrics_jsonl}")
    set_default_registry(prev_reg)


def serve_lm(args):
    from repro.configs import get_smoke_config
    from repro.models import lm

    cfg = get_smoke_config(args.arch)
    key = jax.random.PRNGKey(0)
    params = lm.init_params(cfg, key)
    B, S = args.streams, 64
    S_tok = S - cfg.prefix_len
    tokens = jax.random.randint(key, (B, S_tok), 0, cfg.vocab_size)
    prefix = None
    if cfg.prefix_len:
        prefix = (0.02 * jax.random.normal(
            key, (B, cfg.prefix_len, cfg.d_model))).astype(jnp.bfloat16)
    cache = lm.init_cache(cfg, B, max_len=S + args.tokens)
    prefill = jax.jit(lambda p, c, t, px: lm.prefill(p, cfg, t, c, px))
    decode = jax.jit(lambda p, c, t: lm.decode_step(p, cfg, t, c))
    logits, cache = prefill(params, cache, tokens, prefix)
    nxt = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    t0 = time.perf_counter()
    for _ in range(args.tokens):
        logits, cache = decode(params, nxt, cache)
        nxt = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    nxt.block_until_ready()
    dt = time.perf_counter() - t0
    print(
        f"[lm:{cfg.name}] {args.tokens} tokens x {B} streams in {dt:.2f}s "
        f"= {args.tokens*B/dt:.1f} tok/s (CPU, reduced config)"
    )


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--service", default="viterbi",
                    choices=["viterbi", "engine", "lm"])
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--streams", type=int, default=8)
    ap.add_argument("--stream-len", type=int, default=8192)
    ap.add_argument("--batches", type=int, default=3)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--ebn0", type=float, default=4.0)
    ap.add_argument(
        "--code", default="ccsds-k7",
        help="registry standard to serve (repro.codes.list_codes()): "
        "e.g. wifi-11a-r34 (punctured) or lte-tbcc (tail-biting; "
        "forces --mode batch)",
    )
    ap.add_argument("--optimized", action="store_true")
    ap.add_argument(
        "--mode", default="tiled",
        choices=["tiled", "chunked", "sharded", "batch", "time_parallel"],
        help="decode scenario (README 'Serving' decision table); "
        "time_parallel is the §9 log-depth single-stream latency path",
    )
    ap.add_argument(
        "--use-kernel", action="store_true",
        help="Pallas backend; streaming modes then run the one-pass "
        "time-tiled ACS+traceback kernel (DESIGN.md §8)",
    )
    ap.add_argument("--chunk-len", type=int, default=4096)
    ap.add_argument("--decision-depth", type=int, default=None)
    ap.add_argument(
        "--slo", default="mixed",
        choices=["mixed", "latency", "throughput"],
        help="engine service: SLO class of the synthetic tenants "
        "(mixed = one latency + one throughput + one tail-biting tenant)",
    )
    ap.add_argument(
        "--max-wait-ms", type=float, default=10.0,
        help="engine service: throughput-class batch-assembly deadline "
        "(latency class waits a quarter of this)",
    )
    ap.add_argument(
        "--chaos", default=None, metavar="SCHEDULE.json",
        help="engine service: run the replay under the §13 "
        "fault-injection harness — the JSON file is a "
        "runtime.chaos.ChaosSchedule (attempt-indexed device failures, "
        "timeouts, stragglers, compile errors)",
    )
    ap.add_argument(
        "--checkpoint-dir", default=None,
        help="engine service: periodically checkpoint the "
        "chunked-streaming session table here (DESIGN.md §13); the "
        "graceful drain writes a final checkpoint and prints failover "
        "stats",
    )
    ap.add_argument(
        "--scrub-rate", type=float, default=0.0,
        help="engine service: sampled fraction of dispatches run "
        "through the §14 online SDC scrubber (re-encode syndrome check "
        "+ shadow re-decode; confirmed corruption fails the ticket "
        "with sdc_detected and quarantines the device); 0 disables — "
        "the engine then makes no extra calls at all.  The drain "
        "prints the scrub/quarantine summary",
    )
    ap.add_argument(
        "--metrics-jsonl", default=None,
        help="engine service: record the §12 observability feed "
        "(lifecycle spans + a final metrics snapshot) to this JSONL "
        "file and print the Prometheus text dump on drain; view with "
        "python -m repro.obs.top --jsonl PATH",
    )
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.service == "viterbi":
        serve_viterbi(args)
    elif args.service == "engine":
        serve_engine(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
