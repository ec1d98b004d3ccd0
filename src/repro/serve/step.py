"""Serving step factories: LM prefill / decode, the paper's Viterbi
stream-decode service (DESIGN.md §6), and the multi-tenant
``DecodeEngine`` factory (DESIGN.md §10)."""
from __future__ import annotations

from repro.configs.base import ArchConfig
from repro.models import lm

__all__ = [
    "make_prefill_step",
    "make_decode_step",
    "make_viterbi_serve_step",
    "make_viterbi_decoder",
    "make_decode_engine",
]


def make_prefill_step(cfg: ArchConfig):
    def prefill_step(params, cache, batch):
        return lm.prefill(
            params, cfg, batch["tokens"], cache, batch.get("prefix_embeds")
        )

    return prefill_step


def make_decode_step(cfg: ArchConfig):
    def decode_step(params, cache, tokens):
        return lm.decode_step(params, cfg, tokens, cache)

    return decode_step


def make_viterbi_decoder(vcfg, precision=None, use_kernel: bool = False,
                         decision_depth=None):
    """The service's ViterbiDecoder (DESIGN.md §6) from a ViterbiConfig."""
    from repro.core.decoder import ViterbiDecoder

    return ViterbiDecoder.from_config(
        vcfg,
        precision=precision,
        use_kernel=use_kernel,
        decision_depth=decision_depth,
    )


def make_decode_engine(precision=None, use_kernel: bool = False, **kw):
    """The multi-tenant serving entry point (DESIGN.md §10): a
    ``repro.serve.engine.DecodeEngine`` that buckets ragged
    mixed-code/mixed-SLO requests into padded (F, T) cells and routes
    each assembled batch to the right decode path.  Unlike the step
    factories above it is stateful (queues, jit-fn cache, session
    table), so it is driven with submit/poll/drain rather than wrapped
    in jit — see ``launch/serve.py --service engine``.  Keyword
    arguments pass through to ``DecodeEngine`` (max_batch, max_wait,
    session_capacity, mesh, ...)."""
    from repro.serve.engine import DecodeEngine

    return DecodeEngine(precision=precision, use_kernel=use_kernel, **kw)


def make_viterbi_serve_step(vcfg, precision=None, use_kernel: bool = False,
                            mode: str = "tiled"):
    """Stateless Viterbi serve step (the paper's serving workload),
    through the unified ViterbiDecoder front door (DESIGN.md §6).

    llrs: (n_streams, stream_len, beta) -> bits (n_streams, stream_len).

    mode="tiled": frame tiling turns each stream into stream_len/frame_len
    independent windows, and the windows of every stream form one frame
    batch — all of it pure data
    parallelism (the paper's §III parallelization), sharded over every
    mesh axis.  With ``use_kernel=True`` the windows decode through the
    one-pass time-tiled ACS+traceback kernel (DESIGN.md §8): survivors
    stay in a VMEM ring, no phi round-trip to HBM.  mode="batch": each
    stream is one truncated-Viterbi frame (no tiling — latency scales
    with stream_len; stays on the exact two-pass path).

    The stateful chunked-streaming mode carries state across calls and so
    is not a step function — build the decoder with
    ``make_viterbi_decoder`` and drive init_stream_state / decode_chunk /
    flush_stream directly (see launch/serve.py --mode chunked).

    Standard-code configs (vcfg.code, DESIGN.md §7) flow through
    unchanged: punctured configs serve the SERIAL kept-LLR stream
    (n_streams, Lp) and the decoder re-inserts erasures (tiled windows
    use the erasure-stretched default overlap); tail-biting configs must
    use mode="batch" (WAVA decodes frames whole, and the serve step stays
    a pure jittable function because WAVA's circulations unroll at trace
    time).
    """
    decoder = make_viterbi_decoder(vcfg, precision, use_kernel)

    if decoder.termination == "tailbiting" and mode != "batch":
        raise ValueError(
            f"tail-biting standard {vcfg.code!r} serves via mode='batch' "
            f"(WAVA decodes frames whole), got mode={mode!r}"
        )
    if mode == "tiled":
        # identity for unpunctured decoders; stretches the configured
        # overlap by the puncture expansion otherwise (DESIGN.md §7)
        cfg = decoder.default_tiled_config(vcfg.tiled)

        def serve_step(llrs):
            return decoder.decode_streams_tiled(llrs, cfg=cfg)
    elif mode == "batch":
        if decoder.termination == "tailbiting":
            def serve_step(llrs):
                return decoder.decode_tailbiting(llrs)[0]
        else:
            def serve_step(llrs):
                return decoder.decode_batch(
                    llrs, initial_state=None, final_state=None
                )
    else:
        raise ValueError(f"unknown serve mode {mode!r}")

    return serve_step
