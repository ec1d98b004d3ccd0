"""One-pass time-tiled ACS+traceback kernel (DESIGN.md §8): state-machine
exactness vs the XLA chunked path, oracle parity across ragged shapes,
packed/unpacked ring parity, renorm on/off, tiled one-pass stitching, and
the hlocount HBM bytes-accessed gate."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CODE_K7_CCSDS,
    CodeSpec,
    TiledDecoderConfig,
    ViterbiDecoder,
    build_acs_tables,
    decode_frames,
    tiled_decode_stream,
)
from repro.core.decoder import _chunk_step
from repro.core.encoder import conv_encode
from repro.core.viterbi import (
    AcsPrecision,
    blocks_from_llrs,
    init_metric,
    pick_time_tile,
)
from repro.kernels.ops import ring_dtype, ring_words, viterbi_decode_fused

SPEC = CODE_K7_CCSDS


def _noisy_llrs(n_frames, n_bits, sigma, seed=0):
    rng = np.random.default_rng(seed)
    bits = rng.integers(0, 2, (n_frames, n_bits))
    llr = np.stack(
        [
            1.0 - 2.0 * conv_encode(b, SPEC)
            + rng.normal(0.0, sigma, (n_bits, SPEC.beta))
            for b in bits
        ]
    )
    return bits, jnp.asarray(llr, jnp.float32)


def _replay_chunk_steps(blocks, lam0, hist0, tables, precision, tt, pack):
    """Reference: the XLA streaming state machine, one _chunk_step per
    time tile — the contract the kernel must replay bit-for-bit."""
    hist, lam, outs = hist0, lam0, []
    for lo in range(0, blocks.shape[0], tt):
        hist, lam, b = _chunk_step(
            hist, lam, blocks[lo:lo + tt], tables, precision, False, pack
        )
        outs.append(np.asarray(b))
    return hist, lam, np.concatenate(outs, axis=1)


@pytest.mark.parametrize(
    "pack,renorm,TT",
    [
        pytest.param(pack, renorm, tt,
                     id=f"{r_id}-{p_id}" + ("-tile_eq_depth" if tt == 16
                                            else ""))
        for tt in (8, 16)
        for renorm, r_id in ((True, "renorm"), (False, "raw"))
        for pack, p_id in ((False, "i8-ring"), (True, "packed"))
    ],
)
def test_fused_kernel_replays_chunk_state_machine(pack, renorm, TT):
    """bits, exit metrics AND exit ring all exactly equal the XLA
    chunked path at chunk == time_tile, packed and unpacked, with and
    without per-step renormalization, at a tile below the depth and at
    a tile equal to it (the ring then holds two tiles)."""
    tables = build_acs_tables(SPEC, 2)
    rng = np.random.default_rng(2)
    F, n, D = 3, 192, 16
    llr = jnp.asarray(rng.normal(0, 1, (F, n, SPEC.beta)), jnp.float32)
    blocks = blocks_from_llrs(llr, 2)
    lam0 = init_metric(F, SPEC.n_states, None)
    prec = AcsPrecision(renorm=renorm)
    hist0 = jnp.zeros((D, F, ring_words(tables, pack)), ring_dtype(pack))
    bits_k, lam_k, hist_k = viterbi_decode_fused(
        blocks, lam0, hist0, tables, prec, time_tile=TT, pack_survivors=pack
    )
    hist_r, lam_r, bits_r = _replay_chunk_steps(
        blocks, lam0, hist0, tables, prec, TT, pack
    )
    np.testing.assert_array_equal(np.asarray(bits_k).T, bits_r)
    np.testing.assert_array_equal(np.asarray(lam_k), np.asarray(lam_r))
    np.testing.assert_array_equal(np.asarray(hist_k), np.asarray(hist_r))


def test_fused_kernel_frame_tile_padding():
    """F not a multiple of block_frames exercises the pad/unpad path of
    the one-pass grid (frames are zero-LLR padded, then sliced off)."""
    tables = build_acs_tables(SPEC, 2)
    rng = np.random.default_rng(3)
    F, D, TT = 5, 8, 8
    llr = jnp.asarray(rng.normal(0, 1, (F, 64, SPEC.beta)), jnp.float32)
    blocks = blocks_from_llrs(llr, 2)
    lam0 = init_metric(F, SPEC.n_states, 0)
    hist0 = jnp.zeros((D, F, ring_words(tables, True)), ring_dtype(True))
    ref = viterbi_decode_fused(
        blocks, lam0, hist0, tables, time_tile=TT, pack_survivors=True,
        block_frames=256,
    )
    got = viterbi_decode_fused(
        blocks, lam0, hist0, tables, time_tile=TT, pack_survivors=True,
        block_frames=2,  # 5 % 2 != 0 -> padded frame tile
    )
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("n", [998, 1000, 1024], ids=["ragged2", "r8", "pow2"])
def test_one_pass_chunked_vs_oracle_ragged_T(n):
    """decode_stream_chunked(use_kernel=True) == full decode_frames for
    stream lengths NOT divisible by the time tile (remainder chunks fall
    back to the two-pass step inside the same state machine)."""
    bits, llr = _noisy_llrs(2, n, 0.5, seed=n)
    full = np.asarray(decode_frames(llr, SPEC, 2, None, None))
    dec = ViterbiDecoder(SPEC, use_kernel=True, decision_depth=512)
    got = np.asarray(
        dec.decode_stream_chunked(llr, chunk_len=256, initial_state=None)
    )
    np.testing.assert_array_equal(got, full)
    assert (got != bits).mean() < 1e-3  # and it actually decodes


def test_one_pass_engages_and_ring_is_packed():
    """use_kernel=True turns one-pass streaming on by default, with a
    bit-packed VMEM ring whenever the state count allows."""
    dec = ViterbiDecoder(SPEC, use_kernel=True, decision_depth=256)
    assert dec.one_pass and dec.ring_packed
    state = dec.init_stream_state(2)
    assert state.hist.dtype == jnp.int32
    assert state.hist.shape[-1] == SPEC.n_states // 16
    # the tile is the largest common divisor of chunk and depth that fits
    assert dec._one_pass_tile(128, state.depth_steps) == 128
    # the default depth's packed ring fits; a ring beyond the VMEM
    # budget falls back to two-pass
    big = ViterbiDecoder(SPEC, use_kernel=True, decision_depth=20480)
    assert big._one_pass_tile(2048, 2560) == 512
    big.ring_packed = False  # unpacked 20480-stage ring: > VMEM budget
    assert big._one_pass_tile(2048, 10240) is None


@pytest.mark.parametrize(
    "depth,chunk_len,tile",
    [(256, 512, 128), (512, 384, 64)],
    ids=["tile_eq_depth", "tile_below_depth"],
)
def test_decode_chunk_default_tile_vs_oracle(depth, chunk_len, tile):
    """decode_chunk at the tile the rule picks (no time_tile given),
    chunk by chunk and flushed, == full decode_frames on a noisy
    stream, and the chunks ran one-pass at that tile."""
    bits, llr = _noisy_llrs(2, 1536, 0.5, seed=depth)
    full = np.asarray(decode_frames(llr, SPEC, 2, None, None))
    dec = ViterbiDecoder(SPEC, use_kernel=True, decision_depth=depth)
    state = dec.init_stream_state(2)
    assert dec._one_pass_tile(chunk_len // 2, state.depth_steps) == tile
    outs = []
    for lo in range(0, llr.shape[1], chunk_len):
        state, out = dec.decode_chunk(state, llr[:, lo:lo + chunk_len])
        outs.append(np.asarray(out))
    outs.append(np.asarray(dec.flush_stream(state)))
    got = np.concatenate(outs, axis=1)
    np.testing.assert_array_equal(got, full)
    assert (got != bits).mean() < 1e-3


def test_one_pass_packed_unpacked_ring_parity():
    """Packed and unpacked rings stream bit-identically end to end."""
    _, llr = _noisy_llrs(2, 768, 0.7, seed=5)
    kw = dict(chunk_len=192, initial_state=None)
    a = ViterbiDecoder(
        SPEC, use_kernel=True, decision_depth=256, pack_survivors=True
    ).decode_stream_chunked(llr, **kw)
    b = ViterbiDecoder(
        SPEC, use_kernel=True, decision_depth=256, one_pass=True
    )
    b.ring_packed = False  # force the int8 ring
    b = b.decode_stream_chunked(llr, **kw)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_one_pass_pinned_states_roundtrip():
    """Known start + tail flush through the one-pass path recovers the
    exact transmitted bits (flush traceback pins the final state)."""
    from repro.core.encoder import tail_flush

    rng = np.random.default_rng(6)
    bits = tail_flush(rng.integers(0, 2, 1020), SPEC)
    llr = (
        1.0 - 2.0 * conv_encode(bits, SPEC)
        + rng.normal(0.0, 0.4, (len(bits), SPEC.beta))
    )
    dec = ViterbiDecoder(SPEC, use_kernel=True, decision_depth=256)
    got = np.asarray(
        dec.decode_stream_chunked(
            jnp.asarray(llr, jnp.float32)[None],
            chunk_len=256,
            initial_state=0,
            final_state=0,
        )
    )[0]
    np.testing.assert_array_equal(got, bits)


def test_one_pass_small_code_unpacked_fallback():
    """K=3 (4 states, cannot pack): the ring stays int8 and one-pass
    still replays the XLA path exactly."""
    spec = CodeSpec(k=3, polys=(0o7, 0o5))
    rng = np.random.default_rng(8)
    llr = jnp.asarray(rng.normal(0, 1, (2, 512, 2)), jnp.float32)
    full = np.asarray(decode_frames(llr, spec, 2, None, None))
    dec = ViterbiDecoder(spec, use_kernel=True, decision_depth=256)
    assert not dec.ring_packed
    got = np.asarray(
        dec.decode_stream_chunked(llr, chunk_len=128, initial_state=None)
    )
    np.testing.assert_array_equal(got, full)


def test_tiled_one_pass_matches_two_pass():
    """Window decode through the one-pass kernel stitches the same
    stream as the two-pass tiled path (survivors merge within the
    overlap at this SNR), and the front door routes there."""
    bits, llr = _noisy_llrs(1, 1280, 0.4, seed=9)
    stream = llr[0]
    cfg = TiledDecoderConfig()
    two = np.asarray(tiled_decode_stream(stream, SPEC, cfg))
    one = np.asarray(
        tiled_decode_stream(stream, SPEC, cfg, one_pass=True)
    )
    np.testing.assert_array_equal(one, two)
    dec = ViterbiDecoder(SPEC, use_kernel=True)
    front = np.asarray(dec.decode_stream_tiled(stream, cfg))
    np.testing.assert_array_equal(front, one)
    assert (one != bits[0]).mean() < 1e-3


def test_one_pass_streaming_traffic_gate():
    """DESIGN.md §8 acceptance: >= 5x fewer HBM bytes accessed than the
    two-pass streaming path at T=512 stages, F=1024, K=7, rho=2.

    Backend-aware (ISSUE 7 satellite): on the CPU host the gate runs on
    the modeled static-interface bytes (``xla_mode == "static"``) — the
    CPU lowering materializes bf16 converts and gather buffers a TPU
    fusion keeps on-chip, so measuring it is a proxy of the wrong
    machine.  Against the PACKED two-pass baseline the honest static
    bound at this shape is ~3x, not 5x: the one-pass path still pays the
    2xD-step ring interface and the common LLR blocks, so the survivor-
    stream win is capped near T/D = 256/64 = 4 (the 5x+ figure belongs
    to the unpacked default that streaming actually shipped before §8).
    """
    import jax

    from repro.kernels.traffic import streaming_traffic_report

    rep = streaming_traffic_report()
    if jax.default_backend() == "cpu":
        assert rep["xla_mode"] == "static", rep["xla_mode"]
    assert rep["ratio"] >= 5.0, rep
    assert rep["ratio_vs_packed"] >= 2.5, rep
    # the kernel interface itself must beat the two-pass interface: phi
    # (T*F*S int8) dwarfs everything else the two-pass kernel moves
    assert (
        rep["one_pass"]["kernel_bytes"] * 2
        < rep["two_pass"]["kernel_bytes"]
    ), rep


@pytest.mark.parametrize(
    "shape,dtype,nbytes",
    [
        # Mosaic's own allocation report for the pre-layout ring
        ((2592, 256, 4), jnp.int32, 339_738_624),
        # frames on lanes: the (D+TT, W, BF) packed ring is unpadded
        ((2592, 4, 256), jnp.int32, 2592 * 4 * 256 * 4),
        ((100, 3, 256), jnp.int32, 100 * 4 * 256 * 4),
        ((100, 5, 64), jnp.int32, 100 * 8 * 128 * 4),
        ((100, 1, 256), jnp.bfloat16, 100 * 2 * 256 * 2),
        ((100, 2, 256), jnp.int8, 100 * 4 * 256),
        ((100, 12, 256), jnp.int8, 100 * 16 * 256),
        ((2592, 64, 256), jnp.int8, 2592 * 64 * 256),
        ((256, 4), jnp.float32, 256 * 128 * 4),
    ],
)
def test_vmem_bytes_counts_mosaic_layout(shape, dtype, nbytes):
    """VMEM accounting counts bytes as Mosaic lays them out (the
    tilings Mosaic reported for these shapes when they overflowed)."""
    from repro.core.kernel_geometry import vmem_bytes

    assert vmem_bytes(shape, dtype) == nbytes


def test_one_pass_guard_counts_whole_kernel():
    """The one-pass guard budgets the whole kernel, ring included: the
    default depth's ring fits, a 16x deeper one does not."""
    from repro.core.kernel_geometry import (
        KERNEL_VMEM_BUDGET, fused_decode_vmem_bytes, fused_ring_vmem_bytes,
    )

    ring = fused_ring_vmem_bytes(2560, 32, 256, 64, True)
    total = fused_decode_vmem_bytes(2560, 32, 256, 64, 4, 4, True)
    assert ring == 2592 * 4 * 256 * 4 < total < KERNEL_VMEM_BUDGET
    assert fused_decode_vmem_bytes(40960, 32, 256, 64, 4, 4, True) > (
        KERNEL_VMEM_BUDGET
    )
