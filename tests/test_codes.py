"""Standard-codes subsystem (DESIGN.md §7): registry, puncturing /
rate-matching, tail-biting WAVA decode, and the rate-1/3 (beta=3) audit
of every place B = rho*beta is derived."""
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.codes import (
    REGISTRY,
    PuncturePattern,
    depuncture,
    depuncture_np,
    encode_standard,
    get_code,
    list_codes,
    measure_standard_ber,
    puncture,
    standard_llrs,
    tx_frames,
    wava_decode,
)
from repro.codes.tailbiting import tail_bite_state
from repro.core import CodeSpec, ViterbiDecoder, decode_frames
from repro.core.encoder import conv_encode, conv_encode_jax, tail_flush
from repro.core.trellis import build_acs_tables
from repro.core.viterbi_ref import viterbi_decode_ref

SPEC_K3 = CodeSpec(k=3, polys=(0o7, 0o5))


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_entries_resolve_and_build():
    assert "wifi-11a-r34" in list_codes() and "lte-tbcc" in list_codes()
    for name in list_codes():
        code = get_code(name)
        assert 0.0 < code.rate <= 1.0
        tables = build_acs_tables(code.spec, 2)
        assert tables.llr_block == 2 * code.spec.beta
        if code.puncture is not None:
            assert code.rate > code.spec.rate  # puncturing raises the rate


def test_registry_unknown_name():
    with pytest.raises(KeyError, match="unknown standard code"):
        get_code("wifi-11b")


def test_lte_tbcc_is_rate_third_tailbiting():
    code = get_code("lte-tbcc")
    assert code.spec.beta == 3 and code.termination == "tailbiting"
    assert abs(code.rate - 1.0 / 3.0) < 1e-9


# ---------------------------------------------------------------------------
# puncture / depuncture
# ---------------------------------------------------------------------------

def test_puncture_roundtrip_mask_structure():
    pat = get_code("wifi-11a-r34").puncture
    x = jnp.arange(1.0, 49.0).reshape(24, 2)  # no zeros in the input
    kept = puncture(x, pat)
    assert kept.shape == (pat.punctured_len(24),)
    back = np.asarray(depuncture(kept, pat))
    mask = pat._tiled_mask(24)
    np.testing.assert_array_equal(back[mask], np.asarray(x)[mask])
    assert (back[~mask] == 0).all()  # erasures are exactly zero-LLR


def test_puncture_batched_and_vmap():
    pat = get_code("dvb-s-r78").puncture
    x = jnp.asarray(np.random.default_rng(0).normal(size=(5, 28, 2)))
    kept = puncture(x, pat)
    assert kept.shape == (5, pat.punctured_len(28))
    v = jax.vmap(lambda a: depuncture(a, pat))(kept)
    np.testing.assert_allclose(
        np.asarray(v), np.asarray(depuncture(kept, pat))
    )


PUNCTURED = sorted(n for n, c in REGISTRY.items() if c.puncture is not None)


@pytest.mark.parametrize("periods", [1, 2, 3])
@pytest.mark.parametrize("name", PUNCTURED)
def test_depuncture_np_equals_depuncture(name, periods):
    """The host twin re-inserts the erasures where the device scatter
    does, for one stream and a batch, and keeps the input's dtype."""
    pat = get_code(name).puncture
    rng = np.random.default_rng(zlib.crc32(name.encode()) + periods)
    kept = rng.normal(size=(3, periods * pat.n_kept)).astype(np.float32)
    want = np.asarray(depuncture(jnp.asarray(kept), pat))
    got = depuncture_np(kept, pat)
    assert isinstance(got, np.ndarray) and got.dtype == np.float32
    assert got.shape == (3, periods * pat.period, pat.beta)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(depuncture_np(kept[1], pat), want[1])
    with pytest.raises(ValueError, match="inconsistent"):
        depuncture_np(kept, pat, n=periods * pat.period + 1)


def test_stages_for_inverts_punctured_len():
    for name in list_codes():
        pat = get_code(name).puncture
        if pat is None:
            continue
        for n in range(pat.period, 6 * pat.period):
            assert pat.stages_for(pat.punctured_len(n)) == n


def test_pattern_validation():
    with pytest.raises(ValueError):
        PuncturePattern(mask=((0, 0),))  # keeps nothing
    with pytest.raises(ValueError):
        PuncturePattern(mask=((1, 2),))  # non-binary
    with pytest.raises(ValueError):
        PuncturePattern(mask=((1,), (1, 0)))  # ragged


@given(seed=st.integers(0, 2**16))
@settings(max_examples=10, deadline=None, derandomize=True)
def test_property_puncture_decode_roundtrip_all_standards(seed):
    """ISSUE satellite: depuncture(puncture(x)) + decode at high Eb/N0
    recovers the message for EVERY registry entry."""
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    for name in list_codes():
        code = get_code(name)
        decoder = _decoder_cache(name)
        n = 96 + 2 * int(rng.integers(0, 16))
        bits = jnp.asarray(
            rng.integers(0, 2, (2, n)), jnp.int32
        )
        llrs = standard_llrs(
            jax.random.fold_in(key, zlib.crc32(name.encode())),
            encode_standard(tx_frames(bits, code, decoder.rho), code),
            9.0, code,
        )
        out = np.asarray(decoder.decode_batch(llrs))[:, :n]
        np.testing.assert_array_equal(
            out, np.asarray(bits), err_msg=f"{name} failed at 9 dB"
        )


_DECODERS = {}


def _decoder_cache(name):
    if name not in _DECODERS:
        _DECODERS[name] = ViterbiDecoder.from_standard(name)
    return _DECODERS[name]


# ---------------------------------------------------------------------------
# tail-biting: encoder circularity + WAVA vs brute force
# ---------------------------------------------------------------------------

def test_tailbite_encoder_closes_circle():
    rng = np.random.default_rng(3)
    for spec in (SPEC_K3, get_code("lte-tbcc").spec):
        bits = rng.integers(0, 2, 50)
        s0 = tail_bite_state(bits, spec.k)
        # encoding from s0 must end in s0 (circular trellis)
        from repro.core.trellis import build_transitions

        tr = build_transitions(spec)
        s = s0
        for u in bits:
            s = int(tr.next_state[s, u])
        assert s == s0
        # numpy and jax tail-biting encoders agree
        a = conv_encode(bits, spec, tail_bite=True)
        b = np.asarray(conv_encode_jax(jnp.asarray(bits), spec, tail_bite=True))
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_wava_equals_brute_force_circular_k3(seed):
    """ISSUE satellite: WAVA == exhaustive circular decode on a small
    K=3 code (metric equality; at these SNRs the ML path is unique).
    The ground truth is tests/oracle.py's full 2^n codeword enumeration
    (every tail-biting sequence, not just every boundary state)."""
    from oracle import ml_path

    rng = np.random.default_rng(seed)
    spec = SPEC_K3
    n = 16
    bits = rng.integers(0, 2, n)
    coded = conv_encode(bits, spec, tail_bite=True)
    llr = 1.0 - 2.0 * coded.astype(np.float64)
    llr = llr + rng.normal(0.0, 0.45, llr.shape)

    want_bits, want_metric = ml_path(llr, spec, tail_bite=True)
    tables = build_acs_tables(spec, 2)
    got, conv = wava_decode(
        jnp.asarray(llr, jnp.float32)[None], tables, max_iters=8
    )
    got = np.asarray(got[0])
    assert bool(np.asarray(conv[0]))
    # the WAVA path is tail-biting consistent; its metric must match the
    # exhaustive optimum (bit equality follows when the optimum is unique)
    s0 = tail_bite_state(got, spec.k)
    got_metric = float(
        ((1.0 - 2.0 * conv_encode(got, spec, initial_state=s0)) * llr).sum()
    )
    np.testing.assert_allclose(got_metric, want_metric, rtol=1e-6)
    np.testing.assert_array_equal(got, want_bits)


def test_wava_kernel_and_packed_bit_identical():
    code = get_code("lte-tbcc")
    kb, kn = jax.random.split(jax.random.PRNGKey(7))
    bits = jax.random.bernoulli(kb, 0.5, (3, 128)).astype(jnp.int32)
    llrs = standard_llrs(kn, encode_standard(bits, code), 5.0, code)
    tables = build_acs_tables(code.spec, 2)
    a, _ = wava_decode(llrs, tables)
    b, _ = wava_decode(llrs, tables, use_kernel=True)
    c, _ = wava_decode(llrs, tables, pack_survivors=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


# ---------------------------------------------------------------------------
# front door: from_standard end to end (the PR's acceptance criteria)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["wifi-11a-r34", "lte-tbcc"])
def test_from_standard_recovers_at_6db_jnp_equals_kernel(name):
    code = get_code(name)
    pt, dec = measure_standard_ber(
        name, 6.0, 1024, jax.random.PRNGKey(11), n_frames=8
    )
    assert pt.ber == 0.0, f"{name} not BER-clean at 6 dB"
    # bit-exact between the jnp path and the Pallas kernel path
    kb, kn = jax.random.split(jax.random.PRNGKey(12))
    bits = jax.random.bernoulli(kb, 0.5, (4, 300)).astype(jnp.int32)
    llrs = standard_llrs(
        kn, encode_standard(tx_frames(bits, code), code), 6.0, code
    )
    a = ViterbiDecoder.from_standard(name).decode_batch(llrs)
    b = ViterbiDecoder.from_standard(name, use_kernel=True).decode_batch(llrs)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(a)[:, :300], np.asarray(bits))


def test_punctured_tiled_and_chunked_match_batch():
    """The puncture argument threads through every decode shape: tiled
    windows and chunked streaming agree with one-shot batch decode."""
    code = get_code("wifi-11a-r23")
    kb, kn = jax.random.split(jax.random.PRNGKey(13))
    n = 4096
    bits = jax.random.bernoulli(kb, 0.5, (1, n)).astype(jnp.int32)
    llrs = standard_llrs(kn, encode_standard(bits, code), 7.0, code)
    dec = ViterbiDecoder.from_standard(code.name, decision_depth=1024)
    batch = np.asarray(dec.decode_batch(llrs, initial_state=None))[0]
    tiled = np.asarray(dec.decode_stream_tiled(llrs[0]))
    chunked = np.asarray(
        dec.decode_stream_chunked(llrs, chunk_len=1000, initial_state=None)
    )[0]
    assert (tiled != batch).mean() < 2e-3  # tiling edge effects only
    np.testing.assert_array_equal(chunked, batch)
    np.testing.assert_array_equal(batch, np.asarray(bits)[0])


def test_punctured_decoder_stretches_depth_and_overlap():
    dec = ViterbiDecoder.from_standard("dvb-s-r78", decision_depth=1024)
    plain = ViterbiDecoder.from_standard("dvb-s")
    assert dec.decision_depth == int(
        -(-1024 * dec.puncture.expansion // 2) * 2
    )
    assert (
        dec.default_tiled_config().overlap
        > plain.default_tiled_config().overlap
    )


def test_tailbiting_rejects_stream_modes():
    dec = ViterbiDecoder.from_standard("lte-tbcc")
    llrs = jnp.zeros((1, 60, 3))
    with pytest.raises(ValueError, match="tail-biting|tiled"):
        dec.decode_stream_tiled(llrs[0])
    with pytest.raises(ValueError, match="tail-biting|chunked"):
        dec.decode_stream_chunked(llrs)


# ---------------------------------------------------------------------------
# rate-1/3 / beta audit (ISSUE satellite): every B = rho*beta derivation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rho", [1, 2])
def test_beta3_decode_matches_reference(rho):
    spec = get_code("lte-tbcc").spec  # beta = 3
    rng = np.random.default_rng(17)
    bits = tail_flush(rng.integers(0, 2, 120), spec)
    coded = conv_encode(bits, spec)
    llr = 1.0 - 2.0 * coded.astype(np.float64)
    llr = llr + rng.normal(0.0, 0.6, llr.shape)
    want = viterbi_decode_ref(llr, spec, initial_state=0, final_state=0)
    pad = (-len(bits)) % rho
    llr_p = np.concatenate([llr, np.zeros((pad, spec.beta))]) if pad else llr
    got = np.asarray(
        decode_frames(
            jnp.asarray(llr_p, jnp.float32)[None], spec, rho=rho,
            initial_state=0, final_state=0,
        )[0]
    )[: len(bits)]
    np.testing.assert_array_equal(got, want)


def test_beta3_kernel_matches_jnp():
    spec = get_code("lte-tbcc").spec
    rng = np.random.default_rng(19)
    llrs = jnp.asarray(rng.normal(size=(4, 64, 3)), jnp.float32)
    a = decode_frames(llrs, spec, rho=2, initial_state=None)
    b = decode_frames(llrs, spec, rho=2, initial_state=None, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_gsm_k5_decodes_and_packs():
    """K=5 (16 states): the packed-survivor path and blocks_from_llrs
    must not assume the k=7 shapes."""
    code = get_code("gsm-cs1")
    pt, _ = measure_standard_ber(
        code, 7.0, 456, jax.random.PRNGKey(23), n_frames=4
    )
    assert pt.ber == 0.0
    rng = np.random.default_rng(29)
    llrs = jnp.asarray(rng.normal(size=(2, 64, 2)), jnp.float32)
    a = decode_frames(llrs, code.spec, rho=2, initial_state=None)
    b = decode_frames(
        llrs, code.spec, rho=2, initial_state=None, pack_survivors=True
    )
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_codespec_accepts_list_polys():
    """ISSUE satellite: CodeSpec must hash (lru_cache keys, jit statics)
    even when constructed from a list of polynomials."""
    a = CodeSpec(k=7, polys=[0o133, 0o171, 0o165])
    b = CodeSpec(k=7, polys=(0o133, 0o171, 0o165))
    assert a == b and hash(a) == hash(b)
    assert build_acs_tables(a, 2) is build_acs_tables(b, 2)  # cache hit


def test_decode_batch_pads_odd_lengths():
    """decode_batch zero-LLR pads n % rho internally (punctured lengths
    land on odd stage counts all the time)."""
    spec = get_code("wifi-11a").spec
    rng = np.random.default_rng(31)
    bits = rng.integers(0, 2, 101)
    coded = conv_encode(bits, spec)
    llr = jnp.asarray(1.0 - 2.0 * coded, jnp.float32)[None]
    dec = ViterbiDecoder(spec)
    out = np.asarray(dec.decode_batch(llr, initial_state=0))[0]
    np.testing.assert_array_equal(out, bits)
    with pytest.raises(ValueError, match="final_state"):
        dec.decode_batch(llr, initial_state=0, final_state=0)
