"""Pallas ACS kernel vs pure-jnp oracle: shape/dtype sweeps + properties."""
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core import CODE_K7_CCSDS, CodeSpec, build_acs_tables, decode_frames
from repro.core.viterbi import AcsPrecision, blocks_from_llrs, init_metric
from repro.kernels.ops import viterbi_forward
from repro.kernels.ref import acs_forward_ref
from repro.kernels.viterbi_acs import unpack_survivors

SPECS = {
    "k3": CodeSpec(k=3, polys=(0o7, 0o5)),
    "k5": CodeSpec(k=5, polys=(0o27, 0o31)),
    "k7": CODE_K7_CCSDS,
    "k7r3": CodeSpec(k=7, polys=(0o171, 0o133, 0o165)),
}


def _run_both(spec, rho, n_frames, n_stages, seed=0, precision=None, **kw):
    tb = build_acs_tables(spec, rho)
    rng = np.random.default_rng(seed)
    llr = jnp.asarray(
        rng.normal(0, 1, (n_frames, n_stages, spec.beta)), jnp.float32
    )
    blocks = blocks_from_llrs(llr, rho)
    lam0 = init_metric(n_frames, spec.n_states, None)
    precision = precision or AcsPrecision()
    lam_r, phi_r = acs_forward_ref(
        blocks,
        lam0,
        jnp.asarray(tb.fused_w),
        n_states=tb.n_states,
        n_slots=tb.n_slots,
        carry_dtype=precision.carry_dtype,
        matmul_dtype=precision.matmul_dtype,
        renorm=precision.renorm,
    )
    lam_k, phi_k = viterbi_forward(blocks, lam0, tb, precision, **kw)
    return lam_r, phi_r, lam_k, phi_k


@pytest.mark.parametrize("spec_name", list(SPECS))
@pytest.mark.parametrize("rho", [1, 2])
def test_kernel_matches_ref_shapes(spec_name, rho):
    spec = SPECS[spec_name]
    lam_r, phi_r, lam_k, phi_k = _run_both(spec, rho, 48, 24)
    np.testing.assert_allclose(lam_r, lam_k, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(phi_r, phi_k)


@pytest.mark.parametrize(
    "matmul_dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"]
)
@pytest.mark.parametrize(
    "carry_dtype", [jnp.float32, jnp.bfloat16], ids=["cf32", "cbf16"]
)
def test_kernel_dtype_sweep(matmul_dtype, carry_dtype):
    """All four precision corners of the paper's Table I."""
    prec = AcsPrecision(matmul_dtype=matmul_dtype, carry_dtype=carry_dtype)
    lam_r, phi_r, lam_k, phi_k = _run_both(
        SPECS["k7"], 2, 32, 32, precision=prec
    )
    np.testing.assert_allclose(lam_r, lam_k, rtol=1e-2, atol=1e-2)
    # survivor decisions must agree between kernel and oracle at equal dtypes
    agree = (np.array(phi_r) == np.array(phi_k)).mean()
    assert agree > 0.999


def test_kernel_frame_padding():
    """F not a multiple of the frame tile exercises the pad/unpad path."""
    for F in (1, 7, 255, 257):
        lam_r, phi_r, lam_k, phi_k = _run_both(SPECS["k7"], 2, F, 8)
        np.testing.assert_allclose(lam_r, lam_k, rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(phi_r, phi_k)


@pytest.mark.parametrize("n_stages", [256, 600, 1030])
@pytest.mark.parametrize("pack", [False, True], ids=["i8", "packed"])
def test_kernel_time_grid_matches_ref(n_stages, pack):
    """The two-pass kernel's time grid (128-step tiles, carry in VMEM
    scratch): one full tile, and ragged last tiles whose padded steps
    must leave the carry untouched."""
    lam_r, phi_r, lam_k, phi_k = _run_both(
        SPECS["k7"], 2, 5, n_stages, seed=n_stages, pack_survivors=pack
    )
    np.testing.assert_array_equal(np.asarray(lam_r), np.asarray(lam_k))
    if pack:
        phi_k = unpack_survivors(phi_k, 64, 4)
    np.testing.assert_array_equal(phi_r, phi_k)


def test_kernel_survivor_packing_roundtrip():
    """pack_survivors returns the PACKED (T, F, S//16) int32 words —
    eager unpacking would re-materialize exactly the tensor packing
    exists to avoid; traceback consumes the words natively."""
    lam_r, phi_r, lam_k, phi_k = _run_both(
        SPECS["k7"], 2, 130, 16, pack_survivors=True
    )
    assert phi_k.dtype == jnp.int32 and phi_k.shape == (8, 130, 4)
    np.testing.assert_array_equal(
        phi_r, unpack_survivors(phi_k, 64, 4)
    )
    np.testing.assert_allclose(lam_r, lam_k, rtol=1e-5, atol=1e-5)


def test_kernel_packed_traceback_end_to_end():
    """decode_frames(use_kernel=True, pack_survivors=True): the packed
    phi flows straight into the lazy-unpacking traceback (this path used
    to re-materialize the int8 tensor first)."""
    spec = SPECS["k7"]
    rng = np.random.default_rng(12)
    llr = jnp.asarray(rng.normal(0, 1, (4, 96, spec.beta)), jnp.float32)
    a = decode_frames(llr, spec, 2, None, None, use_kernel=True)
    b = decode_frames(llr, spec, 2, None, None, use_kernel=True,
                      pack_survivors=True)
    c = decode_frames(llr, spec, 2, None, None)
    np.testing.assert_array_equal(np.array(a), np.array(b))
    np.testing.assert_array_equal(np.array(a), np.array(c))


def test_unpack_survivors_inverse():
    rng = np.random.default_rng(3)
    phi = rng.integers(0, 4, (5, 6, 64)).astype(np.int8)
    packed = np.zeros((5, 6, 4), dtype=np.int32)
    for g in range(4):
        for b in range(16):
            packed[..., g] |= phi[..., g * 16 + b].astype(np.int32) << (2 * b)
    out = np.array(unpack_survivors(jnp.asarray(packed), 64, 4))
    np.testing.assert_array_equal(out, phi)


@given(
    n_frames=st.integers(1, 40),
    n_steps=st.integers(1, 12),
    seed=st.integers(0, 1000),
)
@settings(max_examples=15, deadline=None)
def test_property_kernel_equiv(n_frames, n_steps, seed):
    lam_r, phi_r, lam_k, phi_k = _run_both(
        SPECS["k5"], 2, n_frames, 2 * n_steps, seed=seed
    )
    np.testing.assert_allclose(lam_r, lam_k, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(phi_r, phi_k)


def test_end_to_end_decode_with_kernel():
    """decode_frames(use_kernel=True) == decode_frames(use_kernel=False)."""
    from repro.core.encoder import conv_encode, tail_flush

    spec = SPECS["k7"]
    rng = np.random.default_rng(9)
    bits = tail_flush(rng.integers(0, 2, 250), spec)
    coded = conv_encode(bits, spec)
    llr = (1.0 - 2.0 * coded) + rng.normal(0, 0.6, coded.shape)
    llr = jnp.asarray(llr, jnp.float32)[None]
    a = decode_frames(llr, spec, 2, 0, 0, use_kernel=False)
    b = decode_frames(llr, spec, 2, 0, 0, use_kernel=True)
    np.testing.assert_array_equal(np.array(a), np.array(b))
    np.testing.assert_array_equal(np.array(a[0])[: len(bits)], bits)
