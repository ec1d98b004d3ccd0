"""Compile rehearsal for a TPU v5e without the chip (DESIGN.md §8/§9).

The TPU compiler is installed alongside JAX, and it compiles for a chip
that is described rather than attached.  These tests AOT-compile the
three Pallas kernels with Mosaic (``interpret=False``) at the
``decode_64k`` cell's real shapes (512 frames x 65536 stages, ccsds-k7,
radix-4), check that each lowers to a ``tpu_custom_call``, check that
the padding-aware VMEM guards admit and refuse the same shapes Mosaic
does, and check that the XLA decode of the whole cell fits 16 GiB of HBM.
The two-pass kernel is also compiled at 256 states (the UMTS K=9 rate
1/3 code) at the ``umts.rmc1536`` cell's shapes.
Nothing here runs: a compile that passes is not a chip run.

All of it lives in this one file so one test worker loads the TPU
library; the topology is described inside a fixture, never at import,
and the tests skip where it cannot be described.  The persistent
compilation cache is off around these compiles (a TPU executable
written here cannot be read back without a chip).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.codes import get_code
from repro.core import CODE_K7_CCSDS, build_acs_tables
from repro.core.kernel_geometry import (
    DEFAULT_BLOCK_FRAMES,
    KERNEL_VMEM_BUDGET,
    VMEM_CAPACITY_BYTES,
    forward_time_tile,
    forward_vmem_bytes,
    fused_decode_vmem_bytes,
    one_pass_time_tile,
    pick_transfer_tile,
)
from repro.core.viterbi import AcsPrecision, decode_frames
from repro.kernels.viterbi_acs import (
    acs_decode_fused_pallas,
    acs_forward_pallas,
    transfer_matrix_pallas,
)

SPEC = CODE_K7_CCSDS
RHO = 2
F, N_STAGES = 512, 1 << 16  # the decode_64k cell
T = N_STAGES // RHO
S, R, B = SPEC.n_states, 1 << RHO, RHO * SPEC.beta
DEPTH_STEPS = 5120 // RHO  # default decision depth
CHUNK_STEPS = 4096 // RHO  # default chunk length
HBM_BYTES = 16 * 2**30
K9 = get_code("umts-k9-r13").spec  # 256 states, rate 1/3: B = 6


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def w(one_chip):
    tables = build_acs_tables(SPEC, RHO)
    return jax.ShapeDtypeStruct(tables.fused_w.shape, jnp.float32,
                                sharding=one_chip)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    lowered = jax.jit(fn).lower(*args)
    compiled = lowered.compile()
    return lowered.as_text(), compiled


@pytest.mark.parametrize("packed", [False, True], ids=["i8", "packed"])
def test_two_pass_kernel_compiles_at_decode_64k(one_chip, w, packed):
    text, _ = _compile(
        lambda b, l, w: acs_forward_pallas(
            b, l, w, n_states=S, n_slots=R, pack_survivors=packed,
            interpret=False,
        ),
        _sds((T, F, B), jnp.float32, one_chip),
        _sds((F, S), jnp.float32, one_chip),
        w,
    )
    assert "tpu_custom_call" in text


def _w_sds(spec, sharding):
    tables = build_acs_tables(spec, RHO)
    return jax.ShapeDtypeStruct(tables.fused_w.shape, jnp.float32,
                                sharding=sharding)


@pytest.mark.parametrize("f,steps", [(1024, 256), (512, 64)],
                         ids=["dtch_1024", "dcch_512"])
def test_two_pass_kernel_compiles_at_umts_rmc1536(one_chip, f, steps):
    """256 states, packed survivors (16 words per frame-step): the DTCH
    batch (1024 frames on the 512-stage rung) and a DCCH batch (512
    frames on the 128-stage rung) of the ``umts.rmc1536`` cell."""
    n_states, b = K9.n_states, RHO * K9.beta
    text, _ = _compile(
        lambda x, l, w: acs_forward_pallas(
            x, l, w, n_states=n_states, n_slots=R, pack_survivors=True,
            interpret=False,
        ),
        _sds((steps, f, b), jnp.float32, one_chip),
        _sds((f, n_states), jnp.float32, one_chip),
        _w_sds(K9, one_chip),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("spec", [SPEC, K9], ids=["k7", "k9"])
def test_two_pass_count_is_what_mosaic_needs(one_chip, spec, monkeypatch):
    """The two-pass kernel compiles with its scoped-VMEM limit set to
    the counted footprint alone, no headroom: the count covers what
    Mosaic allocates.  At 256 states three counted potentials left
    3.2 MiB out, and Mosaic refused the kernel at that limit."""
    from repro.kernels import viterbi_acs
    from repro.kernels.viterbi_acs import _acs_weights, _forward_call

    n_states, b, bf = spec.n_states, RHO * spec.beta, DEFAULT_BLOCK_FRAMES
    tt = forward_time_tile(256, bf, n_states, b, R, True)
    need = forward_vmem_bytes(tt, bf, n_states, b, R, True)
    monkeypatch.setattr(viterbi_acs, "vmem_limit_bytes", lambda n: n)

    def launch(x, l, w):
        theta, pred = _acs_weights(w, n_states, R, b, jnp.float32)
        return _forward_call(
            x, l, theta, pred, n_states=n_states, n_slots=R, n_steps=256,
            time_tile=tt, block_frames=bf, carry_dtype=jnp.float32,
            matmul_dtype=jnp.float32, renorm=True, pack_survivors=True,
            semiring="tropical", interpret=False,
        )

    text, _ = _compile(
        launch,
        _sds((256, b, 2 * bf), jnp.float32, one_chip),
        _sds((n_states, 2 * bf), jnp.float32, one_chip),
        _w_sds(spec, one_chip),
    )
    assert "tpu_custom_call" in text and need < KERNEL_VMEM_BUDGET


@pytest.mark.parametrize("packed", [False, True], ids=["i8", "packed"])
def test_one_pass_kernel_compiles_at_default_depth(one_chip, w, packed):
    """The default 5120-stage decision depth: the rule picks the largest
    common tile of depth and chunk (512 steps, 6 ring steps walked per
    ACS step), the guard admits the ring, and Mosaic compiles it (frames
    on lanes, (D+TT, W, BF))."""
    tt = one_pass_time_tile(DEPTH_STEPS, CHUNK_STEPS, S, packed)
    assert tt == 512
    W = S // 16 if packed else S
    text, _ = _compile(
        lambda b, l, h, w: acs_decode_fused_pallas(
            b, l, h, w, n_states=S, n_slots=R, k=SPEC.k, rho=RHO,
            time_tile=tt, pack_survivors=packed, interpret=False,
        ),
        _sds((CHUNK_STEPS, F, B), jnp.float32, one_chip),
        _sds((F, S), jnp.float32, one_chip),
        _sds((DEPTH_STEPS, F, W), jnp.int32 if packed else jnp.int8,
             one_chip),
        w,
    )
    assert "tpu_custom_call" in text


def test_one_pass_kernel_compiles_at_dvbs_r78_depth(one_chip, w):
    """DVB-S rate 7/8's stretched depth (5120 x 14/8 = 8960 stages, the
    deepest ring a benchmark cell runs) under 57344-stage session chunks
    of 4 frames: the rule picks a 896-step tile, the guard admits the
    ring, and Mosaic compiles it, packed and int8."""
    depth, chunk, f = 8960 // RHO, 57344 // RHO, 4
    for packed in (True, False):
        tt = one_pass_time_tile(depth, chunk, S, packed)
        assert tt == 896
        W = S // 16 if packed else S
        text, _ = _compile(
            lambda b, l, h, w: acs_decode_fused_pallas(
                b, l, h, w, n_states=S, n_slots=R, k=SPEC.k, rho=RHO,
                time_tile=tt, pack_survivors=packed, interpret=False,
            ),
            _sds((chunk, f, B), jnp.float32, one_chip),
            _sds((f, S), jnp.float32, one_chip),
            _sds((depth, f, W), jnp.int32 if packed else jnp.int8,
                 one_chip),
            w,
        )
        assert "tpu_custom_call" in text


def test_one_pass_guard_refuses_what_mosaic_refuses(one_chip, w):
    """A ring beyond VMEM: the guard refuses the shape (so the decoder
    takes the two-pass path), and Mosaic, compiling it anyway at the
    largest scoped limit, refuses it too."""
    from repro.kernels.viterbi_acs import _acs_weights, _fused_call

    depth, tt = 16 * DEPTH_STEPS, 32
    need = fused_decode_vmem_bytes(depth, tt, DEFAULT_BLOCK_FRAMES, S, B, R,
                                   True)
    assert need > VMEM_CAPACITY_BYTES > KERNEL_VMEM_BUDGET
    assert one_pass_time_tile(depth, CHUNK_STEPS, S, True) is None

    def launch(b, l, h, w):
        theta, pred = _acs_weights(w, S, R, B, jnp.float32)
        return _fused_call(
            b, l, h, theta, pred, n_states=S, n_slots=R, k=SPEC.k, rho=RHO,
            time_tile=tt, block_frames=DEFAULT_BLOCK_FRAMES,
            carry_dtype=jnp.float32, matmul_dtype=jnp.float32, renorm=True,
            pack_survivors=True, interpret=False,
        )

    with pytest.raises(Exception, match="(?i)vmem|resource|exhaust|memory"):
        _compile(
            launch,
            _sds((CHUNK_STEPS, B, F), jnp.float32, one_chip),
            _sds((S, F), jnp.float32, one_chip),
            _sds((depth, S // 16, F), jnp.int32, one_chip),
            w,
        )


@pytest.mark.parametrize("n_frames", [1, 4])
def test_transfer_kernel_compiles_at_decode_64k_length(one_chip, w,
                                                       n_frames):
    text, _ = _compile(
        lambda b, w: transfer_matrix_pallas(
            b, w, n_states=S, n_slots=R, transfer_tile=pick_transfer_tile(T),
            interpret=False,
        ),
        _sds((T, n_frames, B), jnp.float32, one_chip),
        w,
    )
    assert "tpu_custom_call" in text


def test_transfer_kernel_logprob_compiles(one_chip, w):
    """The §15 soft path runs the same kernel in the log semiring."""
    text, _ = _compile(
        lambda b, w: transfer_matrix_pallas(
            b, w, n_states=S, n_slots=R, transfer_tile=64,
            semiring="logprob", interpret=False,
        ),
        _sds((1024, 16, B), jnp.float32, one_chip),
        w,
    )
    assert "tpu_custom_call" in text


def test_xla_decode_64k_fits_hbm(one_chip):
    """The XLA decode of the whole cell fits one chip's HBM."""
    _, compiled = _compile(
        lambda x: decode_frames(x, SPEC, RHO, 0, None, AcsPrecision()),
        _sds((F, N_STAGES, SPEC.beta), jnp.float32, one_chip),
    )
    mem = compiled.memory_analysis()
    total = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
    )
    assert 0 < total < HBM_BYTES
