"""The UMTS rate-1/3 K=9 code (3GPP TS 25.212 §4.2.3.1): 256 states
through the two-pass frame kernel (interpret mode on the CPU) against
the scalar reference, through ``DecodeEngine``'s "batch" route, and the
spans that route reports."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.codes import encode_standard, get_code, standard_llrs
from repro.core import ViterbiDecoder
from repro.core.encoder import conv_encode
from repro.core.viterbi_ref import viterbi_decode_ref
from repro.kernels.viterbi_acs import forward_plan
from repro.obs import SpanRecorder
from repro.serve.engine import DecodeEngine, DecodeRequest

CODE = "umts-k9-r13"
TAIL = 8


def _frames(n_frames, n_stages, seed, ebn0=1.0):
    """(bits, LLRs) of zero-tailed code blocks of ``n_stages`` stages."""
    code = get_code(CODE)
    rng = np.random.default_rng(seed)
    bits = rng.integers(0, 2, (n_frames, n_stages))
    bits[:, n_stages - TAIL:] = 0
    llrs = standard_llrs(
        jax.random.PRNGKey(seed),
        encode_standard(jnp.asarray(bits, jnp.int32), code), ebn0, code,
    )
    return bits, np.asarray(llrs)


def _metric(llrs, bits):
    """Path metric of ``bits`` from state 0: sum of LLR * (+-1) symbol."""
    coded = conv_encode(np.asarray(bits, np.int64), get_code(CODE).spec)
    return float(np.sum(np.asarray(llrs, np.float64) * (1 - 2 * coded)))


def test_registry_entry():
    code = get_code(CODE)
    assert code.spec.k == 9 and code.spec.polys == (0o557, 0o663, 0o711)
    assert code.spec.n_states == 256 and code.spec.beta == 3
    assert code.rate == pytest.approx(1 / 3)
    assert code.termination == "zero" and code.puncture is None
    assert code.family == "umts" and "25.212" in code.notes


@pytest.mark.parametrize("n_stages", [120, 268], ids=["dcch", "dtch"])
def test_kernel_decode_batch_equals_reference(n_stages):
    """Two frames at 1 dB, where the decisions are far from clean: the
    kernel's bits are the reference's, and so is their path metric."""
    bits, llrs = _frames(2, n_stages, seed=n_stages)
    dec = ViterbiDecoder.from_standard(CODE, use_kernel=True)
    assert dec.ring_packed  # 16 survivor words per frame-step
    got = np.asarray(dec.decode_batch(llrs))
    assert got.shape == (2, n_stages)
    for f in range(2):
        ref = viterbi_decode_ref(llrs[f], get_code(CODE).spec)
        np.testing.assert_array_equal(got[f], ref)
        assert _metric(llrs[f], got[f]) == _metric(llrs[f], ref)
    assert (got != bits).any()  # the channel left errors to decode


def test_engine_mixed_sizes_route_batch():
    """DCCH and DTCH frames through the engine, kernel on: the
    throughput class takes the "batch" route by shape, and every
    ticket's bits equal the frame decoded directly."""
    _, dcch = _frames(3, 120, seed=1)
    _, dtch = _frames(2, 268, seed=2)
    frames = [*dcch, *dtch]
    engine = DecodeEngine(use_kernel=True, max_batch=4)
    tickets = [engine.submit(DecodeRequest(llrs=x, code=CODE,
                                           slo="throughput"), now=0.0)
               for x in frames]
    engine.drain(now=1.0)
    dec = ViterbiDecoder.from_standard(CODE)
    for t, x in zip(tickets, frames):
        assert t.path == "batch"
        want = np.asarray(dec.decode_batch(x[None], initial_state=0))[0]
        np.testing.assert_array_equal(t.bits, want)


def test_frame_path_spans_count_padding_and_tile():
    """engine.assemble counts the LLR elements the requests carried and
    the cell's; decoder.launch names the trellis, the radix steps after
    padding and the time tile the two-pass kernel takes."""
    rec = SpanRecorder()
    engine = DecodeEngine(use_kernel=True, max_batch=4, recorder=rec)
    _, dtch = _frames(3, 268, seed=3)
    for x in dtch:
        engine.submit(DecodeRequest(llrs=x, code=CODE, slo="throughput"),
                      now=0.0)
    engine.drain(now=1.0)
    (assemble,) = rec.find("engine.assemble")
    assert assemble.attrs == {"n_real": 3, "f": 4,
                              "real_elems": 3 * 268 * 3,
                              "cell_elems": 4 * 512 * 3}
    (launch,) = rec.find("decoder.launch")
    tile = forward_plan(3, 256, 256, 6, 4, True)[2]
    assert launch.attrs == {"n_states": 256, "steps": 256,
                            "fwd_time_tile": tile}
    assert tile == 128
