"""The plain reference against an independent NumPy float64 Viterbi, the
program's decoder and the bits that were sent."""
import numpy as np

from benchlib import channel, reference
from np_viterbi import viterbi as np_viterbi

CCSDS, K = (0o171, 0o133), 7


def _llrs(seed, rows, n, ebn0, **kw):
    bits, llrs = channel.make_llrs(channel.jax_key(seed, 9), rows, n, CCSDS,
                                   K, ebn0, **kw)
    return np.asarray(bits), np.asarray(llrs)


def test_matches_numpy_float64():
    _, x = _llrs(1, 6, 700, 1.5)
    ends = np.array([700, 333, 512, 1, 699, 64])
    for start_zero in (True, False):
        a = reference.viterbi(x, CCSDS, K, start_zero, ends)
        b = np_viterbi(x, CCSDS, K, start_zero, ends)
        assert np.array_equal(a, b)


def test_recovers_sent_bits_at_high_snr():
    bits, x = _llrs(2, 3, 512, 9.0)
    assert np.array_equal(reference.viterbi(x, CCSDS, K, True), bits)


def test_matches_the_program():
    from repro.core import ViterbiDecoder

    _, x = _llrs(3, 4, 1024, 2.5)
    dec = ViterbiDecoder.from_standard("ccsds-k7")
    prog = np.asarray(dec.decode_batch(x, initial_state=0,
                                       time_parallel=False))
    assert np.array_equal(reference.viterbi(x, CCSDS, K, True), prog)


def test_path_gap():
    bits, x = _llrs(4, 1, 400, 3.0)
    ref = reference.viterbi(x, CCSDS, K, True)[0]
    assert reference.path_gap(x[0], ref, ref, CCSDS, K) == 0.0
    bad = ref.copy()
    bad[200] ^= 1
    gap = reference.path_gap(x[0], ref, bad, CCSDS, K)
    assert gap > 0  # any other path scores below the ML path
    # the gap is the metric difference of the two re-encoded paths
    def metric(b):
        return float(np.sum(x[0] * (1 - 2 * reference.encode_np(b, CCSDS,
                                                                 K))))
    assert np.isclose(gap, metric(ref) - metric(bad))


def test_depuncture_places_sent_llrs():
    mask = ((1, 1), (1, 0), (0, 1))
    kept = np.arange(1, 9, dtype=np.float32)  # 6 stages keep 8 bits
    out = reference.depuncture(kept, mask, 6, 2)
    assert out.tolist() == [[1, 2], [3, 0], [0, 4], [5, 6], [7, 0], [0, 8]]
