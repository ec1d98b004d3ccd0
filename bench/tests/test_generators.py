"""The seeded generators: the same seed gives the same requests, another
seed gives other ones, and every seed of an open loop gets the same
multiset of sizes and gaps."""
import numpy as np
import pytest

from benchlib import channel, loops
from benchlib.spec import load_cell
from tiny import cell_of

BIG = 2**33 + 17  # wider than 32 bits, as the driver's seeds are


def _frames(seed, seconds=2.0):
    cell = cell_of("wifi.steady")
    cell.traffic.update(rate_per_s=40.0, drain_s=0.5)
    return loops.make_driver(cell.traffic, cell.config, seed, seconds)


def _sessions(seed):
    cell = load_cell("ccsds.links8")
    cell.traffic.update(sessions=3, chunk_stages=256, pool_chunks=4)
    return loops.make_driver(cell.traffic, cell.config, seed, 1.0)


def test_channel_same_seed_same_llrs():
    polys, k = (0o171, 0o133), 7
    a = channel.make_llrs(channel.jax_key(BIG, 1), 2, 64, polys, k, 2.5)
    b = channel.make_llrs(channel.jax_key(BIG, 1), 2, 64, polys, k, 2.5)
    c = channel.make_llrs(channel.jax_key(BIG + 1, 1), 2, 64, polys, k, 2.5)
    assert np.array_equal(np.asarray(a[1]), np.asarray(b[1]))
    assert not np.array_equal(np.asarray(a[1]), np.asarray(c[1]))


def test_encoder_matches_the_program():
    """The copied encoder and puncturing agree with the program's."""
    import jax.numpy as jnp
    from repro.codes import encode_standard, get_code

    bits = np.random.default_rng(0).integers(0, 2, (3, 96)).astype(np.int32)
    for name, mask in (("ccsds-k7", None),
                       ("wifi-11a-r34", ((1, 1), (1, 0), (0, 1)))):
        code = get_code(name)
        mine = channel.encode(jnp.asarray(bits), code.spec.polys, 7, False)
        mine = np.asarray(mine).reshape(3, -1)[
            :, channel.kept_index(mask, 96, 2)]
        theirs = np.asarray(encode_standard(jnp.asarray(bits), code))
        assert np.array_equal(mine, theirs.reshape(3, -1))


def test_frames_deterministic_per_seed():
    a, b, c = _frames(BIG), _frames(BIG), _frames(BIG + 1)
    assert a.reqs == b.reqs and np.array_equal(a.offsets, b.offsets)
    assert all(np.array_equal(a.llrs(r), b.llrs(r))
               for r in range(len(a.reqs)))
    assert a.reqs != c.reqs
    assert not np.array_equal(a.llrs(0)[:8], c.llrs(0)[:8])
    # the same work in another order
    assert sorted(a.reqs) == sorted(c.reqs)
    assert np.isclose(a.offsets[-1], c.offsets[-1])


def test_closed_pool_blocks_hold_every_stratum():
    """A closed loop's pool: every block of ``clients`` frames holds one
    frame of each stratum of the mix, in the seed's order."""
    cell = cell_of("wifi.closed64")
    cell.traffic.update(clients=8, pool_requests=64)
    d = [loops.make_driver(cell.traffic, cell.config, s, 1.0)
         for s in (BIG, BIG, BIG + 1)]
    assert d[0].reqs == d[1].reqs and d[0].reqs != d[2].reqs
    assert sorted(d[0].reqs) == sorted(d[2].reqs)
    order = loops._blocks(64, 8, np.random.default_rng(BIG))
    assert sorted(order) == list(range(64))
    for b in range(8):
        assert sorted(order[8 * b: 8 * b + 8] // 8) == list(range(8))
    with pytest.raises(ValueError):
        loops._blocks(60, 8, np.random.default_rng(BIG))


def test_frames_follow_the_standard():
    d = _frames(BIG)
    for r, (code, length) in enumerate(d.reqs):
        _, _, nst, psdu = d.rows[r]
        n_dbps = d.codes[code]["n_dbps"]
        assert psdu == 8 * length
        assert nst % n_dbps == 0
        assert nst - n_dbps < 16 + 8 * length + 6 <= nst
        x = d.llrs(r)
        if code == "wifi-11a-r34":
            assert x.shape == (nst * 4 // 3,)
        else:
            assert x.shape == (nst, 2)


def test_sessions_deterministic_per_seed():
    a, b, c = _sessions(BIG), _sessions(BIG), _sessions(BIG + 1)
    assert np.array_equal(a.pool, b.pool)
    assert np.array_equal(a.offsets, b.offsets)
    assert not np.array_equal(a.pool, c.pool)


def test_session_stream_is_one_codeword():
    """Cycling the pool gives one continuous codeword: a noiseless pool
    decodes without a single error across the wrap."""
    from np_viterbi import viterbi

    d = _sessions(BIG)
    bits, llrs = channel.make_llrs(
        channel.jax_key(BIG, 2), 1, 4 * 256, (0o171, 0o133), 7, 30.0,
        circular=True)
    pool = np.asarray(llrs).reshape(4, 256, 2)
    d.pool = pool
    stream = d.stream_llrs(0, 0, 8 * 256)  # twice round the pool
    out = viterbi(stream[None], (0o171, 0o133), 7, start_zero=False)[0]
    want = np.tile(np.roll(np.asarray(bits)[0], -d.offsets[0] * 256), 2)
    assert np.array_equal(out[64:-64], want[64:-64])


def test_bursts_keep_the_mean_and_stay_in_on_periods():
    cell = cell_of("wifi.steady")
    cell.traffic.update(rate_per_s=40.0, drain_s=0.5,
                        burst={"on_s": 0.2, "off_s": 0.3})
    a = loops.make_driver(cell.traffic, cell.config, BIG, 2.0)
    b = loops.make_driver(cell.traffic, cell.config, BIG + 1, 2.0)
    assert np.all(np.mod(a.offsets, 0.5) < 0.2)
    n = len(a.offsets)
    assert abs(a.offsets[-1] - n / 40.0) < 0.5  # the mean rate holds
    assert np.isclose(a.offsets[-1], b.offsets[-1])  # the same on-time


def test_punctured_session_pool_is_serial_and_one_codeword():
    """A punctured code's session chunks are serial kept-LLR streams of
    whole pattern periods, and the depunctured stream is one codeword."""
    from np_viterbi import viterbi

    cell = load_cell("ccsds.links8")
    mask = [[1, 1], [1, 0], [0, 1]]
    cell.config["codes"] = {"wifi-11a-r34": dict(
        k=7, polys_octal=["133", "171"], puncture=mask)}
    cell.config["ebn0_db"] = {"wifi-11a-r34": 30.0}
    cell.traffic.update(code="wifi-11a-r34", sessions=2, chunk_stages=258,
                        pool_chunks=4)
    d = loops.make_driver(cell.traffic, cell.config, BIG, 1.0)
    assert d.pool.shape == (4, 258 * 4 // 3)
    bits, _ = channel.make_llrs(channel.jax_key(BIG, 2), 1, 4 * 258,
                                (0o133, 0o171), 7, 30.0, mask=mask,
                                circular=True)
    stream = d.stream_llrs(1, 0, 8 * 258)
    assert stream.shape == (8 * 258, 2)
    out = viterbi(stream[None], (0o133, 0o171), 7, start_zero=False)[0]
    want = np.tile(np.roll(np.asarray(bits)[0], -d.offsets[1] * 258), 2)
    assert np.array_equal(out[64:-64], want[64:-64])
    cell.traffic["chunk_stages"] = 256  # not whole periods of 3
    with pytest.raises(ValueError):
        loops.make_driver(cell.traffic, cell.config, BIG, 1.0)


def test_a_mix_may_bring_its_own_driver(tmp_path, monkeypatch):
    """``traffic/<mix>.py`` is found by the mix's name and builds the
    driver; without one the loops here do."""
    from benchlib import spec

    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "mine.py").write_text(
        "from benchlib import loops\n"
        "class Mine(loops.FrameDriver):\n"
        "    def arrivals(self, n, rng):\n"
        "        return 0.01 * (1 + rng.permutation(n))\n"
        "def make_driver(traffic, config, seed, seconds):\n"
        "    codes = loops.codes_of(config)\n"
        "    return Mine(traffic, config, codes, config['ebn0_db'], seed,"
        " seconds)\n")
    cell = cell_of("wifi.steady")
    cell.traffic.update(rate_per_s=40.0, drain_s=0.5)
    assert isinstance(loops.driver_for(cell, BIG, 1.0), loops.FrameDriver)
    monkeypatch.setattr(spec, "BENCH_DIR", tmp_path)
    cell.traffic_name = "mine"
    d = loops.driver_for(cell, BIG, 1.0)
    assert type(d).__name__ == "Mine"
    assert np.allclose(np.sort(d.offsets), 0.01 * np.arange(1, 61))
