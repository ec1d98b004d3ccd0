"""Multi-tenant DecodeEngine (DESIGN.md §10): cell bucketing
determinism, bit-exactness of engine output vs direct ViterbiDecoder
decode for every registry code (punctured + tail-biting), SLO -> path
routing, session eviction/flush equivalence to uninterrupted chunked
streaming, jit-cache hit accounting, and the max-wait / backpressure
policies — all on the virtual clock, so every assertion is
deterministic."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.codes import (
    REGISTRY,
    depuncture,
    encode_standard,
    get_code,
    standard_llrs,
)
from repro.core import decoder as decoder_mod
from repro.core.decoder import ViterbiDecoder
from repro.core.kernel_geometry import pick_cell_frames, pick_cell_length
from repro.core.trellis import build_transitions
from repro.core.viterbi_ref import forward_ref, traceback_ref
from repro.obs import SpanRecorder
from repro.serve.engine import DecodeEngine, DecodeRequest


def _request(code_name, n_bits, slo, seed, ebn0=5.0):
    """(true bits, DecodeRequest) through the standard tx chain."""
    rng = np.random.default_rng(seed)
    code = get_code(code_name)
    bits = jnp.asarray(rng.integers(0, 2, (1, n_bits)), jnp.int32)
    llrs = standard_llrs(
        jax.random.PRNGKey(seed), encode_standard(bits, code), ebn0, code
    )
    return np.asarray(bits)[0], DecodeRequest(
        llrs=np.asarray(llrs)[0], code=code_name, slo=slo
    )


def _direct(code_name, llrs):
    """The engine's decode contract, run directly: zero-terminated
    frames pin the initial state to 0 (the §7 framing contract — every
    frame starts there) with an argmax final end, tail-biting codes
    run WAVA."""
    dec = ViterbiDecoder.from_standard(code_name)
    if dec.termination == "tailbiting":
        return np.asarray(dec.decode_tailbiting(llrs[None])[0])[0]
    return np.asarray(
        dec.decode_batch(llrs[None], initial_state=0, final_state=None)
    )[0]


def test_cell_rungs():
    """Bucketing geometry (DESIGN.md §10): power-of-two ladders with a
    floor, punctured multiples, and the frame-rung cap."""
    assert pick_cell_length(1) == 64
    assert pick_cell_length(64) == 64
    assert pick_cell_length(65) == 128
    assert pick_cell_length(129, multiple=3) == 258
    with pytest.raises(ValueError):
        pick_cell_length(0)
    assert pick_cell_frames(1, 32) == 1
    assert pick_cell_frames(5, 32) == 8
    assert pick_cell_frames(33, 32) == 32
    assert pick_cell_frames(40, 48) == 48


def test_engine_bitexact_every_registry_code():
    """Engine output == direct ViterbiDecoder decode, bit for bit, for
    a mixed ragged workload over EVERY registry standard — ragged
    lengths pad to cell rungs with trailing zero LLRs (information-free
    stages, the §7 erasure argument), tail-biting cells stay
    exact-length."""
    reqs, refs = [], []
    for i, name in enumerate(sorted(REGISTRY)):
        tb = REGISTRY[name].termination == "tailbiting"
        for j, n in enumerate((40,) if tb else (57, 90)):
            _, req = _request(name, n, "throughput", 31 * i + j)
            reqs.append(req)
            refs.append(_direct(name, req.llrs))
    engine = DecodeEngine(max_batch=8)
    outs = engine.decode(reqs)
    for out, ref in zip(outs, refs):
        np.testing.assert_array_equal(out, ref)
    s = engine.stats()
    assert s["completed"] == len(reqs)
    assert s["queue_depth"] == 0


def test_bucketing_deterministic():
    """Two fresh engines fed the same timed submissions assemble the
    same cells in the same order and produce identical bits."""
    reqs = []
    for i in range(10):
        _, req = _request("ccsds-k7", 48 + 7 * i, "throughput", seed=i)
        reqs.append(req)
    logs, outs = [], []
    for _ in range(2):
        engine = DecodeEngine(max_batch=4)
        outs.append(engine.decode(reqs))
        logs.append([
            (b["cell"], b["f_cell"], b["n_real"], b["path"], b["tickets"])
            for b in engine.batch_log
        ])
    assert logs[0] == logs[1]
    for a, b in zip(*outs):
        np.testing.assert_array_equal(a, b)


def test_slo_routing_table():
    """The §10 routing table: tail-biting -> wava regardless of SLO;
    latency-class cells that underfill the (injected) device budget ->
    time_parallel, bit-identical to the sequential path; throughput ->
    dense batch."""
    engine = DecodeEngine(underfill_rows=1024)
    bits_tp, req_tp = _request("ccsds-k7", 512, "latency", seed=3)
    t_tp = engine.submit(req_tp, now=0.0)
    _, req_bat = _request("ccsds-k7", 512, "throughput", seed=4)
    t_bat = engine.submit(req_bat, now=0.0)
    _, req_tb = _request("lte-tbcc", 40, "latency", seed=5)
    t_tb = engine.submit(req_tb, now=0.0)
    engine.drain(now=0.0)
    assert (t_tp.path, t_bat.path, t_tb.path) == (
        "time_parallel", "batch", "wava"
    )
    np.testing.assert_array_equal(t_tp.bits, _direct("ccsds-k7", req_tp.llrs))
    np.testing.assert_array_equal(
        t_bat.bits, _direct("ccsds-k7", req_bat.llrs)
    )
    # CPU budget (underfill_rows=0) keeps latency traffic sequential
    engine_cpu = DecodeEngine(underfill_rows=0)
    t_seq = engine_cpu.submit(req_tp, now=0.0)
    engine_cpu.drain(now=0.0)
    assert t_seq.path == "batch"
    np.testing.assert_array_equal(t_seq.bits, t_tp.bits)


def test_sharded_dispatch():
    """Cells whose frame rung fills the mesh route onto the §6 sharded
    frame decoder and stay bit-identical (1 CPU device: every rung
    fills it)."""
    from repro.distributed.decoder import engine_dispatch_ready, frame_mesh

    mesh = frame_mesh()
    assert engine_dispatch_ready(1, mesh)
    engine = DecodeEngine(mesh=mesh, max_batch=4)
    refs, reqs = [], []
    for i in range(4):
        _, req = _request("ccsds-k7", 70, "throughput", seed=20 + i)
        reqs.append(req)
        refs.append(_direct("ccsds-k7", req.llrs))
    outs = engine.decode(reqs)
    assert engine.batch_log[0]["path"] == "sharded"
    for out, ref in zip(outs, refs):
        np.testing.assert_array_equal(out, ref)


def test_jit_cache_no_recompile_same_cell():
    """Repeated same-cell batches hit the engine's fn cache (and so
    jax's trace cache): misses stay flat, hits climb."""
    engine = DecodeEngine(max_batch=4)
    for round_ in range(3):
        reqs = [
            _request("ccsds-k7", 60, "throughput", seed=50 + 4 * round_ + i)[1]
            for i in range(4)
        ]
        engine.decode(reqs)
        cache = engine.stats()["jit_cache"]
        assert cache["misses"] == 1
        assert cache["hits"] == round_
        assert cache["entries"] == 1


def test_max_wait_and_backpressure():
    """Assembly policy on the virtual clock: a lone latency request
    waits max_wait then flushes; a full cell flushes immediately; past
    max_pending, submissions are dropped with the rejected counter."""
    engine = DecodeEngine(
        max_batch=4, max_wait={"latency": 0.001, "throughput": 0.010}
    )
    _, req = _request("ccsds-k7", 60, "latency", seed=70)
    t = engine.submit(req, now=0.0)
    assert engine.poll(now=0.0005) == []  # deadline not reached
    assert not t.done
    done = engine.poll(now=0.0011)
    assert done == [t] and t.done and t.sojourn == pytest.approx(0.0011)
    # a full cell flushes at once, before any deadline
    tickets = [
        engine.submit(_request("ccsds-k7", 60, "latency", 71 + i)[1], now=0.1)
        for i in range(4)
    ]
    assert all(x.done for x in engine.poll(now=0.1))
    assert all(t.done for t in tickets)
    # backpressure
    engine2 = DecodeEngine(max_pending=1)
    a = engine2.submit(req, now=0.0)
    b = engine2.submit(req, now=0.0)
    assert not a.dropped and b.dropped
    assert engine2.stats()["rejected"] == 1


def test_session_multi_tenant_equivalence():
    """Sessions at DIFFERENT stream positions fuse into one dispatch
    and each still equals uninterrupted decode_stream_chunked; closing
    flushes the ring tail."""
    rng = np.random.default_rng(8)
    dec = ViterbiDecoder.from_standard("ccsds-k7", decision_depth=256)
    llr_a = rng.normal(0, 1, (1, 1024, 2)).astype(np.float32)
    llr_b = rng.normal(0, 1, (1, 768, 2)).astype(np.float32)
    ref_a = np.asarray(
        dec.decode_stream_chunked(llr_a, chunk_len=256, initial_state=None)
    )[0]
    ref_b = np.asarray(
        dec.decode_stream_chunked(llr_b, chunk_len=256, initial_state=None)
    )[0]
    engine = DecodeEngine(decision_depth=256)
    sa = engine.open_session("ccsds-k7", now=0.0)
    t0 = engine.submit_chunk(sa, llr_a[0, :256], now=0.0)
    engine.poll(now=0.0)  # A is now 256 stages ahead of B
    sb = engine.open_session("ccsds-k7", now=0.1)
    got = {sa: [t0.bits], sb: []}
    for lo in range(0, 768, 256):
        t1 = engine.submit_chunk(sa, llr_a[0, 256 + lo: 512 + lo], now=0.2)
        t2 = engine.submit_chunk(sb, llr_b[0, lo: lo + 256], now=0.2)
        done = engine.poll(now=0.2)
        assert {t1.id, t2.id} == {t.id for t in done}
        assert engine.batch_log[-1]["n_real"] == 2  # fused dispatch
        got[sa].append(t1.bits)
        got[sb].append(t2.bits)
    got[sa].append(engine.close_session(sa))
    got[sb].append(engine.close_session(sb))
    np.testing.assert_array_equal(np.concatenate(got[sa]), ref_a)
    np.testing.assert_array_equal(np.concatenate(got[sb]), ref_b)
    assert engine.stats()["sessions"] == 0


def test_session_punctured_serial_chunks():
    """Punctured sessions consume serial kept-LLR chunks in whole
    pattern periods; per-chunk depuncture == whole-stream depuncture,
    so the engine stream equals decode_stream_chunked on the serial
    stream."""
    rng = np.random.default_rng(9)
    dec = ViterbiDecoder.from_standard("wifi-11a-r34", decision_depth=256)
    serial = rng.normal(0, 1, (1, 512)).astype(np.float32)  # 512 % 4 == 0
    ref = np.asarray(
        dec.decode_stream_chunked(serial, chunk_len=4096, initial_state=None)
    )[0]
    engine = DecodeEngine(decision_depth=256)
    sid = engine.open_session("wifi-11a-r34", now=0.0)
    outs = []
    for lo in range(0, 512, 128):
        t = engine.submit_chunk(sid, serial[0, lo: lo + 128], now=0.0)
        engine.poll(now=0.0)
        outs.append(t.bits)
    outs.append(engine.close_session(sid))
    np.testing.assert_array_equal(np.concatenate(outs), ref)
    with pytest.raises(ValueError):  # partial period rejected
        sid2 = engine.open_session("wifi-11a-r34", now=0.0)
        engine.submit_chunk(sid2, serial[0, :126], now=0.0)


def test_punctured_session_chunk_is_depunctured_on_the_host(monkeypatch):
    """A dvb-s-r78 chunk is depunctured with numpy inside
    ``submit_chunk``, under an ``engine.depuncture`` span: no JAX
    depuncture runs and nothing crosses to or from the device until the
    group's one stacked copy."""
    import importlib

    # the package re-exports ``puncture`` (the function) over its module
    puncture_mod = importlib.import_module("repro.codes.puncture")

    def no_device_depuncture(*a, **k):
        raise AssertionError("JAX depuncture on the session path")

    monkeypatch.setattr(puncture_mod, "depuncture", no_device_depuncture)
    rng = np.random.default_rng(15)
    rec = SpanRecorder()
    engine = DecodeEngine(decision_depth=256, recorder=rec)
    sid = engine.open_session("dvb-s-r78", now=0.0)
    serial = rng.normal(0, 1, 2 * 256).astype(np.float32)  # 64 periods
    tickets = []
    for lo in (0, 256):
        with jax.transfer_guard("disallow_explicit"):
            tickets.append(
                engine.submit_chunk(sid, serial[lo: lo + 256], now=0.0)
            )
        shaped = engine._sessions[sid].pending[-1][1]
        assert isinstance(shaped, np.ndarray) and shaped.shape == (1, 224, 2)
        engine.poll(now=0.0)
    assert all(t.done and t.error is None for t in tickets)
    dep = rec.find("engine.depuncture")
    assert len(dep) == 2
    submits = {s.id for s in rec.find("engine.submit")}
    for sp in dep:
        assert sp.parent in submits
        assert sp.attrs == {"kept": 256, "erased": 224 * 2 - 256}
    assert engine.registry.counter("engine_erasures_total").value(
        code="dvb-s-r78") == 2 * 192


def _start_free_metric(llrs, bits, spec):
    """Path metric of ``bits`` over the (n, beta) stages ``llrs``, from
    the start state that suits them best (the sessions start with every
    state equal), in float64."""
    tr = build_transitions(spec)
    theta = 1.0 - 2.0 * np.asarray(tr.out_bits, np.float64)
    s = np.arange(spec.n_states)
    metric = np.zeros(spec.n_states)
    for t, u in enumerate(np.asarray(bits, np.int64)):
        metric += theta[s, u] @ np.asarray(llrs[t], np.float64)
        s = tr.next_state[s, u]
    return float(metric.max())


@pytest.mark.parametrize("ebn0", [8.0, 1.0])
def test_dvbs_r78_sessions_match_reference(ebn0):
    """Three DVB-S rate-7/8 sessions at different stream positions fuse
    into one group, chunk after chunk, and each chunk's bits are the
    scalar reference's ML decisions given the stream up to the front
    when they were emitted (``viterbi_ref``, float64).  At high Eb/N0
    the bits are equal; at low Eb/N0 near-ties may flip under float32
    rounding, so their path metric must equal the reference's best
    over the same stages within 1e-4."""
    code = get_code("dvb-s-r78")
    pat = code.puncture
    chunk = 32 * pat.period  # 224 stages, 112 radix steps
    lp = chunk // pat.period * pat.n_kept
    # decision depth 256 -> 448 stages (2 chunks) after the stretch
    engine = DecodeEngine(decision_depth=256)
    n_chunks = {"a": 6, "b": 5, "c": 4}
    serial, stages = {}, {}
    for i, (name, m) in enumerate(sorted(n_chunks.items())):
        key = jax.random.PRNGKey(100 + i)
        bits = jax.random.bernoulli(key, 0.5, (1, m * chunk))
        x = standard_llrs(jax.random.fold_in(key, 1),
                          encode_standard(bits.astype(jnp.int32), code),
                          ebn0, code)
        serial[name] = np.asarray(x)[0]
        stages[name] = np.asarray(depuncture(x, pat))[0]
    sids = {}
    sent = {name: 0 for name in n_chunks}
    emitted = {name: 0 for name in n_chunks}
    answers = []  # (session, bits, e0, e1, front)

    def round_(names):
        tks = {}
        for name in names:
            i = sent[name]
            tks[name] = engine.submit_chunk(
                sids[name], serial[name][i * lp: (i + 1) * lp], now=0.0
            )
            sent[name] += 1
        engine.poll(now=0.0)
        for name, tk in tks.items():
            e0 = emitted[name]
            emitted[name] += tk.bits.shape[0]
            answers.append((name, tk.bits, e0, emitted[name],
                            sent[name] * chunk))
        return engine.batch_log[-1]["n_real"]

    # a starts alone, b one chunk later, c two: then all three fuse
    for start in ("a", "b", "c"):
        sids[start] = engine.open_session(code.name, now=0.0)
        if start != "c":
            round_([n for n in sids])
    while any(sent[n] < m for n, m in n_chunks.items()):
        live = [n for n, m in n_chunks.items() if sent[n] < m]
        assert round_(live) == len(live)
    assert {a[0] for a in answers if a[3] > a[2]} == set(n_chunks)
    for name in n_chunks:
        tail = engine.close_session(sids[name])
        n = n_chunks[name] * chunk
        answers.append((name, tail, emitted[name], n, n))
        emitted[name] += tail.shape[0]
        assert emitted[name] == n  # every stage emitted once
    fwd = {name: forward_ref(stages[name].astype(np.float64), code.spec,
                             initial_state=None) for name in n_chunks}
    for name, bits, e0, e1, front in answers:
        if e1 == e0:
            continue
        lam, phi = fwd[name]
        ref = traceback_ref(lam[:front], phi[:front], code.spec)
        got = np.asarray(bits, np.int64)
        if ebn0 >= 6.0:
            np.testing.assert_array_equal(got, ref[e0:e1])
            continue
        spliced = ref.copy()
        spliced[e0:e1] = got
        x = stages[name][:front]
        gap = (_start_free_metric(x, ref, code.spec)
               - _start_free_metric(x, spliced, code.spec))
        assert abs(gap) <= 1e-4, (name, e0, e1, gap)


def test_session_eviction_is_forced_flush():
    """LRU eviction == close_session: the evicted tenant's chunk bits
    plus the parked tail equal uninterrupted chunked streaming over
    exactly what it consumed."""
    rng = np.random.default_rng(10)
    dec = ViterbiDecoder.from_standard("ccsds-k7", decision_depth=256)
    llr = rng.normal(0, 1, (1, 512, 2)).astype(np.float32)
    engine = DecodeEngine(decision_depth=256, session_capacity=2)
    s1 = engine.open_session("ccsds-k7", now=0.0)
    s2 = engine.open_session("ccsds-k7", now=0.1)
    t = engine.submit_chunk(s1, llr[0], now=0.2)
    engine.poll(now=0.2)  # touches s1 -> s2 is now LRU
    engine.open_session("ccsds-k7", now=0.3)  # evicts s2
    s = engine.stats()
    assert s["sessions_evicted"] == 1 and s["sessions"] == 2
    assert engine.evicted_tail(s2).shape == (0,)  # consumed nothing
    # evict s1 too: emitted + tail == uninterrupted streaming
    engine.open_session("ccsds-k7", now=0.4)
    got = np.concatenate([t.bits, engine.evicted_tail(s1)])
    ref = np.asarray(
        dec.decode_stream_chunked(llr, chunk_len=512, initial_state=None)
    )[0]
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize(
    "layout, on_device",
    [
        # (frames, chunks already consumed) per state; at depth 128 and
        # 96-stage chunks a state emits nothing, part of the window and
        # all of it after 0, 1 and 2 chunks
        pytest.param([(1, 0), (1, 1), (1, 2), (1, 3)], False,
                     id="one_frame_positions"),
        pytest.param([(1, 1), (2, 0), (1, 2), (3, 1)], False,
                     id="multi_frame"),
        pytest.param([(1, 2), (1, 0), (1, 1), (5, 0)], False,
                     id="trailing_pad"),
        pytest.param([(1, 2), (1, 0), (2, 1)], True, id="device_chunks"),
    ],
)
def test_decode_chunk_multi_matches_solo(layout, on_device):
    """Decoder-level contract under the engine: decode_chunk_multi on
    states at different positions and of different frame counts ==
    each state driven alone, bits and carries alike.  The trailing
    many-frame state is the engine's pad.  The engine hands host
    chunks; chunks already on the device are accepted too."""
    rng = np.random.default_rng(11)
    dec = ViterbiDecoder.from_standard("ccsds-k7", decision_depth=128)
    c = 96

    def chunk(f):
        x = rng.normal(0, 1, (f, c, 2)).astype(np.float32)
        return jnp.asarray(x) if on_device else x

    states, chunks = [], []
    for f, consumed in layout:
        s = dec.init_stream_state(f, initial_state=None)
        for _ in range(consumed):
            s, _ = dec.decode_chunk(s, chunk(f))
        states.append(s)
        chunks.append(chunk(f))
    got, outs = dec.decode_chunk_multi(states, chunks)
    assert len(got) == len(outs) == len(states)
    for s, ch, g, out in zip(states, chunks, got, outs):
        ref, solo = dec.decode_chunk(s, ch)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(solo))
        np.testing.assert_array_equal(np.asarray(g.lam), np.asarray(ref.lam))
        np.testing.assert_array_equal(np.asarray(g.hist),
                                      np.asarray(ref.hist))
        assert g.pos == ref.pos and g.lam.shape == ref.lam.shape
    with pytest.raises(ValueError):
        dec.decode_chunk_multi(states[:1], chunks)
    with pytest.raises(ValueError):
        dec.decode_chunk_multi(states[:2], [chunks[0], chunks[1][:, :48]])


def test_session_split_keyed_on_rung():
    """Groups of 3, 5 and 8 one-frame states padded to rung 8 share ONE
    compiled split program (keyed on the rung, not the session count);
    with no state in warm-up and no pad, the split is one device
    operation and the stack one host-to-device copy."""
    rng = np.random.default_rng(14)
    rec = SpanRecorder()
    # depth 64 and 88-stage chunks: a shape no other test splits; one
    # chunk takes a fresh state out of warm-up
    dec = ViterbiDecoder.from_standard(
        "ccsds-k7", decision_depth=64, recorder=rec
    )
    c, rung = 88, 8
    split = decoder_mod._split_frames
    states = [dec.init_stream_state(1) for _ in range(rung)]
    for k in (3, 5, 8, 8):
        group = states[:k]
        if k < rung:
            group = group + [dec.init_stream_state(rung - k)]
        chunks = [rng.normal(0, 1, (s.n_frames, c, 2)).astype(np.float32)
                  for s in group]
        new, _ = dec.decode_chunk_multi(group, chunks)
        states[:k] = new[:k]
        sp = rec.find("decoder.split")[-1]
        stack = rec.find("decoder.stack")[-1]
        assert sp.attrs["sliced"] == (1 if k < rung else 0)
        assert stack.attrs["h2d_arrays"] == 1
        assert stack.attrs["h2d_bytes"] == rung * c * 2 * 4
        if k == 3:
            # the process-wide cache may already hold this shape; what
            # matters is that no later session count adds to it
            compiled = split._cache_size()
    assert split._cache_size() == compiled
    # the last group: all 8 states past warm-up, no pad
    assert sp.attrs == {"split_ops": 1, "sliced": 0}


def test_split_frames_splits_carries_only():
    """The group's split program cuts the metric and ring carries into
    one-frame pieces and nothing else: the bits are not split on the
    device."""
    lam = jnp.arange(3 * 4, dtype=jnp.float32).reshape(3, 4)
    hist = jnp.arange(5 * 3 * 2, dtype=jnp.int32).reshape(5, 3, 2)
    pieces = decoder_mod._split_frames(lam, hist)
    assert len(pieces) == 2
    lams, hists = pieces
    assert [p.shape for p in lams] == [(1, 4)] * 3
    assert [p.shape for p in hists] == [(5, 1, 2)] * 3
    np.testing.assert_array_equal(np.asarray(hists[1]),
                                  np.asarray(hist[:, 1:2]))


def test_decode_chunk_multi_outs_share_one_host_buffer():
    """Every output of a fused group, a state still in warm-up and a
    multi-frame state included, is rows of ONE host copy of the group's
    bits: ``to_host`` counts one device-to-host copy of the whole
    (F, n) array, and all rows share its memory."""
    rng = np.random.default_rng(15)
    dec = ViterbiDecoder.from_standard("ccsds-k7", decision_depth=128)
    c = 96  # T 48 radix steps against D 64: one chunk leaves a state
    # in warm-up with 32 of its 48 steps emitted

    def chunk(f):
        return rng.normal(0, 1, (f, c, 2)).astype(np.float32)

    states = []
    for f, consumed in [(1, 1), (1, 2), (2, 3), (1, 4)]:
        s = dec.init_stream_state(f, initial_state=None)
        for _ in range(consumed):
            s, _ = dec.decode_chunk(s, chunk(f))
        states.append(s)
    chunks = [chunk(s.n_frames) for s in states]
    _, outs = dec.decode_chunk_multi(states, chunks)
    assert [o.shape for o in outs] == [(1, 64), (1, 96), (2, 96), (1, 96)]
    host, n, nbytes = decoder_mod.to_host(outs)
    assert (n, nbytes) == (1, 5 * c * 4)
    group = outs[0].bits.host()
    assert group.shape == (5, c)
    assert all(np.shares_memory(group, h) for h in host)
    # a second fetch copies nothing; a copy asked for is a copy
    assert decoder_mod.to_host(outs)[1:] == (0, 0)
    assert not np.shares_memory(np.array(outs[1], copy=True), host[1])
    for s, ch, h in zip(states, chunks, host):
        _, solo = dec.decode_chunk(s, ch)
        np.testing.assert_array_equal(h, np.asarray(solo))


def test_session_group_bits_cross_in_one_copy():
    """Three one-frame sessions and the engine's pad fuse into one
    dispatch whose bits come back in ONE device-to-host copy, and each
    ticket's bits equal the session decoded alone."""
    rng = np.random.default_rng(16)
    rec = SpanRecorder()
    engine = DecodeEngine(decision_depth=64, max_batch=4, recorder=rec)
    dec = ViterbiDecoder.from_standard("ccsds-k7", decision_depth=64)
    c = 96
    llrs = [rng.normal(0, 1, (c, 2)).astype(np.float32) for _ in range(3)]
    tickets = [
        engine.submit_chunk(engine.open_session("ccsds-k7", now=0.0), x,
                            now=0.0)
        for x in llrs
    ]
    assert len(engine.poll(now=0.0)) == 3
    (assemble,) = rec.find("engine.assemble")
    assert assemble.attrs == {"n_real": 3, "f": 4}
    (wait,) = rec.find("engine.device_wait")
    assert wait.attrs == {"d2h_arrays": 1, "d2h_bytes": 4 * c * 4}
    for t, x in zip(tickets, llrs):
        _, solo = dec.decode_chunk(
            dec.init_stream_state(1, initial_state=None), x[None])
        np.testing.assert_array_equal(t.bits, np.asarray(solo)[0])


def test_session_groups_respect_max_batch():
    """More concurrent sessions than max_batch split into several fused
    dispatches — the frame cap holds and occupancy never exceeds 1."""
    rng = np.random.default_rng(12)
    engine = DecodeEngine(decision_depth=128, max_batch=2)
    sids = [engine.open_session("ccsds-k7", now=0.0) for _ in range(3)]
    for sid in sids:
        engine.submit_chunk(
            sid, rng.normal(0, 1, (128, 2)).astype(np.float32), now=0.0
        )
    engine.poll(now=0.0)
    session_batches = [b for b in engine.batch_log if b["path"] == "session"]
    assert [b["n_real"] for b in session_batches] == [2, 1]
    assert all(b["f_cell"] <= 2 for b in session_batches)
    assert engine.stats()["occupancy"] <= 1.0


def test_close_session_leaves_other_tenants_queued():
    """close_session drains only its own session; another tenant's
    pending chunk stays queued and completes at the next poll — and a
    ticket completed out of band by a close is delivered by the next
    poll exactly once."""
    rng = np.random.default_rng(13)
    engine = DecodeEngine(decision_depth=128)
    sa = engine.open_session("ccsds-k7", now=0.0)
    sb = engine.open_session("ccsds-k7", now=0.0)
    ta = engine.submit_chunk(
        sa, rng.normal(0, 1, (128, 2)).astype(np.float32), now=0.0
    )
    tb = engine.submit_chunk(
        sb, rng.normal(0, 1, (128, 2)).astype(np.float32), now=0.0
    )
    engine.close_session(sa, now=0.0)
    assert ta.done and not tb.done  # B untouched by A's close
    assert engine._sessions[sb].pending
    done = engine.poll(now=0.0)
    assert {t.id for t in done} == {ta.id, tb.id}  # ta delivered once
    assert not engine.poll(now=0.0)  # ...and only once


def test_request_validation():
    engine = DecodeEngine()
    with pytest.raises(ValueError):  # punctured code wants serial LLRs
        engine.submit(DecodeRequest(
            np.zeros((32, 2), np.float32), "wifi-11a-r34", "latency"
        ), now=0.0)
    with pytest.raises(ValueError):  # wrong beta
        engine.submit(DecodeRequest(
            np.zeros((32, 2), np.float32), "lte-tbcc", "latency"
        ), now=0.0)
    with pytest.raises(ValueError):  # unknown SLO class
        engine.submit(DecodeRequest(
            np.zeros((32, 2), np.float32), "ccsds-k7", "gold"
        ), now=0.0)
    with pytest.raises(KeyError):  # unknown code
        engine.submit(DecodeRequest(
            np.zeros((32, 2), np.float32), "nope", "latency"
        ), now=0.0)
