"""Observability subsystem (DESIGN.md §12): registry semantics,
Prometheus round-trip through the validating smoke parser, span
nesting + JSONL replay, spans mirrored into the profiler's trace, the
engine's and decoder's spans at each layer boundary, and the engine
contracts — decode bits identical with tracing off/on for EVERY
registry code, and ``stats()`` (registry-backed since §12) exactly
matching an independent legacy recomputation of the same replayed
trace, backpressure rejects included."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.codes import REGISTRY, encode_standard, get_code, standard_llrs
from repro.obs import (
    JsonlSink,
    MetricsRegistry,
    NullRecorder,
    NullRegistry,
    SpanRecorder,
    default_registry,
    set_default_registry,
)
from repro.obs.smoke import parse_prometheus
from repro.serve.engine import DecodeEngine, DecodeRequest


def _request(code_name, n_bits, slo, seed, ebn0=5.0):
    """(true bits, DecodeRequest) through the standard tx chain — same
    helper as tests/test_engine.py."""
    rng = np.random.default_rng(seed)
    code = get_code(code_name)
    bits = jnp.asarray(rng.integers(0, 2, (1, n_bits)), jnp.int32)
    llrs = standard_llrs(
        jax.random.PRNGKey(seed), encode_standard(bits, code), ebn0, code
    )
    return np.asarray(bits)[0], DecodeRequest(
        llrs=np.asarray(llrs)[0], code=code_name, slo=slo
    )


# -- registry semantics -------------------------------------------------------

def test_counter_monotonic_and_labels():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "requests")
    c.inc(2, code="a", path="batch")
    c.inc(3, code="b", path="wava")
    c.inc(1, code="a", path="batch")
    assert c.value(code="a", path="batch") == 3
    assert c.total() == 6
    assert c.total(code="a") == 3
    with pytest.raises(ValueError):
        c.inc(-1, code="a", path="batch")


def test_registry_type_conflict_rejected():
    reg = MetricsRegistry()
    reg.counter("x_total")
    with pytest.raises(TypeError):
        reg.gauge("x_total")
    # get-or-create: same name + same type returns the same family
    assert reg.counter("x_total") is reg.counter("x_total")


def test_gauge_set_add():
    g = MetricsRegistry().gauge("depth")
    g.set(5, q="a")
    g.add(-2, q="a")
    assert g.value(q="a") == 3


def test_histogram_quantile_matches_percentile():
    """The bounded exact-value window makes quantile() reproduce
    np.percentile (linear interpolation) — the engine stats() parity
    guarantee."""
    rng = np.random.default_rng(0)
    h = MetricsRegistry().histogram("lat_seconds", window=4096)
    vals = rng.gamma(2.0, 0.01, 513)
    for v in vals:
        h.observe(float(v), slo="latency")
    assert h.count(slo="latency") == 513
    for q in (0.5, 0.99):
        assert h.quantile(q, slo="latency") == pytest.approx(
            np.percentile(vals, q * 100), rel=1e-12
        )


def test_null_registry_and_default_swap():
    """default_registry() is a no-op Null until a launcher installs a
    real one; the swap returns the previous registry for restoration."""
    assert isinstance(default_registry(), NullRegistry)
    default_registry().counter("anything_total").inc(5, a="b")  # no-op
    reg = MetricsRegistry()
    prev = set_default_registry(reg)
    try:
        assert default_registry() is reg
    finally:
        set_default_registry(prev)
    assert isinstance(default_registry(), NullRegistry)


def test_prometheus_round_trip():
    """render_prometheus() output survives the validating text-format
    parser, values and label escaping intact."""
    reg = MetricsRegistry()
    reg.counter("rq_total", "with \"quotes\" and \\slash").inc(
        7, code='c"x"', path="a\\b"
    )
    reg.gauge("depth").set(3)
    h = reg.histogram("soj_seconds")
    for v in (1e-6, 0.003, 2.0, 100.0):
        h.observe(v, slo="latency")
    fams = parse_prometheus(reg.render_prometheus())
    assert fams["rq_total"]["type"] == "counter"
    (name, labels, value), = fams["rq_total"]["samples"]
    assert labels == {"code": 'c"x"', "path": "a\\b"} and value == 7
    assert fams["soj_seconds"]["type"] == "histogram"
    count = [v for n, _, v in fams["soj_seconds"]["samples"]
             if n == "soj_seconds_count"]
    assert count == [4.0]


# -- spans --------------------------------------------------------------------

def test_span_nesting_and_jsonl_sink(tmp_path):
    path = str(tmp_path / "t.jsonl")
    clock = iter(float(i) for i in range(100))
    rec = SpanRecorder(clock=lambda: next(clock), sink=JsonlSink(path))
    with rec.span("outer", code="ccsds-k7") as outer:
        rec.event("ping", n=1)  # open span -> rides on the span record
        with rec.span("inner") as inner:
            inner.set(depth=3)
        outer.set(path="batch")
    rec.event("solo", n=2)  # no open span -> top-level JSONL line
    rec.close()
    assert rec.open_spans == 0
    (o,) = rec.find("outer")
    kids = rec.children(o)
    assert [s.name for s in kids] == ["inner"]
    assert kids[0].t0 >= o.t0 and kids[0].t1 <= o.t1
    lines = [json.loads(x) for x in open(path)]
    # spans write at close (inner first), the standalone event in order
    assert [(ln["type"], ln["name"]) for ln in lines] == [
        ("span", "inner"), ("span", "outer"), ("event", "solo"),
    ]
    assert lines[0]["parent"] == o.id
    assert lines[1]["attrs"]["path"] == "batch"
    assert [e["name"] for e in lines[1]["events"]] == ["ping"]
    assert lines[2]["span"] is None


def test_span_records_exceptions():
    rec = SpanRecorder()
    with pytest.raises(RuntimeError):
        with rec.span("boom"):
            raise RuntimeError("kapow")
    (s,) = rec.find("boom")
    assert s.t1 is not None and "kapow" in s.attrs["error"]
    assert rec.open_spans == 0


def test_null_recorder_is_inert():
    rec = NullRecorder()
    assert not rec.enabled
    with rec.span("x") as s:
        s.set(a=1)
        rec.event("e")
    assert rec.find("x") == [] and rec.open_spans == 0


# -- spans in the profiler's trace --------------------------------------------

def _host_events(trace_dir, prefix):
    """(line name, [(name, start_ns, end_ns)]) of every host line of the
    ``.xplane.pb`` under ``trace_dir`` holding events named ``prefix*``."""
    from jax.profiler import ProfileData

    (path,) = trace_dir.glob("plugins/profile/*/*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events if e.name.startswith(prefix)]
            if evs:
                out.append((line.name, evs))
    return out


@pytest.mark.parametrize("kind", ["mirrored", "null"])
def test_spans_mirror_into_profiler_trace(tmp_path, kind):
    """An enabled SpanRecorder's nested spans come back from the
    profiler's ``.xplane.pb`` on one host line, with the same names and
    nesting; a NullRecorder writes nothing there."""
    rec = {"mirrored": SpanRecorder(), "null": NullRecorder()}[kind]
    with jax.profiler.trace(str(tmp_path)):
        with rec.span("obs_t.poll"):
            with rec.span("obs_t.batch"):
                with rec.span("obs_t.stack"):
                    jnp.ones(8).block_until_ready()
                with rec.span("obs_t.split"):
                    pass
    lines = _host_events(tmp_path, "obs_t.")
    if kind != "mirrored":
        assert lines == []
        return
    assert len(lines) == 1  # one host thread's line
    evs = {name: (t0, t1) for name, t0, t1 in lines[0][1]}
    assert sorted(evs) == sorted(s.name for s in rec.spans)
    by_id = {s.id: s for s in rec.spans}
    for s in rec.spans:
        if s.parent is None:
            continue
        (c0, c1), (p0, p1) = evs[s.name], evs[by_id[s.parent].name]
        assert p0 <= c0 and c1 <= p1, (s.name, by_id[s.parent].name)
    # siblings do not overlap
    assert evs["obs_t.stack"][1] <= evs["obs_t.split"][0]


def test_out_of_order_end_closes_inner_annotations(tmp_path):
    """Ending an outer span while an inner one is open closes the inner
    span's profiler annotation too, inside the outer one's."""
    rec = SpanRecorder()
    with jax.profiler.trace(str(tmp_path)):
        outer = rec.start("obs_o.outer")
        inner = rec.start("obs_o.inner")
        rec.end(outer)
    assert inner._ann is None and rec.open_spans == 0
    (line,) = _host_events(tmp_path, "obs_o.")
    evs = {name: (t0, t1) for name, t0, t1 in line[1]}
    assert set(evs) == {"obs_o.outer", "obs_o.inner"}
    assert evs["obs_o.outer"][0] <= evs["obs_o.inner"][0]
    assert evs["obs_o.inner"][1] <= evs["obs_o.outer"][1]


def _session_engine(n_sessions, c=64, depth=64, recorder=None):
    """Engine with ``n_sessions`` open ccsds-k7 sessions, each with one
    ``c``-stage chunk queued, and the chunks' LLRs."""
    rng = np.random.default_rng(13)
    engine = DecodeEngine(decision_depth=depth, recorder=recorder)
    chunks = {}
    for i in range(n_sessions):
        sid = engine.open_session("ccsds-k7", now=0.0)
        chunks[sid] = rng.normal(0, 1, (3, c, 2)).astype(np.float32)
    return engine, chunks


def test_session_dispatch_decoder_spans():
    """A session-group dispatch of 4 sessions nests engine.poll >
    engine.batch > engine.dispatch > decoder.{stack,validate,launch,
    split}, with one host-to-device copy for the group's chunks; a
    recorder set after the decoders were built reaches them."""
    engine, chunks = _session_engine(4)
    for sid, llr in chunks.items():
        engine.submit_chunk(sid, llr[0], now=0.0)
    rec = SpanRecorder()
    engine.recorder = rec
    done = engine.poll(now=0.0)
    assert len(done) == 4 and rec.open_spans == 0
    (poll,) = rec.find("engine.poll")
    assert poll.attrs == {"n_batches": 1, "n_done": 4}
    (batch,) = rec.children(poll)
    assert batch.name == "engine.batch"
    (disp,) = [c for c in rec.children(batch) if c.name == "engine.dispatch"]
    kids = {c.name: c for c in rec.children(disp)}
    assert {"decoder.stack", "decoder.validate", "decoder.launch",
            "decoder.split", "engine.device_wait"} <= set(kids)
    # the four host chunks stack on the host and cross in ONE copy
    assert kids["decoder.stack"].attrs["h2d_arrays"] == 1
    assert kids["decoder.stack"].attrs["h2d_bytes"] == 4 * 64 * 2 * 4
    # one split program for the group's carries; the emission windows
    # of the sessions still in warm-up (all four are fresh) are cut on
    # the host, and there is no pad state
    assert kids["decoder.split"].attrs == {"split_ops": 1, "sliced": 0}
    # the group's bits come back in ONE copy
    assert kids["engine.device_wait"].attrs == {
        "d2h_arrays": 1, "d2h_bytes": 4 * 64 * 4}
    assert len(rec.find("engine.submit")) == 0  # submitted untraced


@pytest.mark.parametrize("use_kernel", [True, False],
                         ids=["one_pass", "two_pass"])
def test_session_launch_span_carries_one_pass_tile(use_kernel):
    """A one-pass session group tags its decoder.launch span with the
    time tile the rule picked and the ring steps walked per ACS step
    (depth 64 steps, chunks of 128: tile 64, walk 2); the two-pass
    step, and a disabled recorder, set neither."""
    rng = np.random.default_rng(14)
    rec = SpanRecorder()
    engine = DecodeEngine(decision_depth=128, use_kernel=use_kernel,
                          recorder=rec)
    for _ in range(2):
        sid = engine.open_session("ccsds-k7", now=0.0)
        engine.submit_chunk(
            sid, rng.normal(0, 1, (256, 2)).astype(np.float32), now=0.0)
    assert len(engine.poll(now=0.0)) == 2
    (launch,) = rec.find("decoder.launch")
    paths = dict((lbl["path"], v) for lbl, v in engine.registry.counter(
        "decoder_dispatch_total").series())
    if use_kernel:
        assert launch.attrs == {"time_tile": 64, "walk_per_step": 2}
        assert paths == {"chunk_one_pass": 1}
    else:
        assert launch.attrs == {}
        assert paths == {"chunk_two_pass": 1}


def test_batch_route_decoder_spans():
    """A decode_batch route through the engine: engine.submit per
    request, and decoder.depuncture / validate / launch under the
    dispatch; the dense cell is the batch's one host-to-device copy."""
    rec = SpanRecorder()
    engine = DecodeEngine(max_batch=4, recorder=rec)
    reqs = [_request("wifi-11a-r34", 60 + 6 * i, "throughput", seed=i)[1]
            for i in range(3)]
    tickets = [engine.submit(r, now=0.0) for r in reqs]
    engine.drain(now=1.0)
    assert all(t.bits is not None for t in tickets)
    assert len(rec.find("engine.submit")) == 3
    (disp,) = rec.find("engine.dispatch")
    kids = {c.name: c for c in rec.children(disp)}
    assert {"decoder.depuncture", "decoder.validate",
            "decoder.launch"} <= set(kids)
    assert kids["decoder.depuncture"].attrs["h2d_arrays"] == 0
    assert disp.attrs["h2d_arrays"] == 1 and disp.attrs["h2d_bytes"] > 0
    (poll,) = rec.find("engine.poll")
    assert poll.attrs == {"n_batches": 1, "n_done": 3}


def test_decoder_dispatch_total_in_engine_registry():
    """The engine's decoders count decoder_dispatch_total{path} into
    the engine's registry; the process default registry sees none."""
    default = MetricsRegistry()
    prev = set_default_registry(default)
    try:
        engine = DecodeEngine(max_batch=4)
        engine.decode([_request("ccsds-k7", 64, "throughput", seed=1)[1]])
    finally:
        set_default_registry(prev)
    paths = engine.registry.counter("decoder_dispatch_total").series()
    assert sum(v for _, v in paths) == 1
    assert paths[0][0]["path"] == engine.batch_log[-1]["path"]
    assert "decoder_dispatch_total" not in default.snapshot()


# -- engine contracts ---------------------------------------------------------

def _registry_workload():
    """Mixed ragged workload over every registry standard."""
    reqs = []
    for i, name in enumerate(sorted(REGISTRY)):
        tb = REGISTRY[name].termination == "tailbiting"
        for j, n in enumerate((40,) if tb else (57, 90)):
            _, req = _request(name, n, "throughput", 31 * i + j)
            reqs.append(req)
    return reqs


def test_engine_bits_identical_obs_on_off(tmp_path):
    """Decode bits for every registry code (punctured + tail-biting
    included) are identical with tracing disabled and with a live
    SpanRecorder + JSONL sink — instrumentation never touches jitted
    code."""
    reqs = _registry_workload()
    off = DecodeEngine(max_batch=8).decode(reqs)
    rec = SpanRecorder(sink=JsonlSink(str(tmp_path / "e.jsonl")))
    engine_on = DecodeEngine(max_batch=8, recorder=rec)
    on = engine_on.decode(reqs)
    rec.close()
    assert len(off) == len(on) == len(reqs)
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a, b)
    # and the trace actually covered the work
    assert len(rec.find("engine.batch")) == len(engine_on.batch_log)
    disp = rec.find("engine.dispatch")
    assert disp and all("h2d_arrays" in s.attrs for s in disp)


def test_session_bits_identical_obs_on_off():
    """The session twin: fused session dispatches emit the same bits
    with tracing disabled and with a live, mirrored SpanRecorder."""
    out = {}
    for on in (False, True):
        rec = SpanRecorder() if on else None
        engine, chunks = _session_engine(3, recorder=rec)
        bits = {sid: [] for sid in chunks}
        for r in range(3):
            tks = {sid: engine.submit_chunk(sid, llr[r], now=float(r))
                   for sid, llr in chunks.items()}
            engine.poll(now=float(r))
            for sid, t in tks.items():
                bits[sid].append(t.bits)
        for sid in chunks:
            bits[sid].append(engine.close_session(sid))
        out[on] = {sid: np.concatenate(b) for sid, b in bits.items()}
        if on:
            assert len(rec.find("decoder.split")) == 3
    assert out[False].keys() == out[True].keys()
    for sid in out[False]:
        np.testing.assert_array_equal(out[False][sid], out[True][sid])


def test_stats_match_legacy_recomputation():
    """Registry-backed stats() == an independent recomputation of the
    same replayed trace from tickets + batch_log: request lifecycle
    counts (backpressure reject included), batches/paths, occupancy,
    padding waste, jit hit/miss, and exact p50/p99 sojourn."""
    engine = DecodeEngine(max_batch=4, max_pending=6,
                          max_wait={"latency": 0.001, "throughput": 0.004})
    tickets = []
    now = 0.0
    for i in range(18):  # bursts of 9 against max_pending=6 -> rejects
        slo = "latency" if i % 3 == 0 else "throughput"
        _, req = _request("ccsds-k7", 48 + 5 * (i % 4), slo, seed=i)
        tickets.append(engine.submit(req, now=now))
        now += 1e-4
        if i % 9 == 8:
            engine.poll(now=now)
            now += 0.01
    engine.drain(now=now)
    s = engine.stats()

    dropped = [t for t in tickets if t.dropped]
    done = [t for t in tickets if t.bits is not None]
    assert dropped and done  # the trace exercised both outcomes
    assert s["rejected"] == len(dropped)
    assert s["submitted"] == len(tickets) - len(dropped)
    assert s["completed"] == len(done)
    assert s["queue_depth"] == 0 and s["batches"] == len(engine.batch_log)

    paths = {}
    for b in engine.batch_log:
        paths[b["path"]] = paths.get(b["path"], 0) + 1
    assert s["paths"] == paths

    real_f = sum(b["n_real"] for b in engine.batch_log)
    cell_f = sum(b["f_cell"] for b in engine.batch_log)
    assert s["occupancy"] == pytest.approx(real_f / cell_f)
    # ccsds-k7 is rate-1/2 (beta=2): cell elems = f * l_cell * 2
    real_e = 2 * sum(t.n_out for t in done)
    cell_e = 2 * sum(b["f_cell"] * b["cell"][2] for b in engine.batch_log)
    assert s["padding_waste"] == pytest.approx(1.0 - real_e / cell_e)

    # one jit lookup per batch on this session-free workload
    assert s["jit_cache"]["misses"] == s["jit_cache"]["entries"]
    assert (s["jit_cache"]["hits"] + s["jit_cache"]["misses"]
            == s["batches"])

    for slo in ("latency", "throughput"):
        soj = [t.sojourn for t in done if t.slo == slo]
        assert s["latency"][slo]["n"] == len(soj)
        assert s["latency"][slo]["p50"] == pytest.approx(
            np.percentile(soj, 50), rel=1e-12)
        assert s["latency"][slo]["p99"] == pytest.approx(
            np.percentile(soj, 99), rel=1e-12)


def test_engine_prometheus_parses_and_counts():
    engine = DecodeEngine(max_batch=8)
    reqs = [_request("ccsds-k7", 60 + i, "throughput", seed=i)[1]
            for i in range(5)]
    engine.decode(reqs)
    fams = parse_prometheus(engine.registry.render_prometheus())
    total = sum(v for _, lbl, v in fams["engine_requests_total"]["samples"]
                if lbl.get("event") == "completed")
    assert total == len(reqs)
    assert fams["engine_sojourn_seconds"]["type"] == "histogram"


# -- farm progress spans ------------------------------------------------------

def test_farm_progress_spans():
    """BerFarm with an injected recorder emits one farm.point span per
    grid point with farm.progress events carrying running error counts
    and the Wilson CI width."""
    from repro.verify.farm import BerFarm

    rec = SpanRecorder()
    farm = BerFarm(
        codes=["ccsds-k7"], ebn0_dbs=[4.0], paths=["reference"],
        frames_per_point=8, frame_budget=128, batch_frames=4,
        scan_chunk=1, recorder=rec,
    )
    farm.run()
    points = rec.find("farm.point")
    assert len(points) == 1 and rec.open_spans == 0
    (p,) = points
    assert p.attrs["code"] == "ccsds-k7" and "bit_errors" in p.attrs
    prog = [e for e in p.events if e["name"] == "farm.progress"]
    assert len(prog) == 2  # 2 batches / scan_chunk=1
    assert prog[-1]["attrs"]["frames"] == 8
    assert prog[-1]["attrs"]["wilson_ci_width"] > 0
    assert prog[-1]["attrs"]["bit_errors"] == p.attrs["bit_errors"]
