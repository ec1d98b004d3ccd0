"""Fault tolerance (DESIGN.md §13): deterministic chaos schedules,
bounded retry with the degradation ladder, mesh failover, session
checkpoint/restore bit-exactness, deadline shedding, typed errors, and
the backpressure/eviction behaviour under injected faults.  The
acceptance scenario — >= 3 device failures and >= 2 timeouts landing on
a chunked-streaming workload, with the recovered output bit-identical
to uninterrupted ``decode_stream_chunked`` and no request silently
dropped — lives in ``test_session_chaos_bitexact`` (the same contract
the ``chaos-smoke`` CI gate enforces)."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.codes import encode_standard, get_code, standard_llrs
from repro.core.decoder import ViterbiDecoder
from repro.runtime.chaos import (
    ChaosInjector,
    ChaosSchedule,
    DeviceFailure,
    DispatchTimeout,
    FaultEvent,
    TransientCompileError,
)
from repro.runtime.failure import HeartbeatMonitor, RetryPolicy
from repro.serve.engine import DEGRADATION_LADDER, DecodeEngine, DecodeRequest

T, C, DEPTH = 512, 128, 128  # stream length / chunk / decision depth


def _request(code_name, n_bits, slo, seed, **kw):
    """(true bits, DecodeRequest) through the standard tx chain."""
    rng = np.random.default_rng(seed)
    code = get_code(code_name)
    bits = jnp.asarray(rng.integers(0, 2, (1, n_bits)), jnp.int32)
    llrs = standard_llrs(
        jax.random.PRNGKey(seed), encode_standard(bits, code), 5.0, code
    )
    return np.asarray(bits)[0], DecodeRequest(
        llrs=np.asarray(llrs)[0], code=code_name, slo=slo, **kw
    )


def _stream(seed, n=T):
    """One clean-channel LLR stream for session tests."""
    code = get_code("ccsds-k7")
    bits = jnp.asarray(
        np.random.default_rng(seed).integers(0, 2, (1, n)), jnp.int32
    )
    return np.asarray(standard_llrs(
        jax.random.PRNGKey(seed), encode_standard(bits, code), 4.0, code
    ))[0]


def _stream_ref(s):
    dec = ViterbiDecoder.from_standard("ccsds-k7", decision_depth=DEPTH)
    return np.asarray(
        dec.decode_stream_chunked(s[None], chunk_len=C, initial_state=None)
    )[0]


# -- schedule / injector ---------------------------------------------------


def test_schedule_json_roundtrip(tmp_path):
    """Schedules survive JSON — including device=0 (a falsy device id
    must not be dropped), straggler delays, and path filters."""
    sched = ChaosSchedule([
        FaultEvent(at=3, kind="device_failure", device=0),
        FaultEvent(at=1, kind="timeout", path="sharded"),
        FaultEvent(at=7, kind="slow", delay=0.25),
        FaultEvent(at=2, kind="compile_error"),
    ])
    p = tmp_path / "sched.json"
    p.write_text(json.dumps(sched.to_json()))
    back = ChaosSchedule.from_file(p)
    assert back.events == sched.events
    assert back.events[0].at == 1  # sorted by (at, kind)
    dev = [e for e in back.events if e.kind == "device_failure"][0]
    assert dev.device == 0
    assert back.counts() == {
        "device_failure": 1, "timeout": 1, "slow": 1, "compile_error": 1
    }
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultEvent(at=0, kind="meteor_strike")


def test_bit_flip_schedule_roundtrip_and_generate():
    """The silent fault kind (DESIGN.md §14): ``flips`` survives JSON,
    seeded generation draws bit_flip events with a device attribution
    and bounded flip counts."""
    sched = ChaosSchedule([
        FaultEvent(at=2, kind="bit_flip", device=0, flips=3),
        FaultEvent(at=5, kind="bit_flip", device=1),  # default flips=1
    ])
    back = ChaosSchedule.from_json(json.dumps(sched.to_json()))
    assert back.events == sched.events
    assert back.events[0].flips == 3 and back.events[1].flips == 1
    assert back.counts() == {"bit_flip": 2}
    gen = ChaosSchedule.generate(
        seed=5, n_attempts=400, p_device=0.0, p_timeout=0.0, p_slow=0.0,
        p_compile=0.0, p_bit_flip=0.1, n_devices=4, max_flips=3,
    )
    assert gen.events == ChaosSchedule.generate(
        seed=5, n_attempts=400, p_device=0.0, p_timeout=0.0, p_slow=0.0,
        p_compile=0.0, p_bit_flip=0.1, n_devices=4, max_flips=3,
    ).events
    assert gen.counts() == {"bit_flip": len(gen.events)} and gen.events
    for e in gen.events:
        assert 0 <= e.device < 4 and 1 <= e.flips <= 3


def test_bit_flip_arms_silently_and_corrupts():
    """bit_flip never raises at dispatch (the corruption is silent):
    ``on_dispatch`` arms it, ``corrupt`` fires it — flipping exactly
    ``flips`` seeded-deterministic positions, attributing the device,
    and counting at fire time."""
    inj = ChaosInjector(ChaosSchedule([
        FaultEvent(at=0, kind="bit_flip", device=2, flips=3),
    ]))
    assert inj.on_dispatch("ccsds-k7", "batch") == 0.0  # no raise
    assert inj.injected["bit_flip"] == 0  # not counted until it fires
    bits = np.zeros((4, 16), np.int32)
    out, device = inj.corrupt(bits)
    assert device == 2 and int(out.sum()) == 3
    assert bits.sum() == 0  # input untouched (corrupt copies)
    assert inj.injected["bit_flip"] == 1
    # armed events are one-shot: the next dispatch output is clean
    out2, device2 = inj.corrupt(bits)
    assert device2 is None and out2 is bits
    # same schedule -> same flip positions, every run
    inj2 = ChaosInjector(ChaosSchedule([
        FaultEvent(at=0, kind="bit_flip", device=2, flips=3),
    ]))
    inj2.on_dispatch("ccsds-k7", "batch")
    out3, _ = inj2.corrupt(np.zeros((4, 16), np.int32))
    np.testing.assert_array_equal(out3, out)


def test_schedule_generate_deterministic():
    """Seeded generation is reproducible; probabilities validate."""
    a = ChaosSchedule.generate(seed=7, n_attempts=500, n_devices=4)
    b = ChaosSchedule.generate(seed=7, n_attempts=500, n_devices=4)
    assert a.events == b.events
    assert a.counts()  # dense enough to actually draw events
    c = ChaosSchedule.generate(seed=8, n_attempts=500, n_devices=4)
    assert a.events != c.events
    with pytest.raises(ValueError, match="sum"):
        ChaosSchedule.generate(seed=0, n_attempts=10, p_device=0.9,
                               p_timeout=0.9)


def test_injector_fires_and_filters():
    """Events fire one-shot at their attempt index; path-mismatched
    events are skipped (not deferred); slow events return their delay."""
    inj = ChaosInjector(ChaosSchedule([
        FaultEvent(at=0, kind="timeout"),
        FaultEvent(at=1, kind="slow", delay=0.5),
        FaultEvent(at=2, kind="device_failure", device=3, path="sharded"),
        FaultEvent(at=3, kind="compile_error"),
    ]))
    with pytest.raises(DispatchTimeout):
        inj.on_dispatch("ccsds-k7", "batch")
    assert inj.on_dispatch("ccsds-k7", "batch") == 0.5
    # attempt 2 is a batch dispatch -> the sharded-only event skips
    assert inj.on_dispatch("ccsds-k7", "batch") == 0.0
    with pytest.raises(TransientCompileError):
        inj.on_dispatch("ccsds-k7", "batch")
    assert inj.on_dispatch("ccsds-k7", "batch") == 0.0  # schedule spent
    assert inj.attempts == 5
    assert inj.injected == {"timeout": 1, "slow": 1, "compile_error": 1}
    assert inj.total_injected() == 3
    with pytest.raises(DeviceFailure) as ei:
        raise DeviceFailure(device=3)
    assert ei.value.device == 3 and ei.value.kind == "device_failure"


# -- satellite fixes: heartbeat cold start, save_async errors --------------


def test_heartbeat_cold_start_regression():
    """A monitor constructed mid-run (now=100) must NOT declare every
    host dead on the first check — last_seen seeds from the
    construction clock, not 0.0 (the pre-§13 bug)."""
    mon = HeartbeatMonitor(["h0", "h1"], timeout=30.0, now=100.0)
    assert mon.failed(now=110.0) == []  # within the window: alive
    assert mon.failed(now=131.0) == ["h0", "h1"]  # silent past timeout
    mon2 = HeartbeatMonitor(["h0"], timeout=30.0, now=100.0)
    mon2.beat("h0", now=120.0)
    assert mon2.failed(now=149.0) == []
    assert mon2.failed(now=151.0) == ["h0"]


def test_retry_policy_backoff_bounded():
    pol = RetryPolicy(max_retries=5, backoff_base=0.05, backoff_cap=0.4)
    assert pol.backoff(0) == pytest.approx(0.05)
    assert pol.backoff(1) == pytest.approx(0.10)
    assert pol.backoff(2) == pytest.approx(0.20)
    assert pol.backoff(3) == pytest.approx(0.40)
    assert pol.backoff(10) == pytest.approx(0.40)  # capped


def test_save_async_error_surfaced(tmp_path):
    """The pre-§13 save_async dropped background exceptions on the
    floor; the SaveHandle re-raises them from result()/join(), and the
    CheckpointManager surfaces them on the next wait/maybe_save."""
    from repro.runtime.checkpoint import CheckpointManager, save_async

    clobber = tmp_path / "not_a_dir"
    clobber.write_text("a file where the step dir must go")
    h = save_async(clobber / "x", 0, {"a": np.zeros(3)})
    with pytest.raises(OSError):
        h.result(timeout=30.0)
    assert h.done() and isinstance(h.exception(), OSError)

    mgr = CheckpointManager(clobber / "y", interval=1)
    assert mgr.maybe_save(0, {"a": np.ones(2)})
    with pytest.raises(OSError):
        mgr.wait()
    # a healthy manager still round-trips
    ok = CheckpointManager(tmp_path / "ok", interval=1)
    ok.maybe_save(0, {"a": np.ones(2)})
    ok.wait()


def test_torn_session_checkpoint_skipped(tmp_path):
    """manifest-last torn-write detection: a step directory whose
    arrays landed but whose manifest didn't is invisible to restore."""
    from repro.runtime.checkpoint import load_sessions, save_sessions

    sessions = {
        "s0": {"lam": np.arange(4.0, dtype=np.float32),
               "hist": np.zeros((2, 4), np.int8),
               "pos": 7, "code": "ccsds-k7", "consumed": 256},
    }
    save_sessions(tmp_path, 0, sessions, extra={"now": 1.5})
    torn = save_sessions(tmp_path, 1, dict(sessions, s0=dict(
        sessions["s0"], consumed=512)), extra={"now": 2.5})
    os.remove(os.path.join(torn, "manifest.json"))  # the torn write
    step, got, extra = load_sessions(tmp_path)
    assert step == 0 and extra["now"] == 1.5
    assert got["s0"]["consumed"] == 256 and got["s0"]["pos"] == 7
    np.testing.assert_array_equal(got["s0"]["lam"], sessions["s0"]["lam"])
    np.testing.assert_array_equal(got["s0"]["hist"], sessions["s0"]["hist"])
    # no complete checkpoint at all -> empty restore, not an error
    assert load_sessions(tmp_path / "nothing_here") == (None, {}, {})


def test_replan_mesh_keeps_pow2_prefix():
    """Mesh re-planning after device failures keeps the largest
    power-of-two survivor prefix (the ElasticPlanner rule); killing the
    last device of a 1-device mesh returns None (no mesh left).  The
    multi-device shape runs in a subprocess (device count must be set
    before jax initialises)."""
    from repro.distributed.decoder import frame_mesh, replan_mesh

    mesh = frame_mesh()  # 1 CPU device
    dead = int(np.asarray(mesh.devices).reshape(-1)[0].id)
    assert replan_mesh(mesh, {dead}) is None
    assert replan_mesh(mesh, set()) is not None

    prog = (
        "import numpy as np\n"
        "from repro.distributed.decoder import frame_mesh, replan_mesh\n"
        "mesh = frame_mesh()\n"
        "assert mesh.devices.size == 8\n"
        "m = replan_mesh(mesh, {1, 4, 6})  # 5 survive -> pow2 prefix 4\n"
        "ids = [int(d.id) for d in np.asarray(m.devices).reshape(-1)]\n"
        "assert len(ids) == 4 and not {1, 4, 6} & set(ids), ids\n"
        "print('OK', ids)\n"
    )
    env = dict(
        os.environ,
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        JAX_PLATFORMS="cpu",
        PYTHONPATH=os.pathsep.join(
            [os.path.join(os.path.dirname(__file__), "..", "src")]
            + sys.path
        ),
    )
    r = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        env=env,
    )
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout


# -- the acceptance scenario (DESIGN.md §13 / ISSUE gate) ------------------


def test_session_chaos_bitexact():
    """>= 3 device failures + >= 2 timeouts (plus a straggler and a
    compile flake) land on a chunked-streaming workload with batch
    traffic alongside: every session's total output is bit-identical
    to uninterrupted decode_stream_chunked, no ticket is silently
    dropped, and retries stay bounded by the injected-fault count."""
    streams = {f"t{i}": _stream(i) for i in range(2)}
    refs = {sid: _stream_ref(s) for sid, s in streams.items()}
    schedule = ChaosSchedule(
        [FaultEvent(at=a, kind="device_failure") for a in (0, 3, 6)]
        + [FaultEvent(at=a, kind="timeout") for a in (1, 8)]
        + [FaultEvent(at=4, kind="slow", delay=0.01),
           FaultEvent(at=10, kind="compile_error")]
    )
    injector = ChaosInjector(schedule)
    engine = DecodeEngine(
        max_batch=4, decision_depth=DEPTH, chaos=injector,
        dispatch_timeout=0.1,
    )
    for sid in streams:
        engine.open_session("ccsds-k7", sid=sid, now=0.0)
    tickets = {sid: [] for sid in streams}
    batch_tickets = []
    for i in range(T // C):
        now = float(i)
        for sid, s in sorted(streams.items()):
            tickets[sid].append(
                engine.submit_chunk(sid, s[i * C:(i + 1) * C], now=now)
            )
        batch_tickets.append(
            engine.submit(DecodeRequest(streams["t0"][: 3 * 32]), now=now)
        )
        engine.poll(now=now)
    engine.drain(now=10.0)

    s = engine.stats()
    assert sum(s["faults"].values()) == injector.total_injected() > 0
    assert s["faults"]["device_failure"] >= 3
    assert s["faults"]["timeout"] >= 2
    for sid in streams:  # zero dropped sessions
        assert sid in engine._sessions
    all_t = [t for ts in tickets.values() for t in ts] + batch_tickets
    assert all(t.done or t.dropped for t in all_t)  # nothing silent
    assert all(t.error is None for t in all_t)
    for sid in sorted(streams):  # bit-exact under chaos
        tail = engine.close_session(sid, now=10.0)
        got = np.concatenate([t.bits for t in tickets[sid]] + [tail])
        np.testing.assert_array_equal(got, refs[sid])
    assert 0 < s["retries"] <= injector.total_injected()


def test_checkpoint_failover_bitexact(tmp_path):
    """Checkpoint -> crash -> restore on a fresh engine: the restored
    session resumes at the checkpointed stream position, replaying the
    post-checkpoint window re-emits the lost bits byte-for-byte
    (idempotent delivery), and the total equals uninterrupted decode."""
    s0 = _stream(0)
    ref = _stream_ref(s0)
    a = DecodeEngine(max_batch=4, decision_depth=DEPTH,
                     checkpoint_dir=tmp_path)
    a.open_session("ccsds-k7", sid="t0", now=0.0)
    pre = []
    for i in range(2):
        t = a.submit_chunk("t0", s0[i * C:(i + 1) * C], now=float(i))
        a.poll(now=float(i))
        pre.append(t.bits)
    assert a.checkpoint_sessions(now=2.0) is not None
    t = a.submit_chunk("t0", s0[2 * C:3 * C], now=2.5)  # post-ckpt
    a.poll(now=2.5)
    lost = t.bits  # engine "dies" here; this emission is lost
    assert a.stats()["checkpoints"] == 1

    b = DecodeEngine(max_batch=4, decision_depth=DEPTH,
                     checkpoint_dir=tmp_path)
    assert b.restore_sessions(now=3.0) == {"t0": 2 * C}
    tr = b.submit_chunk("t0", s0[2 * C:3 * C], now=3.0)  # client replays
    b.poll(now=3.0)
    np.testing.assert_array_equal(tr.bits, lost)  # idempotent
    t3 = b.submit_chunk("t0", s0[3 * C:4 * C], now=4.0)
    b.poll(now=4.0)
    tail = b.close_session("t0", now=5.0)
    np.testing.assert_array_equal(
        np.concatenate(pre + [tr.bits, t3.bits, tail]), ref
    )
    # restoring on top of a live same-sid session is refused
    c = DecodeEngine(decision_depth=DEPTH, checkpoint_dir=tmp_path)
    c.open_session("ccsds-k7", sid="t0", now=0.0)
    with pytest.raises(ValueError, match="already open"):
        c.restore_sessions(now=0.0)


def test_periodic_checkpoint_on_poll(tmp_path):
    """checkpoint_interval drives automatic session-table checkpoints
    from poll on the engine clock."""
    engine = DecodeEngine(decision_depth=DEPTH, checkpoint_dir=tmp_path,
                          checkpoint_interval=1.0)
    engine.open_session("ccsds-k7", sid="t0", now=0.0)
    s0 = _stream(0)
    engine.submit_chunk("t0", s0[:C], now=0.0)
    engine.poll(now=0.0)   # first poll checkpoints
    engine.poll(now=0.5)   # within the interval: no new step
    assert engine.stats()["checkpoints"] == 1
    engine.submit_chunk("t0", s0[C:2 * C], now=1.6)
    engine.poll(now=1.6)   # past the interval
    assert engine.stats()["checkpoints"] == 2


# -- degradation ladder / failover -----------------------------------------


def test_degrade_time_parallel_to_batch():
    """Retry budget spent on the time_parallel rung degrades to batch
    (DEGRADATION_LADDER) and the answer stays bit-exact — every rung
    decodes the same cell by the §10 routing contract."""
    assert DEGRADATION_LADDER["time_parallel"] == ("time_parallel", "batch")
    injector = ChaosInjector(ChaosSchedule(
        [FaultEvent(at=a, kind="compile_error") for a in range(4)]
    ))
    engine = DecodeEngine(underfill_rows=1024, chaos=injector, retry=3)
    bits, req = _request("ccsds-k7", 512, "latency", seed=3)
    t = engine.submit(req, now=0.0)
    engine.drain(now=0.0)
    assert t.error is None and t.path == "batch"  # landed on the rung below
    s = engine.stats()
    assert s["degraded"] == 1 and s["retries"] == 3
    assert s["faults"]["compile_error"] == 4
    np.testing.assert_array_equal(t.bits, bits)


def test_degrade_sharded_to_batch_on_device_failure():
    """A device failure on the sharded path removes the device,
    re-plans the mesh (None when nothing survives), and degrades the
    dispatch to batch — bit-exact, with the failover counted."""
    from repro.distributed.decoder import frame_mesh

    mesh = frame_mesh()  # 1 CPU device: any rung fills it
    dead = int(np.asarray(mesh.devices).reshape(-1)[0].id)
    injector = ChaosInjector(ChaosSchedule(
        [FaultEvent(at=0, kind="device_failure", device=dead,
                    path="sharded")]
    ))
    engine = DecodeEngine(mesh=mesh, max_batch=4, chaos=injector)
    refs, tickets = [], []
    for i in range(4):
        bits, req = _request("ccsds-k7", 70, "throughput", seed=20 + i)
        refs.append(bits)
        tickets.append(engine.submit(req, now=0.0))
    engine.drain(now=0.0)
    s = engine.stats()
    assert s["failovers"] == 1 and s["degraded"] == 1
    assert engine.mesh is None  # sole device gone -> no mesh left
    for t, ref in zip(tickets, refs):
        assert t.error is None and t.path == "batch"
        np.testing.assert_array_equal(t.bits, ref)
    # the engine keeps serving (without the mesh) after the failover
    bits2, req2 = _request("ccsds-k7", 70, "throughput", seed=30)
    t2 = engine.submit(req2, now=1.0)
    engine.drain(now=1.0)
    np.testing.assert_array_equal(t2.bits, bits2)


def test_degrade_stream_to_xla_chunked(monkeypatch):
    """A kernel-backed one-pass stream cell that keeps faulting falls
    back to the XLA chunked decoder (stream -> stream_xla), bit-exact
    by the kernel-parity contract."""
    from repro.serve import engine as engine_mod

    monkeypatch.setattr(engine_mod, "STREAM_MIN_STEPS", 8)
    injector = ChaosInjector(ChaosSchedule(
        [FaultEvent(at=a, kind="timeout", path="stream")
         for a in range(4)]
    ))
    engine = DecodeEngine(use_kernel=True, chaos=injector, retry=3,
                          decision_depth=DEPTH)
    bits, req = _request("ccsds-k7", 256, "throughput", seed=11)
    t = engine.submit(req, now=0.0)
    engine.drain(now=0.0)
    assert t.error is None and t.path == "stream_xla"
    assert engine.stats()["degraded"] == 1
    np.testing.assert_array_equal(t.bits, bits)


def test_untyped_dispatch_error_is_not_retried_or_degraded(monkeypatch):
    """A real error from a dispatch (here a stand-in for a Mosaic
    compile failure) is never retried or degraded past — a slower rung
    would hide it: the riders fail with a typed error, the fault is
    counted, and its text is kept in stats()."""
    from repro.serve import engine as engine_mod

    monkeypatch.setattr(engine_mod, "STREAM_MIN_STEPS", 8)
    engine = DecodeEngine(use_kernel=True, retry=3, decision_depth=DEPTH)

    def broken(*a, **kw):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    dec = engine._decoder("ccsds-k7")
    monkeypatch.setattr(dec, "decode_stream_chunked", broken)
    _, req = _request("ccsds-k7", 256, "throughput", seed=12)
    t = engine.submit(req, now=0.0)
    engine.drain(now=0.0)
    s = engine.stats()
    assert t.error == "decode_failed:RuntimeError" and t.path is None
    assert s["retries"] == 0 and s["degraded"] == 0
    assert s["faults"] == {"error": 1} and s["failed"] == 1
    assert s["errors"] == [
        "stream: RuntimeError: Mosaic failed to compile TPU kernel"
    ]
    # the engine keeps serving other cells (latency class: batch path)
    bits, req2 = _request("ccsds-k7", 70, "latency", seed=13)
    t2 = engine.submit(req2, now=1.0)
    engine.drain(now=1.0)
    np.testing.assert_array_equal(t2.bits, bits)


def test_heartbeat_driven_failover():
    """Hosts silent past the monitor timeout are treated as failed
    devices at the top of poll: the mesh re-plans without waiting for a
    dispatch to hit the dead device."""
    from repro.distributed.decoder import frame_mesh

    mesh = frame_mesh()
    dead = int(np.asarray(mesh.devices).reshape(-1)[0].id)
    mon = HeartbeatMonitor([dead], timeout=1.0, now=0.0)
    engine = DecodeEngine(mesh=mesh, monitor=mon)
    engine.poll(now=0.5)  # within the window: nothing happens
    assert engine.stats()["failovers"] == 0
    engine.poll(now=2.0)  # silent past the timeout
    assert engine.stats()["failovers"] == 1
    assert engine.mesh is None
    engine.poll(now=3.0)  # already-failed hosts are not re-failed
    assert engine.stats()["failovers"] == 1


# -- typed errors, deadlines, backpressure ---------------------------------


def test_permanent_failure_typed_error():
    """A batch-path dispatch whose retry budget is spent (batch has no
    rung below) fails its tickets with a typed error — and the engine
    keeps serving the next request."""
    injector = ChaosInjector(ChaosSchedule(
        [FaultEvent(at=a, kind="timeout") for a in range(4)]
    ))
    engine = DecodeEngine(chaos=injector, retry=3)
    _, req = _request("ccsds-k7", 96, "throughput", seed=1)
    t = engine.submit(req, now=0.0)
    engine.drain(now=0.0)
    assert t.done and t.error == "decode_failed:DispatchTimeout"
    assert t.bits is None and t.retries == 3
    s = engine.stats()
    assert s["failed"] == 1 and s["retries"] == 3
    bits2, req2 = _request("ccsds-k7", 96, "throughput", seed=2)
    t2 = engine.submit(req2, now=1.0)
    engine.drain(now=1.0)
    assert t2.error is None
    np.testing.assert_array_equal(t2.bits, bits2)
    # decode() refuses to return partial results on typed errors
    eng2 = DecodeEngine(chaos=ChaosInjector(ChaosSchedule(
        [FaultEvent(at=a, kind="timeout") for a in range(4)]
    )), retry=3)
    with pytest.raises(RuntimeError, match="decode_failed"):
        eng2.decode([_request("ccsds-k7", 96, "throughput", seed=3)[1]])


def test_deadline_shedding():
    """Deadline-aware shedding: requests already expired at submit are
    rejected immediately; requests that expire while queued are shed at
    batch assembly — both with the typed error and the expired
    counter."""
    engine = DecodeEngine(max_wait={"throughput": 5.0})
    _, late = _request("ccsds-k7", 96, "throughput", seed=1,
                       deadline=1.0)
    t_late = engine.submit(late, now=2.0)  # dead on arrival
    assert t_late.done and t_late.error == "deadline_exceeded"
    _, queued = _request("ccsds-k7", 96, "throughput", seed=2,
                         deadline=3.0)
    _, fine = _request("ccsds-k7", 96, "throughput", seed=3)
    t_q = engine.submit(queued, now=2.5)
    t_f = engine.submit(fine, now=2.5)
    out = engine.drain(now=4.0)  # past t_q's deadline
    assert t_q.done and t_q.error == "deadline_exceeded"
    assert t_f.done and t_f.error is None and t_f.bits is not None
    assert t_q in out  # shed tickets are still delivered, once
    assert engine.stats()["expired"] == 2


def test_backpressure_reject_counted():
    """max_pending rejects are observable: the dropped ticket plus the
    rejected counter in the metrics registry, for both stateless
    requests and session chunks."""
    engine = DecodeEngine(max_pending=1)
    _, r1 = _request("ccsds-k7", 96, "throughput", seed=1)
    _, r2 = _request("ccsds-k7", 96, "throughput", seed=2)
    t1 = engine.submit(r1, now=0.0)
    t2 = engine.submit(r2, now=0.0)
    assert not t1.dropped and t2.dropped and not t2.done
    engine.open_session("ccsds-k7", sid="t0", now=0.0)
    t3 = engine.submit_chunk("t0", _stream(0)[:C], now=0.0)
    assert t3.dropped
    assert engine.stats()["rejected"] == 2
    assert engine.registry.counter(
        "engine_requests_total", ""
    ).total(event="rejected") == 2


def test_evicted_session_restored_from_checkpoint(tmp_path):
    """Eviction under fault-tolerant serving: an evicted (force-closed)
    session whose state was checkpointed earlier can be restored and
    resumed — replaying the post-checkpoint chunks reproduces the
    uninterrupted stream bit-for-bit."""
    s0 = _stream(0)
    ref = _stream_ref(s0)
    engine = DecodeEngine(decision_depth=DEPTH, session_capacity=1,
                          checkpoint_dir=tmp_path)
    engine.open_session("ccsds-k7", sid="t0", now=0.0)
    pre = []
    for i in range(2):
        t = engine.submit_chunk("t0", s0[i * C:(i + 1) * C], now=float(i))
        engine.poll(now=float(i))
        pre.append(t.bits)
    engine.checkpoint_sessions(now=2.0)
    engine.open_session("ccsds-k7", sid="t1", now=3.0)  # evicts t0
    assert "t0" not in engine._sessions
    assert engine.evicted_tail("t0").shape  # forced close parked a tail
    assert engine.restore_sessions(now=4.0) == {"t0": 2 * C}
    outs = []
    for i in (2, 3):
        t = engine.submit_chunk("t0", s0[i * C:(i + 1) * C], now=5.0 + i)
        engine.poll(now=5.0 + i)
        outs.append(t.bits)
    tail = engine.close_session("t0", now=10.0)
    np.testing.assert_array_equal(np.concatenate(pre + outs + [tail]), ref)


def test_forced_close_delivery_ordering():
    """Tickets completed out of band by a forced close (eviction) are
    delivered by the NEXT poll exactly once — the §10 poll contract
    holds under §13's close-cannot-defer rule."""
    engine = DecodeEngine(decision_depth=DEPTH, session_capacity=1)
    engine.open_session("ccsds-k7", sid="t0", now=0.0)
    t = engine.submit_chunk("t0", _stream(0)[:C], now=0.0)
    engine.open_session("ccsds-k7", sid="t1", now=1.0)  # evicts t0 now
    assert t.done and t.bits is not None  # decoded by the forced close
    first = engine.poll(now=2.0)
    assert t in first
    assert t not in engine.poll(now=3.0)  # exactly once


def test_session_fault_defers_not_drops():
    """A session dispatch that fails permanently in poll requeues its
    chunks (stall, don't drop): the next poll decodes them and the
    stream stays bit-exact."""
    s0 = _stream(0)
    ref = _stream_ref(s0)
    injector = ChaosInjector(ChaosSchedule(
        [FaultEvent(at=a, kind="timeout") for a in range(4)]
    ))
    engine = DecodeEngine(decision_depth=DEPTH, chaos=injector, retry=3)
    engine.open_session("ccsds-k7", sid="t0", now=0.0)
    t0 = engine.submit_chunk("t0", s0[:C], now=0.0)
    out = engine.poll(now=0.0)  # budget spent -> deferred, not failed
    assert out == [] and not t0.done and not t0.dropped
    assert engine.stats()["faults"]["timeout"] == 4
    engine.poll(now=1.0)  # schedule spent: the retry succeeds
    assert t0.done and t0.error is None
    outs = [t0.bits]
    for i in range(1, T // C):
        t = engine.submit_chunk("t0", s0[i * C:(i + 1) * C], now=float(i))
        engine.poll(now=float(i))
        outs.append(t.bits)
    tail = engine.close_session("t0", now=10.0)
    np.testing.assert_array_equal(np.concatenate(outs + [tail]), ref)


def test_stats_fault_keys_additive():
    """§13 adds stats keys without disturbing the §10/§12 schema."""
    engine = DecodeEngine()
    s = engine.stats()
    for k in ("faults", "retries", "degraded", "failovers", "expired",
              "failed", "checkpoints"):
        assert k in s
    assert s["faults"] == {} and s["retries"] == 0
