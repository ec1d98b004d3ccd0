"""The trace reduction on small traces whose answers are worked out by
hand."""
import pytest

from benchlib import xtrace
from benchlib.xtrace import Event

DEV = "/device:TPU:0"
KERNELS = ["acs_decode_fused_pallas", "transfer_matrix_pallas"]


def _dev(name, start, dur, plane=DEV, module=""):
    return Event(plane, "XLA Ops", name, start, dur, module)


def _host(name, start, dur):
    return Event("/host:CPU", "python", name, start, dur)


def test_busy_union_kernels_and_gaps():
    ev = [
        _dev("fusion.1", 100, 200),  # [100, 300)
        _dev("acs_decode_fused_pallas.2", 250, 150,
             module='custom_call_target="tpu_custom_call"'),
        # reads the kernel's output: not kernel time
        _dev("copy.3", 600, 100, module="copy(%acs_decode_fused_pallas.2)"),
        _dev("fusion.4", 950, 100),  # clipped to [950, 1000)
        _host("bench.poll", 0, 500),
        _host("bench.submit", 50, 30),  # nested in poll
        _host("bench.wait", 700, 200),
    ]
    s = xtrace.reduce(ev, 0.0, 1000.0, KERNELS)
    assert s.window_s == pytest.approx(1e-6)
    # busy: [100, 400) + [600, 700) + [950, 1000)
    assert s.busy_s == pytest.approx(450e-9)
    assert s.kernel_s == pytest.approx(150e-9)
    assert s.n_devices == 1
    # idle: [0,100) [400,600) [700,950): poll covers 0-100 (submit 50-80
    # inside it) and 400-500, wait covers 700-900, the rest is other
    g = s.gaps
    assert g["bench.submit"] == pytest.approx(30e-9)
    assert g["bench.poll"] == pytest.approx(70e-9 + 100e-9)
    assert g["bench.wait"] == pytest.approx(200e-9)
    assert g["host.other"] == pytest.approx(100e-9 + 50e-9)
    assert sum(g.values()) == pytest.approx(s.window_s - s.busy_s)
    assert s.top_ops(1)[0][0] == "fusion.1"


def test_busy_is_averaged_over_devices():
    ev = [_dev("a", 0, 500), _dev("b", 0, 250, plane="/device:TPU:1")]
    s = xtrace.reduce(ev, 0.0, 1000.0, KERNELS)
    assert s.n_devices == 2
    assert s.busy_s == pytest.approx(375e-9)


def test_no_device_plane_reads_nothing():
    s = xtrace.reduce([_host("bench.poll", 0, 10)], 0.0, 100.0, KERNELS)
    assert s.n_devices == 0 and s.busy_s == 0.0


def test_reads_a_recorded_trace(tmp_path):
    """A trace recorded here with the profiler: ``read_events`` finds
    the harness's host annotations in the ``.xplane.pb`` with their
    nesting and durations (the CPU has no device plane, so the device
    side reads nothing)."""
    import time

    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.poll"):
        with jax.profiler.TraceAnnotation("bench.submit"):
            f(x).block_until_ready()
        time.sleep(0.02)
    with jax.profiler.TraceAnnotation("bench.wait"):
        time.sleep(0.01)
    jax.profiler.stop_trace()
    rows = xtrace.read_events(str(tmp_path))
    host = {r.name: r for r in rows}
    assert set(host) == {"bench.poll", "bench.submit", "bench.wait"}
    poll, sub, wait = host["bench.poll"], host["bench.submit"], host["bench.wait"]
    assert poll.start_ns <= sub.start_ns
    assert sub.start_ns + sub.dur_ns <= poll.start_ns + poll.dur_ns
    assert poll.dur_ns >= 20e6 and wait.dur_ns >= 10e6
    assert wait.start_ns >= poll.start_ns + poll.dur_ns
    s = xtrace.reduce(rows, poll.start_ns, wait.start_ns + wait.dur_ns,
                      KERNELS)
    assert s.n_devices == 0 and s.busy_s == 0.0
