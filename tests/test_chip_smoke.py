"""chip_smoke.py at toy size on CPU: every phase function runs in
interpret mode (no Mosaic check), the --chips 4 phase runs on 4 virtual
CPU devices in a fresh process, and main() refuses a CPU backend."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def test_phase_batch_toy():
    out = chip_smoke.phase_batch(
        n_frames=4, n_stages=256, n_ref_frames=1, require_mosaic=False
    )
    assert out["ber"] <= chip_smoke.MAX_BER


def test_phase_chunked_toy():
    # depth 256 stages over 512-stage chunks: 128-step ring, one-pass
    out = chip_smoke.phase_chunked(
        n_streams=4, stream_len=2048, chunk_len=512, decision_depth=256,
        require_mosaic=False,
    )
    assert out["one_pass"] > 0


def test_phase_time_parallel_toy():
    chip_smoke.phase_time_parallel(
        frame_counts=(1, 4), n_stages=1024, require_mosaic=False
    )


def test_phase_engine_toy(monkeypatch):
    from repro.serve import engine as engine_mod

    # short throughput frames still take the stream route
    monkeypatch.setattr(engine_mod, "STREAM_MIN_STEPS", 64)
    s = chip_smoke.phase_engine(
        counts=dict(stream=2, batch=2, time_parallel=2, wava=2, soft=2),
        lengths=dict(stream=256, batch=32, time_parallel=1024, wava=40,
                     soft=96),
        max_batch=4, require_mosaic=False,
    )
    assert s["failed"] == 0 and s["completed"] == 10


def test_phase_multichip_on_four_virtual_devices():
    env = dict(
        os.environ,
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        JAX_PLATFORMS="cpu",
        PYTHONPATH=os.pathsep.join(
            [str(ROOT / "src"), str(ROOT), os.environ.get("PYTHONPATH", "")]
        ),
    )
    code = (
        "import chip_smoke; chip_smoke.phase_multichip("
        "n_devices=4, n_frames=8, n_stages=256, n_streams=4, "
        "stream_len=512, tp_frames=2, require_mosaic=False)"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=ROOT,
        capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-4000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("phase 4x")]
    assert len(lines) == 3, r.stdout


@pytest.fixture
def no_cache(monkeypatch):
    """main() turns JAX's persistent cache on; keep this process's
    config as it was."""
    from repro.launch import compile_cache

    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")


def test_main_refuses_cpu(capsys, no_cache):
    rc = chip_smoke.main([])
    out, err = capsys.readouterr()
    assert rc != 0
    assert "no TPU" in err and "cpu" in err
    assert '"ok"' not in out


def test_failed_check_prints_no_result(monkeypatch, capsys, no_cache):
    """Past the platform gate, a failed check exits non-zero with no
    result line (the gate is satisfied by pretending to be a TPU)."""
    import jax

    class FakeTpu:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    def boom(**kw):
        raise chip_smoke.SmokeFailure("injected")

    ran = []
    monkeypatch.setattr(jax, "devices", lambda *a: [FakeTpu()])
    monkeypatch.setattr(chip_smoke, "phase_batch", boom)
    for name in ("phase_chunked", "phase_time_parallel", "phase_engine"):
        monkeypatch.setattr(chip_smoke, name,
                            lambda name=name: ran.append(name))
    rc = chip_smoke.main([])
    out, err = capsys.readouterr()
    assert rc == 1 and "injected" in err
    # the later phases still ran, so one chip run reports every failure
    assert ran == ["phase_chunked", "phase_time_parallel", "phase_engine"]
    assert not any(
        ln.startswith("{") and json.loads(ln).get("ok")
        for ln in out.splitlines()
    )


@pytest.mark.parametrize("chips", ["1", "4"])
def test_script_alone_fails(tmp_path, chips):
    """A directory that holds chip_smoke.py and nothing else of the repo
    exits non-zero and prints no result."""
    (tmp_path / "chip_smoke.py").write_text(
        (ROOT / "chip_smoke.py").read_text()
    )
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "chip_smoke.py", "--chips", chips], cwd=tmp_path,
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


_CACHE_PROBE = """
import sys
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache(root=sys.argv[1]))
import jax, jax.numpy as jnp
jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(7)).block_until_ready()
"""


@pytest.mark.parametrize("env_set", [True, False], ids=["env", "repo"])
def test_compile_cache_location(tmp_path, env_set):
    """With JAX_COMPILATION_CACHE_DIR set, entries land only there;
    without it, only under <root>/.jax_cache."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    given = tmp_path / "given"
    root = tmp_path / "repo"
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(given)
    r = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE, str(root)], env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    used, unused = (
        (given, root / ".jax_cache") if env_set
        else (root / ".jax_cache", given)
    )
    assert r.stdout.split()[-1] == str(used)
    assert any(p.name.startswith("jit_") for p in used.iterdir())
    assert not unused.exists()
