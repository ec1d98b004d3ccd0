"""``bench/run.py`` end to end on the CPU at tiny sizes, and its refusal
to run without a TPU."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from tiny import run_tiny

ROOT = Path(__file__).resolve().parents[2]
CELLS = ["ccsds.links256", "wifi.steady", "ccsds.links8", "wifi.closed64"]


def _metrics(cell_name, per_layer):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    kind = spec["per_layer"] if per_layer else spec["end_to_end"]
    return {m["name"] for m in kind
            if "workloads" not in m or cell_name in m["workloads"]}


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_runs_and_is_correct(cell_name):
    out = run_tiny(cell_name)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["compared"]["bits"] > 0
    assert set(out["metrics"]) == _metrics(cell_name, False)
    assert list(out)[-1] == "checks"
    for m in out["metrics"].values():
        assert m["value"] > 0


@pytest.mark.parametrize("cell_name", ["ccsds.links256", "wifi.closed64"])
def test_traced_run_reads_its_layers(cell_name):
    out = run_tiny(cell_name, seconds=3.0, trace=True)
    assert out["correct"]
    # the CPU has no device plane: the device metrics are left out
    got = set(out["metrics"])
    assert got <= _metrics(cell_name, True)
    assert any(n.startswith("engine_host_ms") for n in got)
    assert "breakdown" in out and "busy_s" in out["device"]


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ccsds.links8",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no CPU fallback" in p.stderr


def test_refuses_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and bench/."""
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ccsds.links8",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_the_knee_sweep_reads_the_open_loop():
    """What ``bench/sweep.py`` reads at each rate: the latency tails of
    every request due in the window, the backlog's trend, the generator's
    lateness."""
    import time

    import sweep
    from benchlib.harness import measure
    from tiny import cell_of, overrides

    cell = cell_of("wifi.steady")
    for part, upd in overrides("wifi.steady").items():
        getattr(cell, part).update(upd)
    cell.end_to_end += sweep.LATENCY
    m = measure(cell, 7, 2.0, False, time.perf_counter(),
                log=lambda *a, **k: None)
    got = m.result["metrics"]
    assert m.result["correct"]
    assert 0 < got["latency_p50_ms"]["value"] <= got["latency_p95_ms"][
        "value"] < 1e4
    items = [a for a in m.driver.answers if a.in_window]
    assert len(m.driver.late) == len(items) > 0
    assert abs(sweep.backlog_trend(items, m.t0, m.t_end)) < 10.0
