"""The DVB-S rate-7/8 cell (``dvbs.transponders4``) at a size the CPU can
run: its puncture mask is the standard's, it runs through the harness
and reads ``correct``, each planted fault turns ``correct`` false, and
its own metric reads the ``engine.depuncture`` spans."""
import copy
import dataclasses
import json
from pathlib import Path

import pytest

from benchlib.spec import load_cell, metric_reader
from test_faults import _flip_answers, _half_batch, _stale_state
from tiny import run_tiny

ROOT = Path(__file__).resolve().parents[2]
CELL = "dvbs.transponders4"

# EN 300 421 Table 2, rate 7/8: X (G1 = 171) and Y (G2 = 133), one
# character per trellis stage, 1 = sent
X = "1000101"
Y = "1111010"


def _tiny(ebn0=None, depth=256, chunk=448, sessions=2, pool=4, answers=64,
          warmup=112) -> dict:
    """The cell cut to CPU size: 448-stage chunks (64 pattern periods),
    decision depth 256 (448 stages after the 7/8 stretch)."""
    cell = load_cell(CELL)
    check = dict(cell.traffic["check"], answers=answers, batch=16,
                 warmup_stages=warmup)
    config = {"engine": dict(cell.config["engine"], decision_depth=depth)}
    if ebn0 is not None:
        config["ebn0_db"] = {c: ebn0 for c in cell.config["ebn0_db"]}
    return {"traffic": dict(sessions=sessions, chunk_stages=chunk,
                            pool_chunks=pool, check=check),
            "config": copy.deepcopy(config)}


def _listed(per_layer: bool) -> set:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    kind = spec["per_layer"] if per_layer else spec["end_to_end"]
    return {m["name"] for m in kind
            if "workloads" not in m or CELL in m["workloads"]}


def test_mask_is_the_standards():
    code = load_cell(CELL).config["codes"]["dvb-s-r78"]
    assert code["k"] == 7 and code["polys_octal"] == ["171", "133"]
    mask = code["puncture"]
    assert "".join(str(r[0]) for r in mask) == X
    assert "".join(str(r[1]) for r in mask) == Y
    from repro.codes import get_code

    assert tuple(map(tuple, mask)) == get_code("dvb-s-r78").puncture.mask


def test_cell_runs_and_is_correct():
    out = run_tiny(CELL, overrides_=_tiny())
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["compared"]["bits"] > 0
    assert set(out["metrics"]) == _listed(False)
    for m in out["metrics"].values():
        assert m["value"] > 0


def test_traced_run_reads_the_depuncture_span():
    out = run_tiny(CELL, seconds=3.0, trace=True, overrides_=_tiny())
    assert out["correct"], out["checks"]
    got = set(out["metrics"])
    # the CPU has no device plane: the device metrics are left out
    assert got <= _listed(True)
    assert {"engine_depuncture_ms.mbps", "engine_host_ms.mbps",
            "h2d_arrays.mbps"} <= got
    assert out["metrics"]["h2d_arrays.mbps"]["value"] == 1.0
    assert out["metrics"]["engine_depuncture_ms.mbps"]["value"] > 0


@pytest.mark.parametrize("fault", ["flip", "stale", "stale_pos", "half"])
def test_planted_fault_fails(fault, monkeypatch):
    if fault == "flip":
        _flip_answers(monkeypatch)
    elif fault == "half":
        _half_batch(monkeypatch)
    else:
        _stale_state(monkeypatch, advance_pos=fault == "stale_pos")
    assert not run_tiny(CELL, overrides_=_tiny())["correct"]


def test_control_fails_and_program_passes():
    """As ``test_control.py``: at 1 dB, with a longer depth and lead-in,
    the reference in bfloat16 fails the comparison the program passes."""
    ov = _tiny(ebn0=1.0, depth=1024, chunk=1792, sessions=16, pool=128,
               answers=512, warmup=896)
    out = run_tiny(CELL, seconds=3.0, seed=1, overrides_=ov, control=True)
    assert out["correct"], out["checks"]
    ctl = out["control"]
    assert ctl["compared"] == out["compared"]["answers"] > 0
    assert not ctl["correct"], ctl


@dataclasses.dataclass
class _Span:
    name: str
    t0: float
    t1: float


@dataclasses.dataclass
class _Run:
    spans: list


def test_depuncture_reader_mean_per_chunk_and_none_without():
    read = metric_reader("engine_depuncture_ms.mbps")
    spans = [_Span("engine.submit", 0.0, 1.0),
             _Span("engine.depuncture", 0.1, 0.102),
             _Span("engine.depuncture", 1.0, 1.004),
             _Span("engine.batch", 2.0, 3.0)]
    assert read(_Run(spans)) == pytest.approx(3.0)
    # what the parent program leaves: no engine.depuncture spans
    assert read(_Run(spans[:1] + spans[3:])) is None
    assert read(_Run([])) is None
