"""The UMTS voice cell (``umts.rmc1536``) at sizes the CPU can run: the
code-block driver's frames, the reference at K=9 rate 1/3, a run through
the harness that reads ``correct`` (and fails it for each planted
fault), and the padding share's reader."""
import copy
import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

from benchlib import channel, reference
from benchlib.spec import codes_of, load_cell, metric_reader, traffic_module
from np_viterbi import viterbi as np_viterbi
from test_faults import _flip_answers, _half_batch
from tiny import run_tiny

ROOT = Path(__file__).resolve().parents[2]
CELL = "umts.rmc1536"
UMTS, K9 = (0o557, 0o663, 0o711), 9


def _listed(cell: str, per_layer: bool) -> set:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    kind = spec["per_layer"] if per_layer else spec["end_to_end"]
    return {m["name"] for m in kind
            if "workloads" not in m or cell in m["workloads"]}


def _driver(seed=5, clients=6, pool=12):
    cell = load_cell(CELL)
    traffic = dict(cell.traffic, clients=clients, pool_requests=pool)
    return traffic_module("rmc1536").make_driver(traffic, cell.config, seed,
                                                 2.0)


def test_code_is_the_standards():
    code = load_cell(CELL).config["codes"]["umts-k9-r13"]
    assert code["k"] == 9 and code["polys_octal"] == ["557", "663", "711"]
    from repro.codes import get_code

    spec = get_code("umts-k9-r13").spec
    assert spec.polys == tuple(int(p, 8) for p in code["polys_octal"])


def test_driver_frames_are_code_blocks():
    """DTCH 244 + 16 + 8 = 268 stages and DCCH 100 + 12 + 8 = 120, 2:1
    in every block of ``clients`` requests, each with its 8-bit zero
    tail, and only the transport block counted as information."""
    d = _driver()
    stages = [nst for _, _, nst, _ in d.rows]
    assert sorted(set(stages)) == [120, 268]
    for lo in range(0, len(d.rows), 6):
        assert sorted(stages[lo: lo + 6]) == [120, 120, 268, 268, 268, 268]
    for _, _, nst, tb in d.rows:
        assert tb == {268: 244, 120: 100}[nst]
    assert d.max_stages == {"umts-k9-r13": 268}
    # the noiseless message ends in 8 zeros: re-draw the rows' bits
    cell = load_cell(CELL)
    c = codes_of(cell.config)["umts-k9-r13"]
    bits, _ = channel.make_llrs(channel.jax_key(5, 4, 0), len(d.rows), 268,
                                c["polys"], c["k"], 2.0,
                                tail_at=np.asarray(stages) - 8, tail_len=8)
    bits = np.asarray(bits)
    for row, (_, r, nst, _) in enumerate(d.rows):
        assert not bits[r, nst - 8: nst].any()
        assert d.llrs(row).shape == (nst, 3)


def test_reference_matches_numpy_at_k9():
    _, x = channel.make_llrs(channel.jax_key(7, 9), 3, 268, UMTS, K9, 1.0)
    x = np.asarray(x)
    ends = np.array([268, 120, 57])
    for start_zero in (True, False):
        a = reference.viterbi(x, UMTS, K9, start_zero, ends)
        b = np_viterbi(x, UMTS, K9, start_zero, ends)
        assert np.array_equal(a, b)


def _tiny(clients=6, pool=12, answers=64, ebn0=None) -> dict:
    cell = load_cell(CELL)
    check = dict(cell.traffic["check"], answers=answers, batch=16)
    config = {"engine": dict(cell.config["engine"], max_batch=4)}
    if ebn0 is not None:
        config["ebn0_db"] = {c: ebn0 for c in cell.config["ebn0_db"]}
    return {"traffic": dict(clients=clients, pool_requests=pool,
                            check=check),
            "config": copy.deepcopy(config)}


def test_cell_runs_and_is_correct():
    out = run_tiny(CELL, overrides_=_tiny())
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["compared"]["bits"] > 0
    assert set(out["metrics"]) == _listed(CELL, False)


def test_traced_run_reads_the_padding_share():
    out = run_tiny(CELL, seconds=3.0, trace=True, overrides_=_tiny())
    assert out["correct"], out["checks"]
    got = set(out["metrics"])
    assert got <= _listed(CELL, True)
    assert {"engine_pad_share.mbps", "engine_host_ms.mbps"} <= got
    # 268 stages pad to the 512 rung, 120 to 128
    assert 0 < out["metrics"]["engine_pad_share.mbps"]["value"] < 100


@pytest.mark.parametrize("fault", ["flip", "half"])
def test_planted_fault_fails(fault, monkeypatch):
    (_flip_answers if fault == "flip" else _half_batch)(monkeypatch)
    assert not run_tiny(CELL, overrides_=_tiny())["correct"]


@dataclasses.dataclass
class _Span:
    name: str
    attrs: dict


@dataclasses.dataclass
class _Run:
    spans: list


def test_pad_share_reader_sums_over_assembles_and_none_without():
    """Two batches: 1024 DTCH frames of 268 stages in 512-stage cells
    and 512 DCCH frames of 120 in 128, the rungs full: 43.1% of the
    cells is padding."""
    read = metric_reader("engine_pad_share.mbps")
    spans = [
        _Span("engine.assemble", dict(n_real=1024, f=1024,
                                      real_elems=1024 * 268 * 3,
                                      cell_elems=1024 * 512 * 3)),
        _Span("engine.assemble", dict(n_real=512, f=512,
                                      real_elems=512 * 120 * 3,
                                      cell_elems=512 * 128 * 3)),
        _Span("engine.batch", {}),
    ]
    want = 100 * (1 - (1024 * 268 + 512 * 120) / (1024 * 512 + 512 * 128))
    assert read(_Run(spans)) == pytest.approx(want)
    assert round(want, 1) == 43.1
    # what the parent program leaves: assemble spans without the counts
    assert read(_Run([_Span("engine.assemble", dict(n_real=3, f=4))]))\
        is None
    assert read(_Run([])) is None
