"""Each cell cut to a size a CPU test can run (Pallas kernels in
interpret mode), through the same harness as the chip runs."""
import copy
import time

from benchlib.harness import measure
from benchlib.spec import load_cell

BIG_SEED = 2**33 + 11

_MIX = [
    {"share": 0.55, "code": "wifi-11a", "len": [[0.5, 14, 14], [0.5, 20, 40]]},
    {"share": 0.45, "code": "wifi-11a-r34", "len": [[1.0, 64, 100]]},
]

TRAFFIC = {
    "ccsds.links256": dict(sessions=4, chunk_stages=512, pool_chunks=8),
    "ccsds.links8": dict(sessions=2, chunk_stages=1024, pool_chunks=4),
    "wifi.steady": dict(rate_per_s=20.0, drain_s=1.0, frames=_MIX),
    "wifi.closed64": dict(clients=4, pool_requests=16, frames=_MIX),
}
ENGINE = {
    "ccsds": dict(decision_depth=256),
    "wifi": dict(max_batch=4),
}
CHECK = dict(answers=64, batch=16, warmup_stages=64)
# the open-loop mix whose rate awaits the knee sweep: no cell lists it yet
UNLISTED = {"wifi.steady": dict(config="wifi-11a-rx", traffic="steady",
                                chips=1)}


def cell_of(cell_name: str):
    return load_cell(cell_name, UNLISTED.get(cell_name))


def overrides(cell_name: str, ebn0=None) -> dict:
    cell = cell_of(cell_name)
    traffic = dict(TRAFFIC[cell_name])
    traffic["check"] = dict(cell.traffic["check"], **CHECK)
    engine = dict(cell.config["engine"], **ENGINE[cell_name.split(".")[0]])
    config = {"engine": engine}
    if ebn0 is not None:
        config["ebn0_db"] = {c: ebn0 for c in cell.config["ebn0_db"]}
    return {"traffic": traffic, "config": copy.deepcopy(config)}


def run_tiny(cell_name: str, seconds=2.0, trace=False, seed=BIG_SEED,
             overrides_=None, control=False) -> dict:
    """The cell cut by ``overrides_`` (default: ``overrides``), measured
    on the CPU; returns the run's result object."""
    cell = cell_of(cell_name)
    for part, upd in (overrides_ or overrides(cell_name)).items():
        getattr(cell, part).update(upd)
    return measure(cell, seed, seconds, trace, time.perf_counter(),
                   control=control, log=lambda *a, **k: None).result
