"""Finding a cell's files by name.

``BENCHMARK.json`` names each cell's configuration and traffic; the
configuration entry names its file, the traffic is ``traffic/<name>.json``
(and ``traffic/<name>.py`` where the mix brings a driver of its own) and
each per-layer metric is read by ``metrics/<name>.py``.  Adding a cell
or a metric adds files and entries; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent

__all__ = ["BENCH_DIR", "ROOT", "Cell", "load_cell", "metric_reader",
           "traffic_module", "codes_of"]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    traffic_name: str
    end_to_end: List[dict]  # the metric entries this cell reports
    per_layer: List[dict]


def _reports(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, workload: Optional[dict] = None) -> Cell:
    """Cell ``name`` of ``BENCHMARK.json``, or, with ``workload`` (its
    ``config``, ``traffic`` and ``chips``), one the file does not list,
    such as a mix whose rate a sweep has still to fix."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    w = workload or cells.get(name)
    if w is None:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (BENCH_DIR / "traffic" / f"{w['traffic']}.json").read_text()
    )
    e2e = [m for m in spec["end_to_end"] if _reports(m, name)]
    per_layer = [m for m in spec["per_layer"] if _reports(m, name)]
    return Cell(name, int(w["chips"]), config, traffic, w["traffic"], e2e,
                per_layer)


def _load(path: Path, prefix: str, name: str):
    mod_spec = importlib.util.spec_from_file_location(
        f"{prefix}_{name.replace('.', '_').replace('-', '_')}", path
    )
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """The ``read(run) -> float | None`` of ``metrics/<name>.py``."""
    return _load(BENCH_DIR / "metrics" / f"{name}.py", "bench_metric",
                 name).read


def traffic_module(name: str):
    """``traffic/<name>.py``, the driver of a mix that its parameters
    alone cannot express, or None."""
    path = BENCH_DIR / "traffic" / f"{name}.py"
    return _load(path, "bench_traffic", name) if path.is_file() else None


def codes_of(config: dict) -> Dict[str, dict]:
    """Registry code name -> {k, polys (ints), mask, ...} of a config."""
    out = {}
    for name, c in config["codes"].items():
        out[name] = dict(c, polys=tuple(int(p, 8) for p in c["polys_octal"]))
    return out
