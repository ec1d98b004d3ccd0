"""One run of one cell: load, warm up, measure for ``--seconds``, check,
print.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared beside its limit.  The same numbers end standard error.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path
from typing import Optional

from .spec import ROOT, load_cell, metric_reader

clock = time.perf_counter

__all__ = ["main", "run_cell", "chip_cell", "measure", "Measured",
           "setup_jax"]

TRACE_S = 4.0  # seconds a traced run profiles, in the middle of its window


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def _quantile(xs, q: float) -> float:
    """The q-quantile by ``statistics.quantiles`` (exclusive method)."""
    xs = sorted(xs)
    if len(xs) == 1:
        return xs[0]
    cut = statistics.quantiles(xs, n=100)
    return cut[int(round(q * 100)) - 1]


def setup_jax(root: Path = ROOT):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    src = root / "src"
    if not (src / "repro" / "serve" / "engine.py").is_file():
        raise FileNotFoundError(
            f"the system under test is not here: {src}/repro is missing")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    import jax

    # the persistent compile cache at a fixed path inside the checkout,
    # unless the environment names one
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax


class _CompileCounter:
    """Counts programs lowered (compiled or loaded from the cache)."""

    def __init__(self):
        from jax import monitoring

        self.n = 0
        self.on = False
        monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_kw):
        if self.on and name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.n += 1


def _engine(config: dict, recorder):
    from repro.obs.metrics import MetricsRegistry
    from repro.serve.engine import DecodeEngine

    return DecodeEngine(registry=MetricsRegistry(), recorder=recorder,
                        **config["engine"])


def _counters(engine) -> dict:
    out = {}
    for lbl, v in engine.registry.counter("engine_llr_elems_total").series():
        out[lbl.get("kind")] = out.get(lbl.get("kind"), 0.0) + v
    return out


def require_chips(jax, cell) -> None:
    """Raise ``NoChip`` unless JAX holds as many TPU chips as the cell
    asks for."""
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        raise NoChip(
            f"JAX found {len(devs)} {devs[0].platform} device(s) "
            f"({devs[0].device_kind}); the cell asks for {cell.chips} TPU "
            f"chip(s), and there is no CPU fallback")


def chip_cell(name: str, workload: Optional[dict] = None):
    """Cell ``name`` (``spec.load_cell``), once JAX is set up and holds
    the chips it asks for."""
    cell = load_cell(name, workload)
    require_chips(setup_jax(), cell)
    return cell


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t_start: float) -> dict:
    """Run cell ``name`` once on this machine's chips and return its
    result object (without printing it)."""
    return measure(chip_cell(name), seed, seconds, trace, t_start).result


@dataclasses.dataclass
class Measured:
    result: dict  # the run's result object
    driver: object  # the traffic driver, with every answer
    t0: float  # the window's start and end, on ``clock``
    t_end: float


def measure(cell, seed: int, seconds: float, trace: bool, t_start: float,
            control: bool = False, log=print) -> Measured:
    """Set up, warm up, measure and check ``cell`` on whatever devices
    JAX holds (``run_cell`` has made sure of the chips).  ``control``
    also compares the control's answers (the reference in bfloat16) in
    the program's place, under the result's key ``control``."""
    root = ROOT
    jax = setup_jax()
    devs = jax.devices()
    from . import check, loops, xtrace

    compiles = _CompileCounter()
    recorder = None
    if trace:
        from repro.obs.trace import SpanRecorder

        recorder = SpanRecorder(max_spans=1 << 20)
    engine = _engine(cell.config, recorder)
    driver = loops.driver_for(cell, seed, seconds)
    t_warm = clock()
    warmed = driver.warm(engine)
    log(f"bench: data and engine {t_warm - t_start:.3f} s, warm-up "
        f"{clock() - t_warm:.3f} s ({warmed} programs)", file=sys.stderr)

    # traced runs profile the middle of the window
    prof = dict(dir=None, t0=None, t1=None, c0=None, c1=None)
    t_len = min(TRACE_S, seconds / 2)
    t_from = (seconds - t_len) / 2

    def on_tick(now: float):
        if not trace:
            return
        if prof["t0"] is None and now - t0 >= t_from:
            prof["dir"] = tempfile.mkdtemp(prefix="bench_trace_")
            prof["c0"] = _counters(engine)
            jax.profiler.start_trace(prof["dir"])
            prof["t0"] = clock()
        elif prof["t0"] is not None and prof["t1"] is None and (
                now - prof["t0"] >= t_len):
            prof["t1"] = clock()
            prof["c1"] = _counters(engine)
            jax.profiler.stop_trace()

    ann = loops._Annotate(trace)
    compiles.on = True
    t0 = clock()
    setup_s = t0 - t_start
    t_end = driver.window(engine, t0, seconds, ann, on_tick)
    compiles.on = False
    if prof["t0"] is not None and prof["t1"] is None:
        prof["t1"] = clock()
        prof["c1"] = _counters(engine)
        jax.profiler.stop_trace()
    window_s = t_end - t0
    driver.finish(engine)
    mem = devs[0].memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    log(f"bench: window {window_s:.3f} s, programs lowered in the window: "
        f"{compiles.n}", file=sys.stderr)

    items = driver.check_items()
    metrics = {}
    if not trace:
        metrics = _end_to_end(cell, driver, items, t0, t_end, setup_s)
    device = dict(platform=devs[0].platform, kind=devs[0].device_kind,
                  count=cell.chips, memory_peak_bytes=peak)
    breakdown = None
    if trace:
        summary = xtrace.summarize(prof["dir"],
                                   root / "bench" / "kernels.json")
        shutil.rmtree(prof["dir"], ignore_errors=True)
        run = _TracedRun(cell, driver, recorder, summary, prof, root,
                         devs[0].device_kind)
        for m in cell.per_layer:
            v = metric_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        breakdown = dict(device_ops=summary.top_ops(10),
                         idle_gaps=summary.top_gaps(10))

    # the reference runs once the window is closed and the engine freed
    del engine
    gc.collect()
    t_chk = clock()
    verdict = check.compare(driver, cell.traffic, seed, control)
    log(f"bench: compared {verdict['compared']} answers "
        f"({verdict['compared_bits']} bits, {verdict['differing_bits']} "
        f"differ from the reference) in {clock() - t_chk:.3f} s",
        file=sys.stderr)
    out = dict(correct=verdict["correct"], attempted=verdict["attempted"],
               failed=verdict["failed"], metrics=metrics, device=device)
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = dict(answers=verdict["compared"],
                           bits=verdict["compared_bits"],
                           differing_bits=verdict["differing_bits"])
    if control:
        out["control"] = verdict["control"]
    out["checks"] = {
        k: {"value": verdict["values"][k], "limit": verdict["limits"][k]}
        for k in verdict["limits"]
    }
    return Measured(out, driver, t0, t_end)


def _end_to_end(cell, driver, items, t0: float, t_end: float,
                setup_s: float) -> dict:
    names = {m["name"]: m["unit"] for m in cell.end_to_end}
    out = {"setup_s": {"value": setup_s, "unit": names["setup_s"]}}
    if "decoded_mbps" in names:
        bits = sum(a.info_bits for a in driver.answers
                   if a.held is not None and t0 <= a.held <= t_end
                   and a.ticket.bits is not None)
        out["decoded_mbps"] = {
            "value": bits / (t_end - t0) / 1e6 / cell.chips,
            "unit": names["decoded_mbps"]}
    lat = []
    for a in items:  # due in the window; a failure misses every limit
        ok = a.held is not None and a.ticket.bits is not None
        lat.append((a.held - a.due) * 1e3 if ok else math.inf)
    for q, key in ((0.50, "latency_p50_ms"), (0.95, "latency_p95_ms")):
        if key in names and lat:
            out[key] = {"value": _quantile(lat, q), "unit": names[key]}
    return out


class _TracedRun:
    """What a per-layer metric reader may read: the trace summary, the
    engine's spans and counters in the traced interval, the answers."""

    def __init__(self, cell, driver, recorder, summary, prof, root,
                 device_kind):
        self.cell = cell
        self.trace = summary
        self.t0, self.t1 = prof["t0"], prof["t1"]
        self.counters = {k: prof["c1"].get(k, 0.0) - prof["c0"].get(k, 0.0)
                         for k in set(prof["c0"]) | set(prof["c1"])}
        self.spans = [s for s in recorder.spans
                      if s.t1 is not None and self.t0 <= s.t0 < self.t1]
        self.answers = driver.answers
        self.peaks = json.loads((root / "bench" / "peaks.json").read_text())
        self.device_kind = device_kind
        self.config = cell.config


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = clock() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start)
    except (NoChip, FileNotFoundError, KeyError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for k, v in out["checks"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(json.dumps(out, default=str).replace("Infinity", "null"))
    return 0
