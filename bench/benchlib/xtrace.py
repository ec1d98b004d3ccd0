"""From a JAX profiler trace to the device numbers of a traced window.

``read_events`` turns the ``.xplane.pb`` that ``jax.profiler`` writes
into plain rows (plane, line, name, start ns, duration ns, module);
``reduce`` does the arithmetic on rows only, so tests can hand it a
small recorded trace:

* busy: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane),
  clipped to the window and averaged over the devices;
* kernel seconds: the summed device time of the operations named after
  one of the kernels listed in ``kernels.json``, or of the Mosaic custom
  calls whose own text names one;
* idle gaps: every stretch of the window in which device 0 ran nothing,
  split by the harness's host annotations (``bench.*``) that cover it;
  time no annotation covers is ``host.other``.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

__all__ = ["Event", "read_events", "reduce", "summarize", "Summary"]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    module: str = ""


def read_events(trace_dir: str) -> List[Event]:
    """Rows of the one ``.xplane.pb`` under ``trace_dir``: device
    operations and the harness's host annotations."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} xplane files in {trace_dir}")
    pd = ProfileData.from_file(paths[0])
    rows: List[Event] = []
    for plane in pd.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                name = ev.name
                if not device and not name.startswith(HOST_PREFIX):
                    continue
                module = ""
                if device:  # every text stat: module, HLO text, op path
                    for _key, val in ev.stats:
                        if isinstance(val, str):
                            module += f"{val} "
                rows.append(Event(plane.name, line.name, name,
                                  float(ev.start_ns), float(ev.duration_ns),
                                  module.strip()))
    return rows


def _union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return merged


def _clip(lo, hi, a, b):
    return max(lo, a), min(hi, b)


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float  # averaged over devices
    kernel_s: float  # ACS kernels, summed over devices
    n_devices: int
    ops: Dict[str, float]  # device seconds by operation
    gaps: Dict[str, float]  # idle seconds of device 0 by host annotation

    def top_ops(self, n: int):
        return [[k, v] for k, v in sorted(self.ops.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int):
        return [[k, v] for k, v in sorted(self.gaps.items(),
                                          key=lambda kv: -kv[1])[:n]]


def _is_kernel(ev: Event, kernels: Sequence[str]) -> bool:
    """An op named after a kernel, or a Mosaic custom call whose own
    text names it (an op that only reads a kernel's output is not)."""
    if any(k in ev.name for k in kernels):
        return True
    return "tpu_custom_call" in ev.module and any(k in ev.module
                                                  for k in kernels)


def reduce(events: Sequence[Event], lo_ns: float, hi_ns: float,
           kernels: Sequence[str]) -> Summary:
    """The window [lo_ns, hi_ns) of a trace's rows."""
    dev: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for ev in events:
        if ev.plane.startswith(DEVICE_PREFIX):
            dev.setdefault(ev.plane, []).append(ev)
        elif ev.name.startswith(HOST_PREFIX):
            host.append(ev)
    window = (hi_ns - lo_ns) / 1e9
    busy_total, kernel_ns = 0.0, 0.0
    ops: Dict[str, float] = {}
    first_busy: List[List[float]] = []
    for i, plane in enumerate(sorted(dev)):
        spans = []
        for ev in dev[plane]:
            a, b = _clip(lo_ns, hi_ns, ev.start_ns, ev.start_ns + ev.dur_ns)
            if b <= a:
                continue
            spans.append((a, b))
            ops[ev.name] = ops.get(ev.name, 0.0) + (b - a) / 1e9
            if _is_kernel(ev, kernels):
                kernel_ns += b - a
        merged = _union(spans)
        busy_total += sum(b - a for a, b in merged)
        if i == 0:
            first_busy = merged
    n_dev = len(dev)
    # idle stretches of the first device, split by host annotation
    gaps: Dict[str, float] = {}
    cursor = lo_ns
    idle = []
    for a, b in first_busy + [[hi_ns, hi_ns]]:
        if a > cursor:
            idle.append((cursor, a))
        cursor = max(cursor, b)
    ann = sorted((ev.start_ns, ev.start_ns + ev.dur_ns, ev.name)
                 for ev in host)
    for a, b in idle:
        covered = []
        for s, e, name in ann:
            if e <= a or s >= b:
                continue
            x, y = _clip(a, b, s, e)
            covered.append((x, y, name))
        # innermost wins: later-starting (nested) annotations override
        marks = sorted({a, b, *[x for x, _, _ in covered],
                        *[y for _, y, _ in covered]})
        for x, y in zip(marks, marks[1:]):
            owner = "host.other"
            for s, e, name in covered:
                if s <= x and y <= e:
                    owner = name
            gaps[owner] = gaps.get(owner, 0.0) + (y - x) / 1e9
    return Summary(window_s=window, busy_s=busy_total / max(n_dev, 1) / 1e9,
                   kernel_s=kernel_ns / 1e9, n_devices=n_dev, ops=ops,
                   gaps=gaps)


def summarize(trace_dir: str, kernels_file: Path) -> Summary:
    """The traced window of ``trace_dir``: from the first to the last of
    the harness's host annotations, which run without a break while the
    profiler is on (device times are on the host's clock in the trace)."""
    kernels = json.loads(Path(kernels_file).read_text())["acs"]
    events = read_events(trace_dir)
    host = [ev for ev in events if ev.name.startswith(HOST_PREFIX)]
    if not host:
        raise ValueError(f"no {HOST_PREFIX}* annotation in {trace_dir}")
    lo = min(ev.start_ns for ev in host)
    hi = max(ev.start_ns + ev.dur_ns for ev in host)
    return reduce(events, lo, hi, kernels)
