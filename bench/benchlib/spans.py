"""Arithmetic on the program's own spans, shared by the span readers in
``metrics/``: the program's ``engine.*`` and ``decoder.*`` spans
(``repro.obs.trace``), as ``harness._TracedRun.spans`` holds them.  A
span here is anything with ``name``, ``id``, ``parent``, ``t0``, ``t1``
and ``attrs``.  Each metric returns None when the run holds none of the
spans it reads, as a program without them (an older commit) does."""
from __future__ import annotations

import statistics
from typing import Dict, Iterable, List, Optional, Tuple

from .xtrace import _union as _merge

__all__ = ["union_s", "decoder_host_ms", "engine_host_share", "h2d_arrays"]

Interval = Tuple[float, float]


def union_s(iv: Iterable[Interval], t0: float = float("-inf"),
            t1: float = float("inf")) -> float:
    """Seconds covered by the union of ``iv``, clipped to [t0, t1)."""
    return sum(max(0.0, min(b, t1) - max(a, t0)) for a, b in _merge(iv))


def _minus(iv, cut) -> List[Interval]:
    """``iv`` less ``cut``, both sorted and disjoint (``_merge``)."""
    out = []
    for a, b in iv:
        for c, d in cut:
            if d <= a or c >= b:
                continue
            if c > a:
                out.append((a, c))
            a = max(a, d)
            if a >= b:
                break
        if a < b:
            out.append((a, b))
    return out


def _children(spans) -> Dict[int, list]:
    kids: Dict[int, list] = {}
    for s in spans:
        kids.setdefault(s.parent, []).append(s)
    return kids


def _descendants(span, kids) -> list:
    out, todo = [], list(kids.get(span.id, ()))
    while todo:
        s = todo.pop()
        out.append(s)
        todo.extend(kids.get(s.id, ()))
    return out


def decoder_host_ms(spans) -> Optional[float]:
    """Mean, over ``engine.batch`` spans that hold ``decoder.*`` spans,
    of the summed duration of the outermost of those, in ms."""
    kids = _children(spans)
    per_batch = []
    for b in spans:
        if b.name != "engine.batch":
            continue
        dec = [s for s in _descendants(b, kids)
               if s.name.startswith("decoder.")]
        ids = {s.id for s in dec}
        top = [s for s in dec if s.parent not in ids]
        if top:
            per_batch.append(sum(s.t1 - s.t0 for s in top))
    return 1e3 * statistics.fmean(per_batch) if per_batch else None


def engine_host_share(spans, t0: float, t1: float) -> Optional[float]:
    """% of [t0, t1) in which the engine's host code ran: the union of
    ``engine.poll`` and ``engine.submit`` spans, less the union of
    ``engine.device_wait`` spans."""
    if t1 <= t0:
        return None
    host = _merge([(s.t0, s.t1) for s in spans
                   if s.name in ("engine.poll", "engine.submit")])
    if not host:
        return None
    wait = _merge([(s.t0, s.t1) for s in spans
                   if s.name == "engine.device_wait"])
    return 100.0 * union_s(_minus(host, wait), t0, t1) / (t1 - t0)


def h2d_arrays(spans) -> Optional[float]:
    """Mean, over ``engine.batch`` spans, of the ``h2d_arrays`` their
    descendant spans report in all."""
    kids = _children(spans)
    per_batch = []
    for b in spans:
        if b.name != "engine.batch":
            continue
        n = [s.attrs["h2d_arrays"] for s in _descendants(b, kids)
             if "h2d_arrays" in s.attrs]
        if n:
            per_batch.append(sum(n))
    return statistics.fmean(per_batch) if per_batch else None
