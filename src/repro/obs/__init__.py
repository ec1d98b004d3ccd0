"""Observability subsystem (DESIGN.md §12): metrics registry and span
tracing mirrored into the JAX profiler's trace — dependency-free,
zero-cost when disabled.

Two layers, composable but independently usable:

  * :mod:`repro.obs.metrics` — ``MetricsRegistry`` of counters, gauges
    and fixed power-of-two-bucket histograms keyed by the serving
    layer's (code, path, F-rung, T-rung) cell labels, with Prometheus
    text and plain-dict snapshot exporters.
  * :mod:`repro.obs.trace` — ``SpanRecorder``/``span(...)`` nested span
    layer with a JSONL event-log sink (``experiments/obs/`` by
    convention), each span also a ``jax.profiler.TraceAnnotation`` so a
    profiler trace puts the host's spans beside the device ops.

``Observability`` bundles one registry + one recorder (+ optional JSONL
sink) for handing to ``DecodeEngine``/``BerFarm``; the module-level
``default_registry()`` is a ``NullRegistry`` until installed, so
library-level instrumentation (decoder path counters) is free by
default.

The §13 fault-tolerance layer accounts through the same registry:
``engine_faults_total{kind,path}``, ``engine_retries_total{path}``,
``engine_backoff_seconds_total{path}`` (virtual backoff budget —
recorded, not slept), ``engine_degraded_total{from,to}``,
``engine_failover_total`` and ``engine_checkpoints_total``, next to the
``expired``/``failed``/``restored`` lifecycle events on the request and
session families.

So does the §14 data-integrity plane: ``engine_scrub_total{event}``
(``sampled``/``frames``/``syndrome_flag`` from the online SDC
scrubber), ``engine_quarantined_total`` (devices failed over on
confirmed corruption), ``decoder_input_sanitized_total{reason,where}``
(clamp-and-count input hardening) and
``decoder_renorm_guard_total{event}`` (overflow-guard renorms and
tightenings for no-renorm precisions), plus the ``invalid``/``sdc``
events on the request family.  ``repro.obs.top`` renders one
``integrity`` line from these when any has fired.

CLI entry points: ``python -m repro.obs.top`` (terminal snapshot) and
``python -m repro.obs.smoke`` (the CI gate).
"""
from __future__ import annotations

from typing import Optional

from repro.obs.metrics import (
    POW2_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NullRegistry,
    default_registry,
    set_default_registry,
)
from repro.obs.trace import JsonlSink, NullRecorder, Span, SpanRecorder

__all__ = [
    "POW2_BUCKETS",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NullRegistry",
    "default_registry",
    "set_default_registry",
    "JsonlSink",
    "NullRecorder",
    "Span",
    "SpanRecorder",
    "Observability",
]


class Observability:
    """One registry + one recorder, wired together.

    ``Observability(jsonl=path)`` opens a :class:`JsonlSink` shared by
    the recorder (span/event lines) and :meth:`dump_metrics` (metrics
    lines), giving the single-file §12 event log.  With ``enabled=False``
    the recorder is the shared no-op and no sink is opened — the
    registry stays real (it is cheap and backs ``stats()``-style
    accessors), tracing costs nothing.
    """

    def __init__(self, enabled: bool = True, jsonl: Optional[str] = None,
                 clock=None, registry: Optional[MetricsRegistry] = None):
        self.registry = registry if registry is not None else MetricsRegistry()
        self.sink = JsonlSink(jsonl) if (jsonl and enabled) else None
        if enabled:
            kw = {"sink": self.sink}
            if clock is not None:
                kw["clock"] = clock
            self.recorder: SpanRecorder = SpanRecorder(**kw)
        else:
            self.recorder = NullRecorder()

    @property
    def enabled(self) -> bool:
        return self.recorder.enabled

    def dump_metrics(self) -> None:
        """Append one ``{"type": "metrics", ...}`` snapshot line to the
        JSONL sink (no-op without a sink)."""
        if self.sink is not None:
            self.sink.write(
                {"type": "metrics", "data": self.registry.snapshot()}
            )

    def close(self) -> None:
        self.dump_metrics()
        if self.sink is not None:
            self.sink.close()
