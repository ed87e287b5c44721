"""repro.obs — the telemetry subsystem (metrics, traces, events).

Three layers, one bundle:

* :mod:`repro.obs.metrics` — ``MetricsRegistry`` of counters, gauges,
  and bounded-memory streaming histograms (p50/p95/p99 without keeping
  every sample), with JSON ``snapshot()`` and Prometheus-style
  ``exposition()``.
* :mod:`repro.obs.tracing` — ``Tracer`` for nested per-request spans
  through the serving pipeline and the trainer (each enabled span is
  also a ``jax.profiler.TraceAnnotation``, and backend compiles are
  attributed to the span that caused them), plus the jit-side
  ``stage()`` / ``step_annotation()`` hooks that line our spans up with
  XLA profiles (``jax.named_scope`` + ``jax.profiler.TraceAnnotation``).
  Off by default with a zero-cost null path.
* :mod:`repro.obs.events` — ``EventLog`` for the versioned-swap
  protocol (swaps, rebuilds, refreshes, stale rejections) with
  per-version hit-rate attribution.

``Telemetry`` is the bundle consumers take as one constructor argument:

    from repro import obs
    engine = RecEngine(cfg, params, source="cached",
                       telemetry=obs.Telemetry(tracing=True))
    ...
    print(engine.telemetry.registry.exposition())
    print(engine.telemetry.events.hit_rate_by_version())

``Telemetry(metrics=False)`` is the genuinely uninstrumented
configuration: the engine records nothing and never dispatches the
hit-rate probe — this is the baseline the ``obs_overhead`` benchmark
scenario compares against.
"""
from __future__ import annotations

from typing import Optional

from repro.obs.events import Event, EventLog
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.tracing import (Span, Tracer, enable_stage_annotations,
                               stage, stage_annotations_enabled,
                               step_annotation)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "Span", "Tracer", "stage", "step_annotation",
    "enable_stage_annotations", "stage_annotations_enabled",
    "Event", "EventLog", "Telemetry",
]


class Telemetry:
    """One bundle of registry + tracer + event log.

    ``metrics``  — master switch. False means *nothing* is recorded and
                   the engine skips even dispatching accounting work
                   (the hit-rate probe); histograms/counters stay empty.
    ``tracing``  — collect per-request spans (host-side timing).
    ``device_stages`` — run the serving forward as separately jitted
                   stages with a sync between each, recording per-stage
                   *device* time — the live Fig-5 mode. Costs the
                   stage-boundary syncs; only turn on when you want the
                   characterization.
    """

    def __init__(self, *, metrics: bool = True, tracing: bool = False,
                 device_stages: bool = False, max_spans: int = 4096,
                 max_events: int = 4096,
                 registry: Optional[MetricsRegistry] = None):
        self.enabled = bool(metrics)
        self.registry = registry if registry is not None else MetricsRegistry()
        self.tracer = Tracer(enabled=tracing and self.enabled,
                             max_spans=max_spans, registry=self.registry)
        self.events = EventLog(max_events=max_events)
        self.device_stages = bool(device_stages) and self.enabled

    @classmethod
    def disabled(cls) -> "Telemetry":
        """The uninstrumented configuration (obs_overhead baseline)."""
        return cls(metrics=False)

    def span(self, name: str, attrs=None, *, step_num=None):
        return self.tracer.span(name, attrs, step_num=step_num)

    def emit(self, kind: str, version=None, **attrs):
        if not self.enabled:
            return None
        return self.events.emit(kind, version, **attrs)

    def snapshot(self) -> dict:
        """Registry snapshot + recent events, JSON-able (--metrics-json)."""
        snap = self.registry.snapshot()
        snap["events"] = [e.to_dict() for e in self.events.events]
        snap["hit_rate_by_version"] = {
            str(k): v for k, v in
            self.events.hit_rate_by_version().items()}
        return snap
