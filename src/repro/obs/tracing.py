"""Per-request span tracing for the serving pipeline.

A *span* is one named, timed region (``enqueue``, ``batch``,
``bucket_pad``, ``sparse_lookup``, ``interaction``, ``mlp``,
``respond``). Spans nest: ``Tracer.span()`` is a context manager and the
tracer maintains a stack, so a ``serve_step`` span contains the
``sparse_lookup`` span it opened. Finished spans land in a bounded deque
(oldest dropped, and counted in ``Tracer.dropped``) — tracing a replica
for a week costs the same memory as tracing it for a minute.

An enabled span also opens ``jax.profiler.TraceAnnotation(name)`` (its
scalar attrs ride along as the event's stats), so while a profiler
trace is recording every program span sits on the host plane, on one
clock with the device ops. While some enabled tracer lives, one
``jax.monitoring`` listener turns each backend compile into a
retroactive ``compile`` span under the innermost open span of every
enabled tracer with a span open, and counts it in that tracer's
registry as ``compiles_total{span=<name>}``.

Two hook layers bridge our spans to XLA's own tooling:

* ``stage(name)`` — used *inside* jitted code (dlrm / embedding_source).
  Disabled (the default) it returns a shared ``nullcontext`` singleton:
  no object allocation, no trace-side effects, and the compiled HLO is
  byte-identical (pinned by ``tests/test_obs.py`` via op histograms).
  Enabled it opens ``jax.named_scope`` + ``jax.profiler.TraceAnnotation``
  so the stage names show up in XLA profiles aligned with our spans.
* ``step_annotation(n)`` — ``jax.profiler.StepTraceAnnotation`` wrapper
  for the serve/train step loop, same disabled-is-free contract.
"""
from __future__ import annotations

import itertools
import time
import weakref
from collections import deque
from contextlib import contextmanager, nullcontext
from typing import Deque, Dict, Iterator, List, Optional

from repro.obs.metrics import MetricsRegistry

__all__ = ["Span", "Tracer", "stage", "step_annotation",
           "enable_stage_annotations", "stage_annotations_enabled"]

# Stage hooks are module-level (not per-Tracer) because they run inside
# jitted functions that know nothing about engine instances. One shared
# disabled singleton keeps the off path allocation-free and lets tests
# assert `stage("x") is stage("y")`.
_NULL = nullcontext()
_STAGE_ANNOTATIONS = False


def enable_stage_annotations(on: bool = True) -> None:
    """Globally toggle named_scope/TraceAnnotation emission in jitted
    stages. Off by default; flipping it on forces retrace (the scopes
    are metadata-only — same ops, pinned by test)."""
    global _STAGE_ANNOTATIONS
    _STAGE_ANNOTATIONS = bool(on)


def stage_annotations_enabled() -> bool:
    return _STAGE_ANNOTATIONS


@contextmanager
def _annotated(name: str):
    import jax

    with jax.named_scope(name), jax.profiler.TraceAnnotation(name):
        yield


def stage(name: str):
    """Context manager wrapping one pipeline stage inside jitted code."""
    if not _STAGE_ANNOTATIONS:
        return _NULL
    return _annotated(name)


def step_annotation(step_num: int, name: str = "serve_step"):
    """StepTraceAnnotation for the host-side step loop."""
    if not _STAGE_ANNOTATIONS:
        return _NULL
    import jax

    return jax.profiler.StepTraceAnnotation(name, step_num=step_num)


def _profiler_annotation(name: str, attrs: Optional[Dict],
                         step_num: Optional[int]):
    """The profiler event an enabled span opens: a step marker when it
    is one step of a loop, else a plain annotation. Scalar attrs become
    the event's stats (``batch`` joins a dispatch to its settle)."""
    import jax

    stats = {k: v for k, v in (attrs or {}).items()
             if isinstance(v, (int, float, str))}
    if step_num is not None:
        return jax.profiler.StepTraceAnnotation(name, step_num=step_num,
                                                **stats)
    return jax.profiler.TraceAnnotation(name, **stats)


# -- compile attribution ----------------------------------------------------
# One listener for the process, registered while some enabled tracer is
# alive. jax.monitoring reports a backend compile (or persistent-cache
# fetch) in the compiling thread when it ends, so the tracers' open stacks
# still hold the span that caused it.
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_WATCHED: "weakref.WeakSet[Tracer]" = weakref.WeakSet()
_LISTENING = False


def _on_compile(event: str, duration: float, **kw) -> None:
    if event != _BACKEND_COMPILE:
        return
    end = time.perf_counter()
    for tr in list(_WATCHED):
        if tr._stack:
            tr._compiled(end - duration, end, kw.get("fun_name"))


def _sync_listener() -> None:
    global _LISTENING
    want = bool(list(_WATCHED))
    if want == _LISTENING:
        return
    import jax

    if want:
        jax.monitoring.register_event_duration_secs_listener(_on_compile)
    else:
        jax.monitoring.unregister_event_duration_listener(_on_compile)
    _LISTENING = want


class Span:
    """One finished (or open) timed region."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "start",
                 "end", "attrs")

    def __init__(self, name: str, trace_id: int, span_id: int,
                 parent_id: Optional[int], start: float,
                 attrs: Optional[Dict] = None):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.start = start
        self.end: Optional[float] = None
        self.attrs: Dict = dict(attrs or {})

    @property
    def duration_ms(self) -> float:
        return ((self.end or time.perf_counter()) - self.start) * 1e3

    def to_dict(self) -> Dict:
        return {"name": self.name, "trace_id": self.trace_id,
                "span_id": self.span_id, "parent_id": self.parent_id,
                "start": self.start, "end": self.end,
                "duration_ms": self.duration_ms, "attrs": self.attrs}


class Tracer:
    """Bounded collector of nested spans.

    ``enabled=False`` (the default for a bare engine) turns ``span()``
    into the shared null context — the serve path pays one attribute
    check, nothing else. ``dropped`` counts the finished spans the ring
    evicted since the last ``clear()``: a number read from the ring is
    whole only while it is 0. ``registry`` holds the
    ``compiles_total{span=...}`` counters (a ``Telemetry`` passes its
    own).
    """

    def __init__(self, *, enabled: bool = True, max_spans: int = 4096,
                 registry: Optional[MetricsRegistry] = None):
        self.finished: Deque[Span] = deque(maxlen=max_spans)
        self.dropped = 0
        self.registry = (registry if registry is not None
                         else MetricsRegistry())
        self._stack: List[Span] = []
        self._ids = itertools.count(1)
        self._trace_ids = itertools.count(1)
        self._enabled = False
        self.enabled = enabled
        # a tracer dropped while enabled leaves the set by itself; the
        # listener goes with the last one
        weakref.finalize(self, _sync_listener).atexit = False

    @property
    def enabled(self) -> bool:
        return self._enabled

    @enabled.setter
    def enabled(self, on: bool) -> None:
        self._enabled = bool(on)
        if self._enabled:
            _WATCHED.add(self)
        else:
            _WATCHED.discard(self)
        _sync_listener()

    def _finish(self, s: Span) -> None:
        if len(self.finished) == self.finished.maxlen:
            self.dropped += 1
        self.finished.append(s)

    def _new(self, name: str, start: float,
             attrs: Optional[Dict]) -> Span:
        parent = self._stack[-1] if self._stack else None
        return Span(name,
                    trace_id=(parent.trace_id if parent
                              else next(self._trace_ids)),
                    span_id=next(self._ids),
                    parent_id=parent.span_id if parent else None,
                    start=start, attrs=attrs)

    @contextmanager
    def _span_cm(self, name: str, attrs: Optional[Dict],
                 step_num: Optional[int]) -> Iterator[Span]:
        with _profiler_annotation(name, attrs, step_num):
            s = self._new(name, time.perf_counter(), attrs)
            self._stack.append(s)
            try:
                yield s
            finally:
                s.end = time.perf_counter()
                self._stack.pop()
                self._finish(s)

    def span(self, name: str, attrs: Optional[Dict] = None, *,
             step_num: Optional[int] = None):
        """Open a span; with ``step_num`` its profiler event is a
        ``StepTraceAnnotation``, so XProf groups the loop by step."""
        if not self.enabled:
            return _NULL
        return self._span_cm(name, attrs, step_num)

    def _compiled(self, start: float, end: float, fun: Optional[str]):
        parent = self._stack[-1].name
        self.record("compile", start, end, {"span": parent, "fun": fun})
        self.registry.counter(
            "compiles_total", "backend compiles, by the innermost open "
            "span", labels={"span": parent}).inc()

    def record(self, name: str, start: float, end: float,
               attrs: Optional[Dict] = None) -> Optional[Span]:
        """Append an already-timed span (perf_counter timestamps),
        nested under the currently open span if any. Used when the timed
        region ends before its logical parent opens (e.g. the batcher
        drain that precedes the serve_step span it belongs to)."""
        if not self.enabled:
            return None
        s = self._new(name, start, attrs)
        s.end = end
        self._finish(s)
        return s

    def spans(self, name: Optional[str] = None) -> List[Span]:
        if name is None:
            return list(self.finished)
        return [s for s in self.finished if s.name == name]

    def traces(self) -> Dict[int, List[Span]]:
        """Finished spans grouped by trace, each in finish order."""
        out: Dict[int, List[Span]] = {}
        for s in self.finished:
            out.setdefault(s.trace_id, []).append(s)
        return out

    def clear(self) -> None:
        self.finished.clear()
        self.dropped = 0
