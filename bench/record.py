"""What one run leaves for the metric readers: the harness's own clocks,
counts of work, and (with ``--trace 1``) the reduced device trace."""
from __future__ import annotations

import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Optional

import jax
import numpy as np

from bench import work

# the scheduler stamps requests on the monotonic clock; so does the harness
clock = time.monotonic


@dataclass
class Run:
    config: dict
    traffic: dict
    peaks: dict
    window_s: float = 0.0
    setup_s: float = 0.0
    peak_bytes: int = 0
    window_compiles: int = 0
    backlog_at_close: Optional[int] = None   # open loop: queued + in flight
    # open loop: per request, seconds from the window's start (nan = never)
    due: Optional[np.ndarray] = None
    submit: Optional[np.ndarray] = None
    dispatch: Optional[np.ndarray] = None
    settle: Optional[np.ndarray] = None
    # per dispatched batch: (dispatch s, requests, real ids)
    batches: list = field(default_factory=list)
    # closed loop: per step, (start s, end s, samples)
    steps: list = field(default_factory=list)
    # work by part ("gather", "head", "update") of what the window finished
    work: dict = field(default_factory=dict)
    host_spans: dict = field(default_factory=dict)   # span name -> [ms]
    trace: object = None            # xplane.Summary of the traced window

    def add_work(self, parts: dict) -> None:
        for k, (flops, nbytes) in parts.items():
            self.work.setdefault(k, work.Work()).add(flops, nbytes)

    def total_work(self) -> work.Work:
        w = work.Work()
        for part in self.work.values():
            w.add(part.flops, part.bytes)
        return w


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``%
    of the values at or below it."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        raise ValueError("percentile of no values")
    return float(v[max(0, int(np.ceil(q / 100.0 * v.size)) - 1)])


@contextmanager
def compile_counter():
    """Counts jaxpr traces and backend compiles while the block runs."""
    box = [0]
    names = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/backend_compile_duration")

    def listen(event, duration, **kw):
        if event in names:
            box[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield box
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def start_trace(log_dir: str) -> None:
    """Profile devices and the host's annotations, without Python's own
    function tracer (which would record every call the loop makes)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def annotate(name: str):
    return jax.profiler.TraceAnnotation(name)
