"""The arithmetic behind the metric files in ``bench/metrics/``. Each
reader takes a ``record.Run`` and returns a number, or None where the run
holds nothing to read (never 0 for a share of a peak)."""
from __future__ import annotations

import numpy as np

from bench.record import percentile

STAGES = ("sparse_lookup", "emb_lookup", "interaction", "mlp")
GATHER = ("sparse_lookup", "emb_lookup")
HEAD = ("interaction", "mlp")


def latency_p99_ms(run):
    if run.due is None or run.due.size == 0:
        return None
    return 1e3 * percentile(run.settle - run.due, 99)


def completed_qps(run):
    """Requests settled in the window over the window."""
    if run.settle is None:
        return None
    n = int(np.sum(run.settle <= run.window_s))
    return n / run.window_s if n else None


def samples_per_s(run):
    if not run.steps:
        return None
    return sum(st[2] for st in run.steps) / run.window_s


def gen_lag_p99_ms(run):
    if run.due is None or run.due.size == 0:
        return None
    return 1e3 * percentile(run.submit - run.due, 99)


def batch_size_mean(run):
    sizes = [b[1] for b in run.batches if b[0] <= run.window_s]
    return float(np.mean(sizes)) if sizes else None


def host_ms_per_batch(run):
    d, s = run.host_spans.get("dispatch"), run.host_spans.get("settle")
    if not d or not s:
        return None
    return (sum(d) + sum(s)) / len(d)


def device_idle_pct(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.busy_s(0.0, run.window_s)
                    / run.window_s)


def _share(work, seconds, peaks):
    """The least time of ``work`` at one chip's ``peaks`` over
    ``seconds`` of device time summed over the chips: on several chips,
    the least time at all their peaks together over the mean chip's
    time."""
    if work is None or not seconds or seconds <= 0:
        return None
    return 100.0 * work.least_s(peaks) / seconds


def step_mfu_pct(run):
    """The least time of all the window's work over the device's busy time
    (serving) or over the window (training), at the peaks of all the
    chips."""
    if run.trace is None:
        return None
    t = (run.window_s if run.steps
         else run.trace.busy_s(0.0, np.inf))
    return _share(run.total_work(), t * run.trace.chips, run.peaks)


def gather_roofline_pct(run):
    if run.trace is None:
        return None
    return _share(run.work.get("gather"),
                  run.trace.scope_s(GATHER, 0.0, np.inf), run.peaks)


def dense_roofline_pct(run):
    if run.trace is None:
        return None
    return _share(run.work.get("head"),
                  run.trace.scope_s(HEAD, 0.0, np.inf), run.peaks)


def outside_stages_ms_per_step(run):
    if run.trace is None or not run.steps:
        return None
    return 1e3 * run.trace.outside_s(STAGES, 0.0, np.inf) / len(run.steps)
