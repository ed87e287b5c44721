"""The program's own instrumentation in a traced window: the device scopes
below the stages, and the ``repro.obs`` spans on the profiler's host plane.

The device scopes (``relayout`` and ``gather_reduce`` inside ``emb_lookup``,
``sparse_update`` and ``dense_update`` in the train step) reach the trace
the way the stages do, through the compiled HLO's ``op_name``; the readers
here take ``xplane.Summary`` as it is. Under autodiff an op's path holds a
stage as ``jvp(mlp)`` or ``transpose(jvp(mlp))``; ``unwrapped`` strips
those wrappers, so the head's backward counts under its stage.

An enabled ``repro.obs.Tracer`` span is also a ``TraceAnnotation``, so the
program's spans sit on the host plane on the device's clock. The harness
wraps some of the same calls in annotations of the same name (``pump``,
``dispatch``, ``settle``, ``train_step``); in a chain of nested events of
one name the innermost, the program's, stands for them all.
"""
from __future__ import annotations

import dataclasses
import re
from dataclasses import dataclass

import numpy as np

from bench.readers import _share

RELAYOUT = "relayout"
GATHER_KERNEL = "gather_reduce"
SPARSE_UPDATE = "sparse_update"
# every scope the program opens, outermost first
SCOPES = ("sparse_lookup", "emb_lookup", RELAYOUT, GATHER_KERNEL,
          "interaction", "mlp", SPARSE_UPDATE, "dense_update")
# every span the serving loop and the trainer open
SPANS = ("pump", "plan", "dispatch", "bucket_pad", "settle", "settle_wait",
         "respond", "train_step", "feed", "step", "rows", "loss_sync")
_WRAPPED = re.compile(r"^(?:jvp|transpose|vmap|remat|checkpoint)\((.*)\)$")


# -- device scopes ------------------------------------------------------------

def _scope_s(s, scope: str):
    """Device seconds under ``scope``; None where no op carries it (a
    program that does not open it)."""
    if not s.in_scopes((scope,)).any():
        return None
    return s.scope_s((scope,), 0.0, np.inf)


def relayout_ms_per_batch(run):
    """Device ms under ``relayout`` per batch dispatched in the window."""
    if run.trace is None:
        return None
    t = _scope_s(run.trace, RELAYOUT)
    n = sum(1 for b in run.batches if b[0] <= run.window_s)
    return 1e3 * t / n if t is not None and n else None


def gather_kernel_roofline_pct(run):
    """Least time of the real ids' gather-reduce over the device time
    under ``gather_reduce``: the kernel's share without the relayout."""
    if run.trace is None:
        return None
    return _share(run.work.get("gather"),
                  _scope_s(run.trace, GATHER_KERNEL), run.peaks)


def sparse_update_ms_per_step(run):
    """Device ms under ``sparse_update`` per step."""
    if run.trace is None or not run.steps:
        return None
    t = _scope_s(run.trace, SPARSE_UPDATE)
    return 1e3 * t / len(run.steps) if t is not None else None


def _unwrap(segment: str) -> str:
    m = _WRAPPED.match(segment)
    while m:
        segment = m.group(1)
        m = _WRAPPED.match(segment)
    return segment


def unwrapped(s):
    """``s`` with autodiff wrappers taken off every path segment."""
    return dataclasses.replace(
        s, scopes=["/".join(_unwrap(g) for g in p.split("/"))
                   for p in s.scopes])


def scope_seconds(s) -> dict:
    """Device seconds under each program scope (autodiff unwrapped),
    with ``busy`` and ``outside`` (busy time under no scope), each summed
    over the chips."""
    u = unwrapped(s)
    out = {name: u.scope_s((name,), 0.0, np.inf) for name in SCOPES}
    out["busy"] = u.chips * u.busy_s(0.0, np.inf)
    out["outside"] = u.outside_s(SCOPES, 0.0, np.inf)
    return out


# -- program spans on the host plane -----------------------------------------

@dataclass
class HostSpan:
    start: float        # seconds from the window's start
    end: float
    name: str
    path: str           # e.g. "pump/settle/settle_wait"
    shadowed: bool = False   # an inner event of the same name stands for it

    @property
    def seconds(self) -> float:
        return self.end - self.start


def nest(events) -> list:
    """``HostSpan``s of ``(start, end, name, line)`` events, each with the
    path of the events holding it on its line; a same-name chain counts
    once, as its innermost event."""
    out = []
    by_line = {}
    for a, b, name, line in events:
        by_line.setdefault(line, []).append((a, b, name))
    for evs in by_line.values():
        stack = []
        for a, b, name in sorted(evs, key=lambda e: (e[0], -e[1])):
            while stack and stack[-1].end <= a:
                stack.pop()
            parent = stack[-1] if stack else None
            if parent is not None and parent.name == name:
                parent.shadowed = True
                path = parent.path
            else:
                path = f"{parent.path}/{name}" if parent else name
            span = HostSpan(a, b, name, path)
            out.append(span)
            stack.append(span)
    return out


def host_spans(path: str) -> list:
    """The program's spans on the host plane of one ``.xplane.pb``, in
    seconds from its ``window`` annotation."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    events, t0 = [], None
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name == "window":
                    t0 = ev.start_ns
                elif ev.name in SPANS:
                    events.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                   ev.name, (plane.name, k)))
    if t0 is None:
        raise ValueError("the trace has no 'window' annotation")
    return nest([((a - t0) * 1e-9, (b - t0) * 1e-9, n, ln)
                 for a, b, n, ln in events])


def idle_by_span(s, spans, k: int, window_s: float) -> list:
    """The ``k`` longest idle gaps, each ``[path, seconds]``: the path of
    the innermost program span covering more than half of it ("" where
    none does)."""
    out = []
    for a, b in sorted(s.gaps(window_s), key=lambda g: g[0] - g[1])[:k]:
        # spans covering more than half of one gap nest in one another
        cover = [sp.path for sp in spans
                 if min(b, sp.end) - max(a, sp.start) > (b - a) / 2]
        out.append([max(cover, key=lambda p: p.count("/"), default=""),
                    float(b - a)])
    return out


# -- what the program spans say ---------------------------------------------

def _seconds(spans, name: str, window_s: float) -> list:
    """Durations of the window's ``name`` spans, in the order they began."""
    return [sp.seconds for sp in sorted(spans, key=lambda sp: sp.start)
            if sp.name == name and not sp.shadowed
            and 0.0 <= sp.start and sp.end <= window_s]


def span_readings(spans, window_s: float) -> dict:
    """Host time by program span over the window: per batch or turn in
    serving, per step in training. A reading whose spans are absent is
    left out."""
    out = {}
    n_batches = len(_seconds(spans, "dispatch", window_s))
    pump = _seconds(spans, "pump", window_s)
    wait = _seconds(spans, "settle_wait", window_s)
    if n_batches and pump and wait:
        out["settle_wait_ms_per_batch"] = 1e3 * float(np.mean(wait))
        out["host_self_ms_per_batch"] = 1e3 * (sum(pump) - sum(wait)) \
            / n_batches
    plan = _seconds(spans, "plan", window_s)
    if plan:
        out["plan_ms_per_turn"] = 1e3 * float(np.mean(plan))
        out["plan_ms_by_third"] = [1e3 * float(t.mean()) if t.size else None
                                   for t in np.array_split(plan, 3)]
    steps = _seconds(spans, "train_step", window_s)
    sync = _seconds(spans, "loss_sync", window_s)
    if steps and sync:
        out["train_host_self_ms_per_step"] = 1e3 * (sum(steps) - sum(sync)) \
            / len(steps)
    totals = {}
    for sp in spans:
        if sp.shadowed or sp.start < 0.0 or sp.end > window_s:
            continue
        n, t = totals.get(sp.path, (0, 0.0))
        totals[sp.path] = (n + 1, t + sp.seconds)
    out["spans"] = {p: [n, t] for p, (n, t) in sorted(totals.items())}
    return out
