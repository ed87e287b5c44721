"""Reduce a JAX profiler trace of the window to device times by layer.

On a TPU the ``.xplane.pb`` has a ``/device:TPU:<n>`` plane whose ``XLA Ops``
line holds one event per executed HLO instruction, named by the
instruction's text (``%fusion.31 = s32[1024000]{...} fusion(...), ...``) and
carrying no scope. Ops inside a while loop appear both as the loop and as
their own events, so intervals overlap. The scope of an op comes from the
compiled programs' HLO text, whose ``metadata={op_name="jit(step)/
sparse_lookup/emb_lookup/..."}`` carries the ``jax.named_scope`` path: ops are
matched to it by instruction name and result type. Times by scope are unions
of intervals, never sums, so nesting counts once.

Host annotations (``window``, ``pump``, ``dispatch``, ``settle``,
``gen_wait``, ``train_step``) are read from the host plane on the same
clock, and label each idle gap of the device by what the host was doing.
"""
from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field

import numpy as np

HOST_NAMES = ("pump", "dispatch", "settle", "gen_wait", "train_step")
_LINE = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+) = (.*)$")
_OPNAME = re.compile(r'op_name="([^"]*)"')


def _split_type(rest: str) -> str:
    """The result type at the head of ``rest`` (a tuple keeps its
    parentheses and inner spaces)."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                return rest[:i + 1]
    return rest.split(" ", 1)[0]


def op_key(text: str):
    m = _LINE.match(text)
    if not m:
        return None
    return m.group(1), _split_type(m.group(2))


def scope_map(hlo_texts) -> dict:
    """(instruction name, result type) -> op_name path, from compiled HLO
    text."""
    out = {}
    for text in hlo_texts:
        for line in text.splitlines():
            key = op_key(line)
            if key is None:
                continue
            m = _OPNAME.search(line)
            out.setdefault(key, m.group(1) if m else "")
    return out


@dataclass
class Summary:
    """Device ops and host annotations of one traced window, in seconds
    from the window's start."""
    starts: np.ndarray
    ends: np.ndarray
    names: list                 # instruction name, e.g. "%fusion.31"
    scopes: list                # op_name path ("" where unknown)
    host: list = field(default_factory=list)   # (start, end, name)
    mapped_share: float = 0.0   # share of op time whose scope was found

    def _union(self, mask, lo: float, hi: float) -> float:
        s = np.clip(self.starts[mask], lo, hi)
        e = np.clip(self.ends[mask], lo, hi)
        order = np.argsort(s)
        total, cur_s, cur_e = 0.0, None, None
        for a, b in zip(s[order], e[order]):
            if cur_e is None or a > cur_e:
                if cur_e is not None:
                    total += cur_e - cur_s
                cur_s, cur_e = a, b
            else:
                cur_e = max(cur_e, b)
        if cur_e is not None:
            total += cur_e - cur_s
        return float(total)

    def busy_s(self, lo: float, hi: float) -> float:
        """Seconds of [lo, hi) in which some op ran on the device."""
        return self._union(np.ones(len(self.names), bool), lo, hi)

    def in_scopes(self, scopes) -> np.ndarray:
        return np.array([any(f"/{s}/" in f"/{p}/" for s in scopes)
                         for p in self.scopes], bool)

    def scope_s(self, scopes, lo: float, hi: float) -> float:
        """Seconds of [lo, hi) in which an op under any of ``scopes``
        ran."""
        return self._union(self.in_scopes(scopes), lo, hi)

    def outside_s(self, scopes, lo: float, hi: float) -> float:
        """Busy seconds of [lo, hi) not covered by any op under
        ``scopes``."""
        return self.busy_s(lo, hi) - self.scope_s(scopes, lo, hi)

    def top_ops(self, k: int) -> list:
        """The ``k`` instructions with the most device time (summed over
        their events; loop bodies count inside their loop too)."""
        tot = {}
        for n, s, e in zip(self.names, self.starts, self.ends):
            tot[n] = tot.get(n, 0.0) + float(e - s)
        return [[n, t] for n, t in
                sorted(tot.items(), key=lambda x: -x[1])[:k]]

    def idle_gaps(self, k: int, window_s: float) -> list:
        """The ``k`` longest device-idle gaps in the window, each named by
        the host annotation that covers most of it."""
        order = np.argsort(self.starts)
        gaps, t = [], 0.0
        for s, e in zip(self.starts[order], self.ends[order]):
            if s > t:
                gaps.append((t, min(s, window_s)))
            t = max(t, e)
        if t < window_s:
            gaps.append((t, window_s))
        gaps = sorted((g for g in gaps if g[1] > g[0]),
                      key=lambda g: g[0] - g[1])[:k]
        out = []
        for a, b in gaps:
            best, label = 0.0, "none"
            for hs, he, name in self.host:
                ov = min(b, he) - max(a, hs)
                if ov > best:
                    best, label = ov, name
            out.append([label, float(b - a)])
        return out


def _device_plane(pd):
    planes = [p for p in pd.planes
              if re.match(r"^/device:TPU:\d+$", p.name)]
    if not planes:
        raise ValueError("the trace has no /device:TPU:<n> plane")
    return planes[0]


def load(path: str, hlo_texts) -> Summary:
    """Summary of one ``.xplane.pb``, times relative to the ``window``
    annotation."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host, t0 = [], None
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "window":
                    t0 = ev.start_ns
                elif ev.name in HOST_NAMES:
                    host.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                 ev.name))
    if t0 is None:
        raise ValueError("the trace has no 'window' annotation")
    keys = scope_map(hlo_texts)
    starts, ends, names, scopes = [], [], [], []
    for line in _device_plane(pd).lines:
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            key = op_key(ev.name)
            names.append(key[0] if key else ev.name.split(" ", 1)[0])
            scopes.append(keys.get(key, "") if key else "")
            starts.append(ev.start_ns)
            ends.append(ev.start_ns + ev.duration_ns)
    starts = (np.asarray(starts, np.float64) - t0) * 1e-9
    ends = (np.asarray(ends, np.float64) - t0) * 1e-9
    dur = ends - starts
    known = np.array([bool(s) for s in scopes], bool)
    mapped = float(dur[known].sum() / dur.sum()) if dur.sum() else 0.0
    host = [((a - t0) * 1e-9, (b - t0) * 1e-9, n) for a, b, n in host]
    return Summary(starts=starts, ends=ends, names=names, scopes=scopes,
                   host=host, mapped_share=mapped)


def load_dir(d: str, hlo_texts) -> Summary:
    files = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {d}, found "
                         f"{len(files)}")
    return load(files[0], hlo_texts)
