"""From a profiler trace of the measured window to the numbers the per-layer
readers take: device busy time, time per XLA module and per device
operation, and the idle gaps with what the host was doing in each.

The JAX profiler writes one ``.xplane.pb`` per traced window. In it every
chip is a plane named ``/device:TPU:<i>``. Its ``XLA Ops`` line holds one
event per operation run on the device, named by the operation's HLO text
(``%fusion.497 = f32[...] fusion(...)``); an operation that holds others,
such as a ``while`` loop, is an event that spans its children's events. Its
``XLA Modules`` line holds one event per executed program, named
``jit_<function>(<id>)``. Host threads are lines of the ``/host:CPU``
plane, on the same clock. The benchmark marks the window with a host
``TraceAnnotation`` (:data:`WINDOW`), and everything here is clipped to it.

A traced window of the closed loop holds millions of device events, so
each line is kept as arrays of start and end times and an index into its
distinct names.
"""
from __future__ import annotations

import glob
import os
import re
from typing import NamedTuple

import numpy as np

WINDOW = "perfbench.window"
EPOCH = "perfbench.epoch"          # host span of one epoch of the window
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_ID_SUFFIX = re.compile(r"\(\d+\)$")


class Line(NamedTuple):
    """Events of one trace line: times in ns, ``name[i]`` indexes
    ``names``."""
    start: np.ndarray
    end: np.ndarray
    name: np.ndarray
    names: list[str]


class Trace(NamedTuple):
    window: tuple[float, float]      # ns, the host's window annotation
    ops: list[Line]                  # per chip: device operations
    modules: list[Line]              # per chip: executed XLA modules
    host: Line                       # host events of every thread

    @property
    def n_chips(self) -> int:
        return len(self.ops)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def line(events) -> Line:
    """A Line from (name, start, end) tuples."""
    b = _Collector()
    b.add(events)
    return b.line()


def _clipped(ln: Line, lo: float, hi: float, keep=None):
    """(start, end) of the events inside [lo, hi], clipped to it, with the
    boolean selector of those events."""
    a, b = np.maximum(ln.start, lo), np.minimum(ln.end, hi)
    sel = b > a
    if keep is not None:
        sel &= keep
    return a[sel], b[sel], sel


def union(start, end) -> tuple[np.ndarray, np.ndarray]:
    """Merge intervals into disjoint sorted ones."""
    start, end = np.asarray(start, np.float64), np.asarray(end, np.float64)
    if start.size == 0:
        return start, end
    order = np.argsort(start, kind="stable")
    start, end = start[order], end[order]
    reach = np.maximum.accumulate(end)
    new = np.ones(start.size, bool)
    new[1:] = start[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, start.size - 1)
    return start[first], reach[last]


def busy_intervals(tr: Trace, chip: int) -> tuple[np.ndarray, np.ndarray]:
    a, b, _ = _clipped(tr.ops[chip], *tr.window)
    return union(a, b)


def busy_s(tr: Trace) -> float:
    """Seconds in which some operation ran on the device, averaged over
    the chips of the trace."""
    if not tr.ops:
        return 0.0
    total = sum(float(np.sum(b - a)) for a, b in
                (busy_intervals(tr, c) for c in range(tr.n_chips)))
    return total * 1e-9 / tr.n_chips


def base_name(name: str) -> str:
    """``jit_epoch(123)`` -> ``jit_epoch``; an operation's HLO text
    ``%fusion.497 = f32[...] fusion(...)`` -> ``%fusion.497``."""
    return _ID_SUFFIX.sub("", name.split(" = ", 1)[0])


def _named(ln: Line, match) -> np.ndarray:
    """Boolean selector of the events whose name satisfies ``match``."""
    hit = np.array([bool(match(n)) for n in ln.names], bool)
    return hit[ln.name] if hit.size else np.zeros(ln.name.size, bool)


def _seconds(tr: Trace, lines: list[Line], match) -> tuple[int, float]:
    n, total = 0, 0.0
    for ln in lines:
        a, b, _ = _clipped(ln, *tr.window, keep=_named(ln, match))
        n += a.size
        total += float(np.sum(b - a))
    chips = max(tr.n_chips, 1)
    return n // chips, total * 1e-9 / chips


def module_seconds(tr: Trace, module: str) -> tuple[int, float]:
    """(executions, device seconds averaged over chips) of the XLA module
    whose name without its id suffix is ``module``, inside the window."""
    return _seconds(tr, tr.modules, lambda n: base_name(n) == module)


def op_seconds(tr: Trace, pattern: str) -> tuple[int, float]:
    """(calls, device seconds averaged over chips) of the device operations
    whose name (their HLO text) matches the regular expression ``pattern``."""
    rx = re.compile(pattern)
    return _seconds(tr, tr.ops, rx.search)


def _leaves(ln: Line) -> np.ndarray:
    """Selector of the operations that hold no other operation (an event
    is a parent where the next event to start begins before it ends)."""
    order = np.argsort(ln.start, kind="stable")
    leaf = np.ones(ln.start.size, bool)
    s, e = ln.start[order], ln.end[order]
    leaf_sorted = np.ones(s.size, bool)
    leaf_sorted[:-1] = s[1:] >= e[:-1]
    leaf[order] = leaf_sorted
    return leaf


def top_ops(tr: Trace, k: int = 10) -> list[list]:
    """The k device operations, by HLO name and counting only operations
    that hold no other, that took most time in the window."""
    acc: dict[str, float] = {}
    for ln in tr.ops:
        a, b, sel = _clipped(ln, *tr.window, keep=_leaves(ln))
        sums = np.bincount(ln.name[sel], weights=b - a,
                           minlength=len(ln.names))
        for j in np.flatnonzero(sums):
            key = base_name(ln.names[j])
            acc[key] = acc.get(key, 0.0) + float(sums[j])
    chips = max(tr.n_chips, 1)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9 / chips] for name, ns in ranked]


def _gaps(tr: Trace) -> tuple[np.ndarray, np.ndarray]:
    """(start, end) in ns of the gaps on chip 0 in which no operation ran,
    in time order."""
    lo, hi = tr.window
    a, b = busy_intervals(tr, 0)
    g0 = np.concatenate([[lo], b])
    g1 = np.concatenate([a, [hi]])
    keep = g1 > g0
    return g0[keep], g1[keep]


def epoch_largest_gaps(tr: Trace, epoch: str) -> np.ndarray:
    """Seconds of the longest idle gap on chip 0 in each host span named
    ``epoch`` inside the window, a gap counting in the span that holds its
    middle. A profiler stall shows as one gap far above the median."""
    if not tr.ops:
        return np.zeros(0)
    h = tr.host
    names = np.asarray(h.names, object)
    sel = ((names[h.name] == epoch) if h.names else np.zeros(0, bool))
    sel &= (h.start >= tr.window[0]) & (h.end <= tr.window[1])
    order = np.argsort(h.start[sel], kind="stable")
    e0, e1 = h.start[sel][order], h.end[sel][order]
    g0, g1 = _gaps(tr)
    mid = 0.5 * (g0 + g1)
    at = np.searchsorted(e0, mid, side="right") - 1
    inside = (at >= 0) & (mid < e1[np.maximum(at, 0)]) if e0.size else \
        np.zeros(mid.size, bool)
    largest = np.zeros(e0.size)
    np.maximum.at(largest, at[inside], (g1 - g0)[inside] * 1e-9)
    return largest


def idle_gaps(tr: Trace, k: int = 10) -> list[list]:
    """The k longest gaps on chip 0 in which no operation ran, each named
    by the shortest host event that covers the gap's middle (what the host
    was doing), or ``"host: none"``."""
    if not tr.ops:
        return []
    g0, g1 = _gaps(tr)
    order = np.argsort(g0 - g1, kind="stable")[:k]
    h = tr.host
    not_window = np.array([n != WINDOW for n in h.names], bool)
    out = []
    for i in order:
        mid = 0.5 * (g0[i] + g1[i])
        cover = (h.start <= mid) & (h.end >= mid)
        if not_window.size:
            cover &= not_window[h.name]
        if cover.any():
            j = np.flatnonzero(cover)[np.argmin((h.end - h.start)[cover])]
            label = f"host: {h.names[h.name[j]]}"
        else:
            label = "host: none"
        out.append([label, float(g1[i] - g0[i]) * 1e-9])
    return out


# -- reading an xplane file ---------------------------------------------------
def find_xplane(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one xplane.pb under {directory}, "
                           f"found {len(found)}")
    return found[0]


class _Collector:
    """Collects events, (name, start, end) tuples or profiler events, into
    the arrays of a Line."""

    def __init__(self):
        self.names: dict[str, int] = {}
        self.idx: list[int] = []
        self.start: list[float] = []
        self.end: list[float] = []

    def add(self, events) -> None:
        names = self.names
        for e in events:
            nm, a, b = ((e.name, e.start_ns, e.end_ns) if hasattr(e, "name")
                        else e)
            self.idx.append(names.setdefault(nm, len(names)))
            self.start.append(a)
            self.end.append(b)

    def line(self) -> Line:
        return Line(np.asarray(self.start, np.float64),
                    np.asarray(self.end, np.float64),
                    np.asarray(self.idx, np.int64), list(self.names))


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` written by ``jax.profiler``."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    ops: dict[int, _Collector] = {}
    modules: dict[int, _Collector] = {}
    host = _Collector()
    for plane in data.planes:
        dev = _DEVICE.match(plane.name)
        for ln in plane.lines:
            if dev is not None and ln.name in (OPS_LINE, MODULES_LINE):
                into = ops if ln.name == OPS_LINE else modules
                into.setdefault(int(dev.group(1)), _Collector()).add(ln.events)
            elif plane.name.startswith("/host:"):
                host.add(ln.events)
    h = host.line()
    marks = np.flatnonzero(np.asarray(h.names, object)[h.name] == WINDOW) \
        if h.names else np.array([], int)
    if marks.size != 1:
        raise RuntimeError(f"trace holds {marks.size} '{WINDOW}' spans")
    chips = sorted(set(ops) | set(modules))
    empty = _Collector().line()
    return Trace(window=(float(h.start[marks[0]]), float(h.end[marks[0]])),
                 ops=[ops[c].line() if c in ops else empty for c in chips],
                 modules=[modules[c].line() if c in modules else empty
                          for c in chips],
                 host=h)
