"""From a traced window to the program's own marks: its named programs, the
device scopes of its solver phases and kernels, and its host spans.

The program names what it runs (``repro.obs``):

* every planner program lowers as XLA module ``jit_<kind>`` (``jit_replan``,
  ``jit_plan``, ...), the epoch program as ``jit_epoch``;
* device scopes mark the solver's phases (``gd_iter``, ``warm_gate``,
  ``greedy_rounding``) and each NOMA kernel call
  (``noma_<kernel>_<link>_<pass>``, which the TPU compiler also takes as the
  call's instruction name, e.g. ``%jvp_noma_intra_up_fwd_.3``);
* host spans mark each host read (``sync.<name>``) and each program call
  of the loop (``dispatch.epoch``, ``dispatch.replan``).

A device operation's full name stack is not in its trace event. It is the
``tf_op`` stat of the event's metadata in the ``.xplane.pb``
(``jit(replan)/while/body/gd_iter/while/body/...``), keyed there by the
operation's HLO text, which is the event's name. ``jax.profiler``'s reader
does not expose metadata stats, so :func:`op_scopes` reads that one map from
the protobuf wire format.

The host and the chip keep their own clocks. :func:`clock_offset` bounds
the offset ``d`` (host time = device time + d) by causal pairs: the n-th
``jit_epoch`` execution starts after the n-th ``dispatch.epoch`` span
begins (and the n-th ``jit_plan`` or ``jit_replan`` after the n-th
``dispatch.replan``), and each ``jit_epoch`` execution ends before the
``sync.trigger`` span that reads it ends. The offset is known only to
that interval; :func:`report` splits idle time at both of its ends and
its middle.
"""
from __future__ import annotations

import re
from typing import NamedTuple

import numpy as np

from perfbench import trace as tracelib

SYNC = "sync."
DISPATCH = "dispatch."
HARNESS = "perfbench."
SCOPES = ("gd_iter", "warm_gate", "greedy_rounding")
KERNEL = re.compile(r"noma_(intra|per_ap|contract)_(up|dn)_(fwd|bwd)")
_CAUSES = (("dispatch.epoch", ("jit_epoch",)),
           ("dispatch.replan", ("jit_plan", "jit_replan")))


# -- the op -> name-stack map, from the protobuf wire format -----------------
def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, i: int = 0, end: int | None = None):
    """(field number, value) of each field of one protobuf message in
    ``buf[i:end]``: an int for a varint, a memoryview for a
    length-delimited field; fixed-width fields are skipped."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire == 1:
            i += 8
            continue
        elif wire == 5:
            i += 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, value


def _map_entries(raw):
    """(key, value) of the entries of a protobuf map field."""
    key = value = None
    for f, v in _fields(raw):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def op_scopes(path: str) -> dict[str, str]:
    """HLO text -> ``tf_op`` (the name stack) of every device operation in
    the ``.xplane.pb`` at ``path``. XPlane: name = 2, event_metadata = 4,
    stat_metadata = 5; XEventMetadata: name = 2, stats = 5; XStat:
    metadata_id = 1, str_value = 5, ref_value = 7."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: dict[str, str] = {}
    for field, plane in _fields(buf):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                events.append(v)
            elif f == 5:
                key, meta = _map_entries(v)
                stat_names[key] = next(
                    (bytes(x).decode() for g, x in _fields(meta) if g == 2), "")
        tf_op_id = next((k for k, v in stat_names.items() if v == "tf_op"),
                        None)
        if not name.startswith("/device:") or tf_op_id is None:
            continue
        for entry in events:
            _, meta = _map_entries(entry)
            hlo = op = None
            for f, v in _fields(meta):
                if f == 2:
                    hlo = bytes(v).decode()
                elif f == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) == tf_op_id:
                        op = (bytes(stat[5]).decode() if 5 in stat
                              else stat_names.get(stat.get(7), ""))
            if hlo and op is not None:
                out[hlo] = op
    return out


def in_scope(tf_op: str, scope: str) -> bool:
    """Whether the name stack ``tf_op`` lies under the named scope
    ``scope`` (a path component, possibly inside a transform such as
    ``transpose(jvp(scope))``)."""
    return re.search(rf"(?:^|[/(]){re.escape(scope)}(?:[)/]|$)",
                     tf_op) is not None


# -- device time by scope and by kernel --------------------------------------
def _leaf_sums(tr: tracelib.Trace) -> dict[str, tuple[int, float]]:
    """Operation name (HLO text) -> (calls, device ns summed over chips) of
    the leaf operations inside the window, counted as ``top_ops`` counts."""
    acc: dict[str, list] = {}
    for ln in tr.ops:
        lo, hi = tr.window
        a, b = np.maximum(ln.start, lo), np.minimum(ln.end, hi)
        sel = (b > a) & tracelib._leaves(ln)
        ns = np.bincount(ln.name[sel], weights=(b - a)[sel],
                         minlength=len(ln.names))
        n = np.bincount(ln.name[sel], minlength=len(ln.names))
        for j in np.flatnonzero(n):
            c = acc.setdefault(ln.names[j], [0, 0.0])
            c[0] += int(n[j])
            c[1] += float(ns[j])
    return {k: (v[0], v[1]) for k, v in acc.items()}


def scope_seconds(tr: tracelib.Trace, tf_ops: dict[str, str],
                  scope: str) -> float:
    """Device seconds, averaged over chips, of the leaf operations whose
    name stack lies under ``scope``, inside the window."""
    total = sum(ns for name, (_, ns) in _leaf_sums(tr).items()
                if in_scope(tf_ops.get(name, ""), scope))
    return total * 1e-9 / max(tr.n_chips, 1)


def kernel_seconds(tr: tracelib.Trace) -> dict[str, tuple[int, float]]:
    """``noma_<kernel>_<link>_<pass>`` -> (calls, device seconds averaged
    over chips) of the NOMA kernel calls inside the window, told by the
    instruction name the kernel's scope gives them."""
    out: dict[str, list] = {}
    for name, (n, ns) in _leaf_sums(tr).items():
        m = KERNEL.search(tracelib.base_name(name))
        if m:
            c = out.setdefault(m.group(0), [0, 0.0])
            c[0] += n
            c[1] += ns
    chips = max(tr.n_chips, 1)
    return {k: (v[0] // chips, v[1] * 1e-9 / chips)
            for k, v in sorted(out.items())}


# -- host spans and the two clocks -------------------------------------------
def host_spans(tr: tracelib.Trace, match) -> tuple[np.ndarray, np.ndarray]:
    """(start, end) in ns, by start, of the host events whose name
    satisfies ``match``, begun inside the window."""
    h = tr.host
    hit = np.array([bool(match(n)) for n in h.names], bool)
    sel = hit[h.name] if hit.size else np.zeros(0, bool)
    sel &= (h.start >= tr.window[0]) & (h.start <= tr.window[1])
    order = np.argsort(h.start[sel], kind="stable")
    return h.start[sel][order], h.end[sel][order]


def sync_count(tr: tracelib.Trace) -> int:
    """Host reads (``sync.*`` spans) begun inside the window."""
    return int(host_spans(tr, lambda n: n.startswith(SYNC))[0].size)


def _executions(tr: tracelib.Trace, modules, chip: int = 0):
    ln = tr.modules[chip]
    hit = np.array([tracelib.base_name(n) in modules for n in ln.names],
                   bool)
    sel = hit[ln.name] if hit.size else np.zeros(0, bool)
    order = np.argsort(ln.start[sel], kind="stable")
    return ln.start[sel][order], ln.end[sel][order]


class Offset(NamedTuple):
    """Bounds, in ns, of the offset d with host time = device time + d."""
    lo: float
    hi: float
    pairs: int

    @property
    def mid(self) -> float:
        return 0.5 * (self.lo + self.hi)


def clock_offset(tr: tracelib.Trace, chip: int = 0) -> Offset | None:
    """The feasible interval of the host-to-device clock offset from the
    causal pairs of the module docstring, or None where the trace holds no
    lower and upper bound (no device plane, or no program spans). Spans
    and executions pair in order; a kind whose counts differ is left out."""
    if not tr.modules:
        return None
    lo, hi, pairs = -np.inf, np.inf, 0
    for name, modules in _CAUSES:
        h0, _ = host_spans(tr, lambda n, name=name: n == name)
        d0, _ = _executions(tr, modules, chip)
        if h0.size and h0.size == d0.size:
            lo = max(lo, float(np.max(h0 - d0)))
            pairs += h0.size
    _, t1 = host_spans(tr, lambda n: n == SYNC + "trigger")
    _, e1 = _executions(tr, ("jit_epoch",), chip)
    if t1.size and t1.size == e1.size:
        hi = min(hi, float(np.min(t1 - e1)))
        pairs += t1.size
    if not (np.isfinite(lo) and np.isfinite(hi)):
        return None
    return Offset(lo, hi, pairs)


def _idle(tr: tracelib.Trace, d: float, chip: int = 0):
    """(start, end), host clock, of the gaps inside the window in which no
    operation ran on ``chip``, the device times shifted by ``d``."""
    ln = tr.ops[chip]
    a, b = tracelib.union(ln.start + d, ln.end + d)
    lo, hi = tr.window
    a, b = np.clip(a, lo, hi), np.clip(b, lo, hi)
    g0 = np.concatenate([[lo], b])
    g1 = np.concatenate([a, [hi]])
    keep = g1 > g0
    return g0[keep], g1[keep]


def _overlap_s(g0, g1, s0, s1) -> float:
    """Seconds of the intervals g that lie inside the union of s."""
    u0, u1 = tracelib.union(s0, s1)
    total = 0.0
    for a, b in zip(u0, u1):
        total += float(np.sum(np.clip(np.minimum(g1, b) - np.maximum(g0, a),
                                      0, None)))
    return total * 1e-9


def sync_idle_s(tr: tracelib.Trace, d: float) -> float:
    """Seconds in the window in which chip 0 was idle and the host was
    inside a ``sync.*`` span, on the host clock with device times shifted
    by the offset ``d``."""
    if not tr.ops:
        return 0.0
    g0, g1 = _idle(tr, d)
    s0, s1 = host_spans(tr, lambda n: n.startswith(SYNC))
    return _overlap_s(g0, g1, s0, s1)


def idle_by_span(tr: tracelib.Trace, d: float) -> dict[str, float]:
    """Chip-0 idle seconds in the window by what the host was in: a
    ``sync.*`` span, else a ``dispatch.*`` span, else a span of the
    benchmark (``perfbench.*`` other than the window), else none."""
    if not tr.ops:
        return {}
    g0, g1 = _idle(tr, d)
    out, taken = {}, (np.zeros(0), np.zeros(0))
    for label, match in (
            ("sync", lambda n: n.startswith(SYNC)),
            ("dispatch", lambda n: n.startswith(DISPATCH)),
            ("harness", lambda n: n.startswith(HARNESS)
             and n != tracelib.WINDOW)):
        s0, s1 = host_spans(tr, match)
        s0 = np.concatenate([taken[0], s0])
        s1 = np.concatenate([taken[1], s1])
        out[label] = _overlap_s(g0, g1, s0, s1) - sum(out.values())
        taken = (s0, s1)
    out["none"] = float(np.sum(g1 - g0)) * 1e-9 - sum(out.values())
    return out


# -- the breakdown of a kept trace -------------------------------------------
def report(tr: tracelib.Trace, tf_ops: dict[str, str]) -> dict:
    """Everything the program's marks show of one traced window, totals in
    seconds: module executions and time, time under each solver scope, the
    kernels by name, the clock offset, host reads, and idle time by span
    with the device clock shifted by each end of the offset interval and by
    its middle."""
    out: dict = {"window_s": tr.window_s, "busy_s": tracelib.busy_s(tr)}
    for mod in ("jit_epoch", "jit_replan", "jit_plan"):
        out[mod] = tracelib.module_seconds(tr, mod)
    out["scopes_s"] = {s: scope_seconds(tr, tf_ops, s) for s in SCOPES}
    out["kernels"] = kernel_seconds(tr)
    out["host_reads"] = sync_count(tr)
    off = clock_offset(tr)
    if off is not None:
        out["clock_offset_ns"] = off._asdict()
        out["idle_s"] = {end: idle_by_span(tr, d) for end, d in
                         (("lo", off.lo), ("mid", off.mid), ("hi", off.hi))}
    return out

