"""Reduction from a profiler trace to device busy time, op times and idle
gaps attributed to the host's spans.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes: every
device plane's op line gives intervals on that device, and the harness's
own ``TraceAnnotation`` spans (names starting ``bench.``) give what the host
was doing. Times are seconds on the trace's clock, which the host spans and
the device planes share. The functions below work on those lists alone, so
they are tested on small hand-made traces as well as on recorded ones.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
Event = Tuple[str, float, float]         # (name, start_s, end_s)

TPU_PLANE = re.compile(r"^/device:TPU:\d+$")
# the TPU compiler runs an all-gather or reduce-scatter it overlaps with
# compute as a custom fusion pair, async-collective-start and -done
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "async-collective")
_SUFFIX = re.compile(r"(\.\d+)+$")
_HLO_NAME = re.compile(r"^%?([^\s=]+)\s*=")
_START_OPERAND = re.compile(r"%([\w.\-]+-start(?:\.\d+)*)\b")


@dataclass
class Trace:
    devices: Dict[str, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)

    def span(self, name: str) -> Optional[Interval]:
        """The first host span of that name."""
        for n, s, e in self.host:
            if n == name:
                return (s, e)
        return None

    def spans(self, name: str) -> List[Interval]:
        return [(s, e) for n, s, e in self.host if n == name]


def xplane_path(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str, *, plane=TPU_PLANE, op_line: str = "XLA Ops",
         host_prefix: str = "bench.") -> Trace:
    """Device ops from planes whose name matches ``plane`` (line named
    ``op_line``, or starting with it), host spans named ``host_prefix*``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    for p in pd.planes:
        is_dev = bool(plane.match(p.name))
        for line in p.lines:
            dev_line = is_dev and line.name.startswith(op_line)
            for e in line.events:
                name = e.name
                s, d = e.start_ns * 1e-9, e.duration_ns * 1e-9
                if dev_line and d > 0 and "::" not in name \
                        and not name.startswith("end: "):
                    tr.devices.setdefault(p.name, []).append(
                        (name, s, s + d))
                elif name.startswith(host_prefix):
                    tr.host.append((name, s, s + d))
    for evs in tr.devices.values():
        evs.sort(key=lambda x: x[1])
    tr.host.sort(key=lambda x: x[1])
    return tr


# --------------------------------------------------------------------------- #
# Interval arithmetic
# --------------------------------------------------------------------------- #
def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def intersect(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Intersection of two unions (each sorted, non-overlapping)."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """``a`` minus ``b`` (both unions)."""
    out = []
    for s, e in a:
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
        if cur < e:
            out.append((cur, e))
    return out


# --------------------------------------------------------------------------- #
# What the metrics read
# --------------------------------------------------------------------------- #
def busy(tr: Trace, within: Sequence[Interval]) -> float:
    """Seconds in which some op ran, inside ``within``, mean over devices."""
    if not tr.devices:
        return 0.0
    within = union(within)
    return sum(length(intersect(union((s, e) for _, s, e in evs), within))
               for evs in tr.devices.values()) / len(tr.devices)


def op_seconds(tr: Trace, within: Sequence[Interval],
               match=lambda name: True) -> float:
    """Summed duration of the ops whose name ``match``es, clipped to
    ``within``, mean over devices."""
    if not tr.devices:
        return 0.0
    within = union(within)
    tot = 0.0
    for evs in tr.devices.values():
        tot += length(c for name, s, e in evs if match(op_name(name))
                      for c in intersect([(s, e)], within))
    return tot / len(tr.devices)


def is_collective(name: str) -> bool:
    return any(c in name for c in COLLECTIVES)


def leaf_ops(evs: Sequence[Event]) -> List[Event]:
    """The ops of one line that hold no other op (a loop's own event holds
    its body's ops)."""
    order = sorted(evs, key=lambda x: (x[1], -x[2]))
    holds = [False] * len(order)
    stack: List[int] = []
    for i, (_, s, _) in enumerate(order):
        while stack and order[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            holds[stack[-1]] = True
        stack.append(i)
    return [ev for ev, h in zip(order, holds) if not h]


def collective_intervals(evs: Sequence[Event]) -> List[Interval]:
    """Each collective of one device's op line, from its start to its end.
    An asynchronous one shows as two ops, ``<collective>-start`` and
    ``<collective>-done``: it runs from the start op's start to the end of
    the done op that follows it. A done's HLO text names its start as its
    operand, except the TPU's ``async-collective-done.N`` fusion, whose
    start is ``async-collective-start.N``."""
    out: List[Interval] = []
    started: Dict[str, float] = {}
    for name, s, e in sorted(evs, key=lambda x: x[1]):
        op = op_name(name)
        if not is_collective(op):
            continue
        if op.endswith("-start"):
            started[_instruction(name)] = s
        elif op.endswith("-done"):
            m = _START_OPERAND.search(name.split("=", 1)[-1])
            key = m.group(1) if m else \
                _instruction(name).replace("-done", "-start")
            out.append((started.pop(key, s), e))
        else:
            out.append((s, e))
    return out


def collective_op_seconds(tr: Trace, within: Sequence[Interval],
                          kinds: Sequence[str] = COLLECTIVES
                          ) -> Optional[float]:
    """Device time of the collective ops of those ``kinds`` themselves: each
    synchronous op, and each ``-start`` and ``-done`` op of an asynchronous
    one, not the time between them. Inside ``within``, mean over devices;
    None where no device ran such an op there."""
    within = union(within)
    tot = 0.0
    found = False
    for evs in tr.devices.values():
        own = intersect(union((s, e) for name, s, e in evs
                              if any(k in op_name(name) for k in kinds)),
                        within)
        found = found or bool(own)
        tot += length(own)
    return tot / len(tr.devices) if found else None


def collective_exposed_seconds(tr: Trace, within: Sequence[Interval]
                               ) -> Optional[float]:
    """Device time in which a collective was in flight (from its start to
    its end, ``collective_intervals``) and no other op ran on the same
    device, inside ``within``, mean over devices; None where no device ran
    a collective there."""
    within = union(within)
    exposed = 0.0
    found = False
    for evs in tr.devices.values():
        coll = intersect(union(collective_intervals(evs)), within)
        found = found or bool(coll)
        other = union((s, e) for name, s, e in leaf_ops(evs)
                      if not is_collective(op_name(name)))
        exposed += length(coll) - length(intersect(coll, other))
    return exposed / len(tr.devices) if found else None


def _instruction(name: str) -> str:
    """An op's instruction name with its number: 'all-gather-start.3'."""
    m = _HLO_NAME.match(name)
    return m.group(1) if m else name


def op_name(name: str) -> str:
    """An op's instruction name without its number: 'fusion.12' and
    '%fusion.12 = bf16[...] fusion(...)' (the TPU trace names an op by its
    HLO text) both give 'fusion'."""
    m = _HLO_NAME.match(name)
    return _SUFFIX.sub("", m.group(1) if m else name)


def step_intervals(tr: Trace) -> List[Interval]:
    """Host time inside the window's training steps: each train_span call
    minus the batch build and the save inside it."""
    calls = union(tr.spans("bench.train_span"))
    inner = union(tr.spans("bench.input") + tr.spans("bench.save"))
    return subtract(calls, inner)


def self_times(evs: Sequence[Event]) -> List[Tuple[str, float, float,
                                                     float]]:
    """(name, start, end, self seconds) of each op: its duration less the
    ops nested inside it on the same line (a loop's body runs inside the
    loop's own event)."""
    order = sorted(range(len(evs)), key=lambda i: (evs[i][1], -evs[i][2]))
    self_s = [e - s for _, s, e in evs]
    stack: List[int] = []
    for i in order:
        _, s, e = evs[i]
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            self_s[stack[-1]] -= min(e, evs[stack[-1]][2]) - s
        stack.append(i)
    return [(n, s, e, self_s[i]) for i, (n, s, e) in enumerate(evs)]


def top_ops(tr: Trace, window: Interval, k: int = 10) -> List[list]:
    """The ``k`` op names with the most self time (nested ops taken out) in
    the window, mean over devices."""
    tot: Dict[str, float] = {}
    lo, hi = window
    for evs in tr.devices.values():
        for name, s, e, own in self_times(evs):
            if lo <= s and e <= hi:
                key = op_name(name)
                tot[key] = tot.get(key, 0.0) + own
    n = max(len(tr.devices), 1)
    return [[name, sec / n] for name, sec in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_by_host(tr: Trace, window: Interval, k: int = 10) -> List[list]:
    """Idle device time in the window (mean over devices), summed by what
    the host was doing: each idle stretch is cut at the host spans' edges
    and each piece goes to the innermost span around it ('bench.none'
    where the harness had no span open)."""
    spans = [(n, s, e) for n, s, e in tr.host if n != "bench.window"]
    edges = sorted({t for _, s, e in spans for t in (s, e)})
    labels = []                       # innermost span between two edges
    for a, b in zip(edges, edges[1:]):
        mid = 0.5 * (a + b)
        inner = [(e - s, n) for n, s, e in spans if s <= mid < e]
        labels.append(min(inner)[1] if inner else "bench.none")
    tot: Dict[str, float] = {}
    for evs in tr.devices.values():
        busy_u = union(clip(((s, e) for _, s, e in evs), window))
        for gs, ge in subtract([window], busy_u):
            lo, hi = bisect.bisect_right(edges, gs), bisect.bisect_left(
                edges, ge)
            cuts = [gs] + edges[lo:hi] + [ge]
            for a, b in zip(cuts, cuts[1:]):
                i = bisect.bisect_right(edges, 0.5 * (a + b)) - 1
                name = labels[i] if 0 <= i < len(labels) else "bench.none"
                tot[name] = tot.get(name, 0.0) + (b - a)
    n = max(len(tr.devices), 1)
    return [[name, sec / n] for name, sec in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]
