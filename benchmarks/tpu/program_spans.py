"""The program's own telemetry spans, as the benchmark reads them.

``repro.telemetry`` records each span with its ``id``, the ``parent`` id
of the span open around it, its start ``t0_s`` and its ``dur_s``, and
while the run is profiled it holds a host annotation ``elsa.<name>``
open over the span. The first functions here read the span records of
one collector; the last two give the device's idle time of a trace to
those annotations. All of them work on plain lists, so a test can feed
them hand-made records and events. Records without an ``id`` (a program
that records none) are left out.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Sequence, Tuple

import trace_reduce as tr

PREFIX = "elsa."
HOST_PLANE = "/host:CPU"
UNCOVERED = "host:none"


def records(tel) -> List[Dict]:
    """Every span record of a collector: its closed rounds' and the
    open round's, with ids."""
    if tel is None:
        return []
    recs = [s for r in tel.rounds for s in r["spans"]] + list(tel._spans)
    return [s for s in recs if "id" in s]


def self_seconds(recs, name: str) -> List[float]:
    """Self time of each span called ``name``: its duration less the
    durations of its children (they run one after another on the
    thread that opened it)."""
    children: Dict[int, float] = {}
    for s in recs:
        if s.get("parent") is not None:
            children[s["parent"]] = children.get(s["parent"], 0.0) \
                + s["dur_s"]
    return [s["dur_s"] - children.get(s["id"], 0.0) for s in recs
            if s["name"] == name]


def outside(recs, names: Sequence[str], ancestor: str) -> List[float]:
    """Durations of the spans called one of ``names`` that have no
    ancestor called ``ancestor``."""
    by_id = {s["id"]: s for s in recs}

    def under(s) -> bool:
        p = by_id.get(s.get("parent"))
        while p is not None:
            if p["name"] == ancestor:
                return True
            p = by_id.get(p.get("parent"))
        return False
    return [s["dur_s"] for s in recs if s["name"] in names and not under(s)]


def _overlap(a: List[Tuple[float, float]],
             b: List[Tuple[float, float]]) -> float:
    """Length of the intersection of two disjoint sorted interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def idle_inside(events, names: Sequence[str], lo: float,
                hi: float) -> Optional[float]:
    """Seconds in which the first device plane ran no operation, inside
    the union of the intervals of the host events called one of
    ``names``, within [lo, hi]; None without a device plane or such an
    event."""
    planes = tr.device_planes(events)
    host = tr.union(tr.clip(
        [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in events
         if e["plane"] == HOST_PLANE and e["name"] in names], lo, hi))
    if not planes or not host:
        return None
    busy = tr.busy_intervals(events, planes[0], lo, hi)
    return (sum(b - a for a, b in host) - _overlap(host, busy)) / 1e9


def idle_by_span(events, lo: float, hi: float,
                 prefix: str = PREFIX) -> List[list]:
    """[[name, seconds], ...], largest first: each instant of [lo, hi]
    in which the first device plane ran no operation, given to the
    innermost host annotation whose name starts with ``prefix`` open at
    that instant, or to ``"host:none"`` where none is. The seconds sum
    to the window's idle time."""
    planes = tr.device_planes(events)
    if not planes:
        return []
    busy = tr.busy_intervals(events, planes[0], lo, hi)
    starts = [a for a, _ in busy]
    cum = [0.0]
    for a, b in busy:
        cum.append(cum[-1] + b - a)

    def busy_until(t: float) -> float:
        k = bisect.bisect_right(starts, t) - 1
        return 0.0 if k < 0 else cum[k] + min(t, busy[k][1]) - busy[k][0]

    idle: Dict[str, float] = {}

    def give(name: str, a: float, b: float) -> None:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            idle[name] = idle.get(name, 0.0) + (b - a) \
                - (busy_until(b) - busy_until(a))

    spans = sorted(((e["start_ns"], e["start_ns"] + e["dur_ns"], e["name"])
                    for e in events if e["plane"] == HOST_PLANE
                    and e["name"].startswith(prefix)),
                   key=lambda s: (s[0], -s[1]))
    # annotations of one thread nest: the innermost open one is the
    # last opened that has not ended
    stack: List[Tuple[float, str]] = []
    cur = lo
    for start, end, name in spans:
        while stack and stack[-1][0] <= start:
            top_end, top = stack.pop()
            give(top, cur, top_end)
            cur = max(cur, top_end)
        give(stack[-1][1] if stack else UNCOVERED, cur, start)
        cur = max(cur, start)
        stack.append((end, name))
    while stack:
        top_end, top = stack.pop()
        give(top, cur, top_end)
        cur = max(cur, top_end)
    give(UNCOVERED, cur, hi)
    return [[name, ns / 1e9] for name, ns in
            sorted(idle.items(), key=lambda kv: -kv[1])]
