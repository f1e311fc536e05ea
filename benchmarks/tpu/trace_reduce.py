"""Reduction of a JAX profiler trace to device metrics.

``load_events`` reads the ``.xplane.pb`` file the profiler writes and
keeps a flat event list. Every other function works on that list, so a
test can feed a small recorded list instead of a trace file. An event
is ``{"plane", "line", "name", "start_ns", "dur_ns"}``.

Device planes are named ``/device:<platform>:<n>``. On each, the ``XLA
Ops`` line holds one event per operation and the ``XLA Modules`` line
one per program run. The host's annotations (``TraceAnnotation``) are
events of the ``/host:CPU`` plane, on the line of the thread that opened
them.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# control-flow operations hold the operations of their bodies
CONTAINERS = ("while", "conditional", "call")


def short_name(name: str) -> str:
    """``%fusion.12 = (f32[...]) fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def find_trace(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load_events(path: str, host_prefixes: Sequence[str] = ()) -> List[Dict]:
    """Device events of every device plane, and the host events whose
    name starts with one of ``host_prefixes``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        host = plane.name == "/host:CPU"
        if not (device or host):
            continue
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if host and not ev.name.startswith(tuple(host_prefixes)):
                    continue
                name = short_name(ev.name) if device else ev.name
                out.append({"plane": plane.name, "line": line.name,
                            "name": name, "start_ns": float(ev.start_ns),
                            "dur_ns": float(ev.duration_ns)})
    return out


def device_planes(events) -> List[str]:
    return sorted({e["plane"] for e in events
                   if e["plane"].startswith("/device:")})


def _ops(events, plane):
    return [e for e in events if e["plane"] == plane and e["line"] == OPS_LINE]


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float,
                                                                   float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def busy_intervals(events, plane, lo: float, hi: float):
    """Disjoint intervals inside [lo, hi] in which some operation ran."""
    ops = [(e["start_ns"], e["start_ns"] + e["dur_ns"])
           for e in _ops(events, plane)]
    return union(clip(ops, lo, hi))


def busy_seconds(events, lo: float, hi: float) -> float:
    """Busy time inside [lo, hi], averaged over the device planes."""
    planes = device_planes(events)
    if not planes:
        return 0.0
    tot = sum(b - a for p in planes
              for a, b in busy_intervals(events, p, lo, hi))
    return tot / len(planes) / 1e9


def module_seconds(events, prefix: str, lo: float, hi: float) -> float:
    """Device time of the programs whose name starts with ``prefix``
    inside [lo, hi], summed over runs, averaged over device planes."""
    planes = device_planes(events)
    if not planes:
        return 0.0
    tot = 0.0
    for p in planes:
        ivs = [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in events
               if e["plane"] == p and e["line"] == MODULES_LINE
               and e["name"].startswith(prefix)]
        tot += sum(b - a for a, b in clip(ivs, lo, hi))
    return tot / len(planes) / 1e9


def module_count(events, prefix: str, lo: float, hi: float) -> int:
    planes = device_planes(events)
    if not planes:
        return 0
    return sum(1 for e in events
               if e["plane"] == planes[0] and e["line"] == MODULES_LINE
               and e["name"].startswith(prefix)
               and lo <= e["start_ns"] <= hi)


def top_ops(events, lo: float, hi: float, k: int = 10):
    """[[name, seconds], ...] of the operations that took most device
    time inside [lo, hi], averaged over device planes; control-flow
    operations, which hold others, are left out."""
    planes = device_planes(events)
    tot: Dict[str, float] = {}
    for p in planes:
        for e in _ops(events, p):
            if e["name"].startswith(CONTAINERS):
                continue
            ivs = clip([(e["start_ns"], e["start_ns"] + e["dur_ns"])], lo, hi)
            if ivs:
                tot[e["name"]] = tot.get(e["name"], 0.0) + \
                    (ivs[0][1] - ivs[0][0])
    n = max(len(planes), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / n / 1e9] for name, ns in best]


def _open_annotation(host, t: float) -> Optional[str]:
    """Innermost (shortest) host annotation open at time ``t``."""
    best = None
    for e in host:
        if e["start_ns"] <= t <= e["start_ns"] + e["dur_ns"]:
            if best is None or e["dur_ns"] < best["dur_ns"]:
                best = e
    return None if best is None else best["name"]


def idle_gaps(events, host_prefixes: Sequence[str], lo: float, hi: float,
              k: int = 10):
    """[[name, seconds], ...] of the longest idle gaps of the first
    device inside [lo, hi], each named by the host annotation open at
    the gap's midpoint (``"host:none"`` when none is)."""
    planes = device_planes(events)
    if not planes:
        return []
    busy = busy_intervals(events, planes[0], lo, hi)
    gaps, cur = [], lo
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    host = [e for e in events if e["plane"] == "/host:CPU"
            and e["name"].startswith(tuple(host_prefixes))]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:k]:
        name = _open_annotation(host, 0.5 * (a + b)) or "host:none"
        out.append([name, (b - a) / 1e9])
    return out


def span_of(events, name: str) -> Optional[Tuple[float, float]]:
    """(start, end) of the first host annotation called ``name``."""
    for e in events:
        if e["plane"] == "/host:CPU" and e["name"] == name:
            return e["start_ns"], e["start_ns"] + e["dur_ns"]
    return None
