"""Federation telemetry: structured metrics + round-phase tracing.

Process-wide observability with a hard zero-overhead-when-disabled
contract (docs/observability.md): every module-level helper here checks
one ``None`` and returns, and the instrumented layers never allocate,
sync, or branch on telemetry state in a way that can perturb the math —
a telemetry-enabled run produces bit-identical histories and event
traces to a disabled one (``tests/test_telemetry.py``).

Usage::

    from repro import telemetry as tm

    tm.enable(meta={"bench": "fed_round"})
    fed.run("elsa", global_rounds=4)             # layers self-instrument
    tm.export("runs/telemetry.jsonl")            # per-round JSONL+summary
    tm.disable()

or scoped::

    with tm.session(jsonl="runs/telemetry.jsonl"):
        fed.run(...)

Instrumented layers (all no-ops while disabled):

- ``repro.runtime`` — every :meth:`EventTrace.log` record bridges to a
  ``runtime.events{kind=...}`` counter (metrics can never disagree with
  the determinism trace), schedulers record round-lifecycle spans
  (``dispatch``/``local_steps``/``uplink``/``edge_agg``/``cloud_agg``/
  ``eval``) and per-phase simulated seconds + comm bytes;
- ``repro.federation.simulation`` — the round loop's lifecycle spans,
  leaf spans of the host work inside profiling (``profile.warmup``/
  ``probe``/``kl``/``cluster``), the batch draws (``data.draw``) and the
  global delta (``agg.delta``), and ``host.syncs{site=...}``, the host's
  reads of device values;
- ``repro.federation.engine`` — spans of each local round's stacking,
  dispatch, loss fetch and unstacking, jit compiles per (split,
  bucket), compile-vs-cached dispatch wall time, cohort/phantom sizes,
  donated-buffer placement;
- ``repro.core.screening`` — verdict counters by reason + trust-ledger
  gauge snapshots;
- ``repro.checkpoint`` — save/restore latency and snapshot bytes;
- ``repro.serving`` — request-latency histogram, adapter hot-swaps.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, Optional, Sequence

from repro.telemetry.collector import (DEFAULT_TIME_BUCKETS, NULL_SPAN,
                                       SCHEMA_VERSION, Histogram, NullSpan,
                                       Telemetry, flat_key)
from repro.telemetry.export import export_jsonl, read_jsonl, summarize
from repro.telemetry.sinks import JsonlSink, Sink, finalize_sink

__all__ = [
    "DEFAULT_TIME_BUCKETS", "SCHEMA_VERSION", "Histogram", "NullSpan",
    "Telemetry", "flat_key", "export_jsonl", "read_jsonl", "summarize",
    "Sink", "JsonlSink", "finalize_sink",
    "enabled", "enable", "disable", "get", "inc", "set_gauge", "observe",
    "span", "record_span", "end_round", "export", "summary", "session",
]

_active: Optional[Telemetry] = None


def enabled() -> bool:
    return _active is not None


def get() -> Optional[Telemetry]:
    """The live collector, or None while disabled."""
    return _active


def enable(meta: Optional[Dict[str, Any]] = None, sink: Optional[Sink] = None,
           retain_rounds: Optional[int] = None) -> Telemetry:
    """Start a fresh collector (replacing any previous one).

    ``sink`` streams every round record as it closes
    (:mod:`repro.telemetry.sinks`); ``retain_rounds`` bounds the
    in-memory round window.  Both default off — the in-memory path is
    unchanged.
    """
    global _active
    _active = Telemetry(meta, sink=sink, retain_rounds=retain_rounds)
    return _active


def disable() -> None:
    """Stop collecting; a streaming sink is flushed (trailing partial
    round + run summary) and closed on the way out."""
    global _active
    if _active is not None:
        finalize_sink(_active)
    _active = None


# -- forwarding helpers (each is one None-check when disabled) -------------

def inc(name: str, value: float = 1.0, **labels: Any) -> None:
    t = _active
    if t is not None:
        t.inc(name, value, **labels)


def set_gauge(name: str, value: float, **labels: Any) -> None:
    t = _active
    if t is not None:
        t.set_gauge(name, value, **labels)


def observe(name: str, value: float,
            buckets: Optional[Sequence[float]] = None,
            **labels: Any) -> None:
    t = _active
    if t is not None:
        t.observe(name, value, buckets=buckets, **labels)


def span(name: str, **attrs: Any):
    t = _active
    return t.span(name, **attrs) if t is not None else NULL_SPAN


def record_span(name: str, dur_s: float = 0.0, **attrs: Any) -> None:
    t = _active
    if t is not None:
        t.record_span(name, dur_s=dur_s, **attrs)


def end_round(round_idx: int, sim_time_s: Optional[float] = None) -> None:
    t = _active
    if t is not None:
        t.end_round(round_idx, sim_time_s=sim_time_s)


def export(path: str) -> Optional[str]:
    """Write the live collector's JSONL; None while disabled."""
    t = _active
    return export_jsonl(t, path) if t is not None else None


def summary() -> Optional[Dict[str, Any]]:
    t = _active
    return summarize(t) if t is not None else None


@contextlib.contextmanager
def session(meta: Optional[Dict[str, Any]] = None,
            jsonl: Optional[str] = None, sink: Optional[Sink] = None,
            retain_rounds: Optional[int] = None):
    """Enable for a block; export to ``jsonl`` (if given) on the way
    out, then restore the previous collector (sessions nest).  A
    ``sink`` streams rounds live instead and is flushed + closed on
    exit (``retain_rounds`` bounds the in-memory window meanwhile)."""
    global _active
    prev = _active
    tel = Telemetry(meta, sink=sink, retain_rounds=retain_rounds)
    _active = tel
    try:
        yield tel
    finally:
        if jsonl is not None:
            export_jsonl(tel, jsonl)
        finalize_sink(tel)
        _active = prev
