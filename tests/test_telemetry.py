"""Federation telemetry: registry semantics, the zero-overhead-disabled
contract, trace<->metrics consistency, and the compile-churn gate.

The two load-bearing guarantees (docs/observability.md):

- **bit-inertness** — a telemetry-enabled run produces identical
  histories and event traces to a disabled one (telemetry is host-side
  bookkeeping only, it never touches device arrays or RNG);
- **trace<->metrics agreement** — ``runtime.events{kind=...}`` counters
  are bridged from :meth:`EventTrace.log` itself, so they must equal
  ``trace.summary()`` exactly, faults and churn included.
"""
import json
import os
import time

import jax
import numpy as np
import pytest

from repro import telemetry as tm
from repro.federation.simulation import FedConfig, Federation
from repro.federation.topology import make_churn_trace, make_fault_trace
from repro.runtime import RuntimeConfig
from repro.runtime.trace import EventTrace

SMALL_KW = dict(n_clients=6, n_edges=2, alpha=0.2, poisoned=(4,),
                total_examples=600, probe_q=8, local_warmup_steps=2,
                lr=2e-2, layers=4, t_rounds=1, batch_size=16, seed=0)

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(autouse=True)
def _telemetry_off():
    """Tests must not leak an enabled collector into each other (or
    into the rest of the suite)."""
    tm.disable()
    yield
    tm.disable()


# ---------------------------------------------------------------------------
# registry semantics (no model, fast)
# ---------------------------------------------------------------------------

def test_flat_key_sorts_labels():
    assert tm.flat_key("a", {}) == "a"
    assert tm.flat_key("a", {"b": 1, "a": 2}) == "a{a=2,b=1}"


def test_counters_gauges_histograms():
    tel = tm.Telemetry()
    tel.inc("c", 2, kind="x")
    tel.inc("c", 3, kind="x")
    tel.inc("c", 1, kind="y")
    assert tel.counter("c", kind="x") == 5
    assert tel.counters_by_name("c") == {"c{kind=x}": 5.0, "c{kind=y}": 1.0}
    tel.set_gauge("g", 1.0)
    tel.set_gauge("g", 7.0)
    assert tel.gauge("g") == 7.0
    tel.observe("h", 0.002)
    tel.observe("h", 50.0)          # beyond the last bound -> overflow
    h = tel.histograms["h"]
    assert h.count == 2 and h.max == 50.0 and h.counts[-1] == 1


def test_histogram_rejects_unsorted_buckets():
    with pytest.raises(ValueError):
        tm.Histogram((1.0, 0.5))


def test_round_records_hold_counter_deltas():
    tel = tm.Telemetry()
    tel.inc("c", 5)
    tel.end_round(0)
    tel.inc("c", 2)
    with tel.span("uplink", edge=1) as sp:
        sp.set(sim_s=3.0)
    tel.end_round(1, sim_time_s=10.0)
    r0, r1 = tel.rounds
    assert r0["counters"] == {"c": 5.0} and r1["counters"] == {"c": 2.0}
    assert r1["sim_time_s"] == 10.0
    assert r1["spans"][0]["name"] == "uplink"
    assert r1["spans"][0]["attrs"]["sim_s"] == 3.0
    assert tel.counter("c") == 7                    # cumulative unharmed


def test_disabled_module_helpers_are_noops():
    assert not tm.enabled() and tm.get() is None
    tm.inc("c")
    tm.set_gauge("g", 1.0)
    tm.observe("h", 1.0)
    tm.end_round(0)
    assert tm.export("/nonexistent/should-not-write") is None
    assert tm.summary() is None
    sp = tm.span("x")
    assert isinstance(sp, tm.NullSpan)
    with sp as s:
        s.set(anything=1)           # still a no-op


def test_span_records_nest_with_ids_parents_and_starts():
    """Nested and sibling spans, a body that raises and a simulated-
    clock record: ids are unique, each parent is the span open around
    it, starts and ends nest, and self time is duration less children."""
    from repro.analysis.telemetry_report import child_seconds
    tel = tm.Telemetry()
    with tel.span("a"):
        with tel.span("b"):
            time.sleep(0.002)
        with pytest.raises(ValueError):
            with tel.span("c"):
                raise ValueError("body fails")
        tel.record_span("sim", sim_s=1.0)
    with tel.span("d"):
        pass
    recs = {s["name"]: s for s in tel._spans}
    a, b, c, d, sim = (recs[k] for k in "a b c d sim".split())
    assert len({s["id"] for s in tel._spans}) == 5
    assert a["parent"] is None and d["parent"] is None
    assert b["parent"] == c["parent"] == sim["parent"] == a["id"]
    assert sim["dur_s"] == 0.0 and sim["attrs"] == {"sim_s": 1.0}
    end = lambda s: s["t0_s"] + s["dur_s"]
    assert a["t0_s"] <= b["t0_s"] and end(b) <= c["t0_s"]
    assert end(c) <= sim["t0_s"] <= end(a) <= d["t0_s"]
    assert tel._open_ids == []          # the raising span closed too
    kids = child_seconds([{"spans": tel._spans}])
    assert kids == {"a": b["dur_s"] + c["dur_s"]}
    assert 0.0 <= a["dur_s"] - kids["a"] < a["dur_s"]


def test_spans_hold_a_trace_annotation_only_while_enabled(monkeypatch):
    opened = []

    class Recorder:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    def refuse(name):
        raise AssertionError(f"profiler called while disabled: {name}")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    assert tm.span("x") is tm.NULL_SPAN
    with tm.span("x"):
        tm.inc("host.syncs", 1, site="x")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    with tm.session():
        with tm.span("outer"):
            with tm.span("inner"):
                pass
    assert opened == ["elsa.outer", "elsa.inner"]


def test_session_nests_and_restores():
    outer = tm.enable({"level": "outer"})
    with tm.session({"level": "inner"}) as inner:
        assert tm.get() is inner
        tm.inc("c")
    assert tm.get() is outer
    assert inner.counter("c") == 1 and outer.counter("c") == 0


def test_export_read_roundtrip(tmp_path):
    with tm.session({"m": 1}) as tel:
        tel.inc("c", 4)
        tel.record_span("uplink", dur_s=0.5, sim_s=2.0)
        tel.end_round(0)
        path = tm.export_jsonl(tel, str(tmp_path / "t.jsonl"))
    d = tm.read_jsonl(path)
    assert d["meta"]["meta"] == {"m": 1}
    assert d["summary"]["counters"] == {"c": 4.0}
    assert d["summary"]["spans"]["uplink"] == {"count": 1, "wall_s": 0.5,
                                               "sim_s": 2.0}
    # killed run: strip the summary line, read_jsonl rebuilds it from
    # the per-round deltas
    lines = open(path).read().strip().split("\n")
    (tmp_path / "cut.jsonl").write_text("\n".join(lines[:-1]) + "\n")
    d2 = tm.read_jsonl(str(tmp_path / "cut.jsonl"))
    assert d2["summary"]["counters"] == {"c": 4.0}
    assert d2["summary"]["spans"]["uplink"]["sim_s"] == 2.0


def test_flush_pending_folds_leftovers(tmp_path):
    with tm.session() as tel:
        tel.inc("c", 1)             # never end_round-ed
        path = tm.export_jsonl(tel, str(tmp_path / "t.jsonl"))
    d = tm.read_jsonl(path)
    assert len(d["rounds"]) == 1 and d["rounds"][0]["round"] is None
    assert d["summary"]["counters"] == {"c": 1.0}


# ---------------------------------------------------------------------------
# EventTrace per-kind index (satellite: O(1) of_kind/count)
# ---------------------------------------------------------------------------

def test_trace_index_matches_linear_scan():
    tr = EventTrace()
    for i in range(20):
        tr.log(float(i), "a" if i % 3 else "b", client=i, round=i)
    assert tr.count("a") == sum(1 for r in tr.records if r[1] == "a")
    assert tr.of_kind("b") == [r for r in tr.records if r[1] == "b"]
    assert tr.of_kind("missing") == [] and tr.count("missing") == 0
    assert tr.summary() == {"b": 7, "a": 13}
    # index rows are the same tuples as the flat log, not copies
    assert tr.of_kind("a")[0] is tr.records[1]


def test_trace_records_setter_rebuilds_index():
    tr = EventTrace()
    tr.log(0.0, "a")
    src = EventTrace()
    src.log(1.0, "b")
    src.log(2.0, "b")
    tr.records = list(src.records)          # checkpoint-resume shape
    assert tr.count("a") == 0 and tr.count("b") == 2
    assert tr == src
    tr.log(3.0, "b")
    assert tr.count("b") == 3 and len(tr) == 3


# ---------------------------------------------------------------------------
# end-to-end: bit-inertness + trace<->metrics agreement
# ---------------------------------------------------------------------------

def _sync_run(enabled: bool):
    tel = tm.enable() if enabled else None
    try:
        fed = Federation(FedConfig(**SMALL_KW, screen=True))
        faults = make_fault_trace(SMALL_KW["n_clients"], faulty_frac=0.5,
                                  crash_rate=0.2, corrupt_rate=0.7,
                                  corrupt_modes=("nan",), seed=3)
        churn = make_churn_trace(SMALL_KW["n_clients"], 1e6,
                                 churn_frac=0.5, seed=7)
        h = fed.run("elsa-nocluster", global_rounds=2, steps_per_round=2,
                    runtime=RuntimeConfig(policy="sync", faults=faults,
                                          churn=churn))
    finally:
        tm.disable()
    return h, tel


@pytest.fixture(scope="module")
def sync_runs():
    """One telemetry-off + one telemetry-on seeded sync run with faults
    and churn, shared by the parity/counter/span/verdict tests."""
    h_off, _ = _sync_run(enabled=False)
    h_on, tel = _sync_run(enabled=True)
    return h_off, h_on, tel


def test_enabled_run_is_bit_inert_and_counts_match_trace(sync_runs):
    h_off, h_on, tel = sync_runs
    # acceptance: identical histories and traces either way
    assert h_on["accuracy"] == h_off["accuracy"]
    assert h_on["loss"] == h_off["loss"]
    assert h_on["time"] == h_off["time"]
    assert h_on["trace"] == h_off["trace"]
    # acceptance: every event kind's counter equals the trace exactly
    summary = h_on["trace"].summary()
    assert summary  # the run must actually have produced events
    for kind, n in summary.items():
        assert tel.counter("runtime.events", kind=kind) == n, kind
    # and no counter series invents event kinds the trace lacks
    bridged = tel.counters_by_name("runtime.events")
    assert len(bridged) == len(summary)
    # per-phase simulated seconds and wire bytes accumulated
    assert tel.counter("runtime.sim.compute_s") > 0
    assert tel.counter("runtime.uplink_bytes") > 0
    # one round record per global round, stamped with the simulated clock
    assert [r["round"] for r in tel.rounds] == [0, 1]
    assert tel.rounds[-1]["sim_time_s"] == pytest.approx(h_on["time"][-1])


def test_round_lifecycle_spans_recorded(sync_runs):
    _, _, tel = sync_runs
    names = {s["name"] for rec in tel.rounds for s in rec["spans"]}
    assert {"dispatch", "local_steps", "uplink", "edge_agg", "cloud_agg",
            "eval"} <= names
    uplinks = [s for rec in tel.rounds for s in rec["spans"]
               if s["name"] == "uplink"]
    # uplink spans carry the edge-round's simulated barrier wait
    assert all("sim_s" in s["attrs"] for s in uplinks)
    assert any(s["attrs"]["sim_s"] > 0 for s in uplinks)


def test_screening_metrics_follow_verdicts(sync_runs):
    _, _, tel = sync_runs
    verdicts = tel.counters_by_name("screening.verdicts")
    assert verdicts, "screened run must record verdict counters"
    assert tel.counter("screening.verdicts", verdict="nonfinite") > 0
    assert 0.0 < tel.gauge("screening.trust_mean") <= 1.0


# ---------------------------------------------------------------------------
# the plain round loop's nested spans and host-sync counts
# ---------------------------------------------------------------------------

LEAVES = {"profile.warmup": "profile", "profile.probe": "profile",
          "profile.kl": "profile", "profile.cluster": "profile",
          "agg.delta": "cloud_agg"}
ENGINE = ("data.draw", "engine.stack", "engine.dispatch", "engine.fetch",
          "engine.unstack")


def test_round_loop_leaf_spans_and_syncs():
    """A batched elsa run records every leaf span under its parent, one
    ``engine.fetch`` sync per engine call (profiling's warm-up
    included), and the same history as with telemetry off."""
    fed = Federation(FedConfig(**SMALL_KW), backend="batched")
    kw = dict(global_rounds=1, steps_per_round=1)
    h_off = fed.run("elsa", **kw)
    with tm.session() as tel:
        h_on = fed.run("elsa", **kw)
    for k in ("accuracy", "loss", "delta"):
        assert h_on[k] == h_off[k], k
    recs = [s for r in tel.rounds for s in r["spans"]] + tel._spans
    by_id = {s["id"]: s for s in recs}
    parent = lambda s: by_id[s["parent"]]["name"]
    for name, want in LEAVES.items():
        got = [parent(s) for s in recs if s["name"] == name]
        assert got and set(got) == {want}, name
    # the engine's leaves sit under local_steps or profiling's warm-up
    for name in ENGINE:
        got = {parent(s) for s in recs if s["name"] == name}
        assert got == {"local_steps", "profile.warmup"}, name
    fetches = [s for s in recs if s["name"] == "engine.fetch"]
    assert any(parent(s) == "profile.warmup" for s in fetches)
    assert tel.counter("host.syncs", site="engine.fetch") == len(fetches)
    n = SMALL_KW["n_clients"]
    assert tel.counter("host.syncs", site="profile.probe") == n
    assert tel.counter("host.syncs", site="profile.kl") == 1
    assert tel.counter("host.syncs", site="eval") == len(h_on["round"])
    assert tel.counter("host.syncs", site="agg.delta") == len(
        jax.tree_util.tree_leaves(fed.lora0))


# ---------------------------------------------------------------------------
# engine compile accounting (satellite: recompile-churn regression gate)
# ---------------------------------------------------------------------------

def test_deadline_scheduler_compiles_once_per_split_bucket():
    """Varying deadline-window cohorts must reuse compiled executables:
    exactly one jit compile per (split, ladder-bucket) — recompile
    churn would show as a counter exceeding its cache entry."""
    tel = tm.enable()
    try:
        fed = Federation(FedConfig(**SMALL_KW))
        churn = make_churn_trace(SMALL_KW["n_clients"], 1e6,
                                 churn_frac=0.5, seed=7)
        fed.run("elsa-nocluster", global_rounds=3, steps_per_round=2,
                runtime=RuntimeConfig(policy="deadline", churn=churn,
                                      deadline_quantile=0.5))
        compiles = tel.counters_by_name("engine.jit_compiles")
        assert compiles, "run must have compiled at least one round fn"
        # one compile per (split, bucket) series, never a recompile
        assert all(v == 1 for v in compiles.values()), compiles
        # counters agree with the engine's own jit cache sizes: total
        # compiles == total specialized executables
        cache = fed.engine.compile_cache_sizes()
        assert sum(compiles.values()) == sum(cache.values())
        assert tel.counter("engine.clients") > 0
        disp = tel.histograms.get("engine.dispatch_s{compiled=True}")
        assert disp is not None and disp.count == sum(cache.values())
    finally:
        tm.disable()


# ---------------------------------------------------------------------------
# checkpoint + report surfaces
# ---------------------------------------------------------------------------

def test_checkpoint_metrics(tmp_path):
    from repro.checkpoint import CheckpointConfig
    from repro.checkpoint.federation import latest_checkpoint, load_state
    tel = tm.enable()
    try:
        fed = Federation(FedConfig(**SMALL_KW))
        fed.run("elsa-nocluster", global_rounds=1, steps_per_round=2,
                runtime=RuntimeConfig(policy="sync"),
                checkpoint=CheckpointConfig(dir=str(tmp_path), every=1))
        load_state(latest_checkpoint(str(tmp_path)))
    finally:
        tm.disable()
    assert tel.counter("checkpoint.saves") == 1
    assert tel.counter("checkpoint.restores") == 1
    assert tel.counter("checkpoint.bytes_written") > 0
    assert tel.counter("checkpoint.bytes_read") \
        == tel.counter("checkpoint.bytes_written")
    assert tel.histograms["checkpoint.save_s"].count == 1


def test_serving_metrics_and_adapter_swap():
    from repro.configs import get_config
    from repro.serving import ServingEngine
    tel = tm.enable()
    try:
        eng = ServingEngine(get_config("qwen2.5-3b").reduced(),
                            batch_size=1, max_len=48, seed=0)
        eng.submit([1, 2, 3], max_new_tokens=3)
        eng.run_until_drained()
        eng.swap_adapter(eng.lora)
    finally:
        tm.disable()
    assert tel.counter("serving.requests") == 1
    assert tel.counter("serving.tokens") == 3
    assert tel.counter("serving.adapter_swaps") == 1
    assert tel.histograms["serving.request_s"].count == 1


def test_report_renders_committed_example():
    """Acceptance: the report CLI renders a per-phase breakdown from
    the committed example JSONL (a real screened sync run with
    corruption faults on the reduced federation)."""
    from repro.analysis.telemetry_report import render
    path = os.path.join(DATA, "telemetry_example.jsonl")
    d = tm.read_jsonl(path)
    out = render(d, show_rounds=True)
    # the per-phase table, in lifecycle order
    assert out.index("local_steps") < out.index("uplink") \
        < out.index("edge_agg") < out.index("cloud_agg")
    # simulated-cost and bytes breakdown
    assert "simulated cost" in out and "wire: uplink" in out
    # events, compile accounting, screening, histograms all surface
    assert "runtime events" in out and "jit compiles" in out
    assert "screening verdicts" in out and "histograms" in out
    # per-round table present with both closed rounds
    assert "round     sim_time" in out
    # counters in the committed file agree with its own trace bridge
    ev = {k: v for k, v in d["summary"]["counters"].items()
          if k.startswith("runtime.events")}
    assert sum(ev.values()) == sum(
        sum(r["counters"].get(k, 0) for k in ev) for r in d["rounds"])


def test_report_main_prints(capsys):
    import sys
    from repro.analysis import telemetry_report
    argv = sys.argv
    sys.argv = ["telemetry_report",
                os.path.join(DATA, "telemetry_example.jsonl")]
    try:
        telemetry_report.main()
    finally:
        sys.argv = argv
    out = capsys.readouterr().out
    assert "telemetry summary" in out and "phase" in out


# ---------------------------------------------------------------------------
# streaming sinks (docs/observability.md)
# ---------------------------------------------------------------------------

def _fill(tel, rounds, spans_per_round=2):
    for g in range(rounds):
        tel.inc("x.events", 3)
        for s in range(spans_per_round):
            tel.record_span("phase", dur_s=0.01, idx=s)
        tel.end_round(g)


def test_jsonl_sink_streams_rounds_live(tmp_path):
    """Every completed round is on disk the moment it closes (a killed
    run loses at most the open round), and close() appends the
    summary so the file parses like an exported JSONL."""
    p = str(tmp_path / "t.jsonl")
    sink = tm.JsonlSink(p)
    tel = tm.Telemetry({"bench": "sink"}, sink=sink)
    _fill(tel, 3)
    lines = [json.loads(l) for l in open(p)]
    assert lines[0]["type"] == "meta" and lines[0]["meta"] == {
        "bench": "sink"}
    assert [l["round"] for l in lines[1:]] == [0, 1, 2]
    tm.finalize_sink(tel)
    d = tm.read_jsonl(p)
    assert len(d["rounds"]) == 3
    assert d["summary"]["counters"]["x.events"] == 9
    sink.close()                                   # idempotent


def test_jsonl_sink_rotation_parts_parse_standalone(tmp_path):
    p = str(tmp_path / "t.jsonl")
    sink = tm.JsonlSink(p, rotate_bytes=600)
    tel = tm.Telemetry({"bench": "rot"}, sink=sink)
    _fill(tel, 12)
    tm.finalize_sink(tel)
    assert sink.parts >= 1
    rounds_seen = []
    for part in sink.rotated_paths() + [p]:
        d = tm.read_jsonl(part)                    # meta line re-stamped
        assert d["meta"]["meta"] == {"bench": "rot"}
        rounds_seen += [r["round"] for r in d["rounds"]]
    assert rounds_seen == list(range(12))          # nothing lost/reordered


def test_retain_rounds_bounds_memory_not_disk(tmp_path):
    p = str(tmp_path / "t.jsonl")
    tel = tm.Telemetry(sink=tm.JsonlSink(p), retain_rounds=2)
    _fill(tel, 8)
    assert [r["round"] for r in tel.rounds] == [6, 7]   # window trimmed
    tm.finalize_sink(tel)
    assert len(tm.read_jsonl(p)["rounds"]) == 8          # disk complete
    with pytest.raises(ValueError):
        tm.Telemetry(retain_rounds=-1)
    with pytest.raises(ValueError):
        tm.JsonlSink(str(tmp_path / "x.jsonl"), rotate_bytes=-1)


def test_session_with_sink_finalizes_on_exit(tmp_path):
    p = str(tmp_path / "s.jsonl")
    with tm.session(meta={"m": 1}, sink=tm.JsonlSink(p)) as tel:
        tel.inc("a")
        tel.end_round(0)
        tel.inc("b")                               # trailing partial round
    d = tm.read_jsonl(p)
    assert len(d["rounds"]) == 2 and d["rounds"][1]["round"] is None
    assert d["summary"]["counters"] == {"a": 1.0, "b": 1.0}
    assert tm.get() is None                        # previous state restored


def test_no_sink_path_is_unchanged():
    """The default in-memory collector never references a sink: runs
    without one keep the historical behavior bit-for-bit."""
    tel = tm.Telemetry()
    _fill(tel, 2)
    assert tel.sink is None and len(tel.rounds) == 2
    tm.finalize_sink(tel)                          # no-op without a sink
    assert len(tel.rounds) == 2                    # no flush side-effect
