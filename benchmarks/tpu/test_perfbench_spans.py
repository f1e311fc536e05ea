"""The program's spans as the benchmark reads them (``program_spans``):
their tree, the device idle given to them on a trace, and the readers
of the metrics that read them. Hand-made records and events, the
recorded ``testdata/trace_slice.json.gz``, and one CPU profile taken
around nested spans, which shows that the spans land on the trace's
clock."""
import gzip
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import pytest  # noqa: E402

import harness  # noqa: E402
import program_spans as ps  # noqa: E402
import trace_reduce as tr  # noqa: E402

DEV, HOST = "/device:TPU:0", ps.HOST_PLANE


def _op(a, b):
    return {"plane": DEV, "line": tr.OPS_LINE, "name": "fusion",
            "start_ns": float(a), "dur_ns": float(b - a)}


def _host(name, a, b):
    return {"plane": HOST, "line": "python", "name": name,
            "start_ns": float(a), "dur_ns": float(b - a)}


# A job over [0, 100] ns: elsa.job holds elsa.profile [10, 40] (with
# elsa.profile.kl [20, 30]) and elsa.edge_agg [60, 80]. The device runs
# [0, 15], [25, 35] and [70, 90].
EVENTS = [_op(0, 15), _op(25, 35), _op(70, 90),
          _host("elsa.job", 5, 95), _host("elsa.profile", 10, 40),
          _host("elsa.profile.kl", 20, 30), _host("elsa.edge_agg", 60, 80),
          _host("bench.job", 0, 100)]


def test_idle_by_span_gives_each_idle_instant_to_the_innermost_span():
    got = dict(ps.idle_by_span(EVENTS, 0, 100))
    # idle: [15, 25] [35, 70] [90, 100]
    want = {"elsa.profile": (20 - 15) + (40 - 35),
            "elsa.profile.kl": 25 - 20,
            "elsa.job": (60 - 40) + (95 - 90),
            "elsa.edge_agg": 70 - 60,
            ps.UNCOVERED: 100 - 95}
    assert got == pytest.approx({k: v / 1e9 for k, v in want.items()})
    assert sum(got.values()) == pytest.approx(
        1e-7 - tr.busy_seconds(EVENTS, 0, 100))
    # clipped to the window
    assert sum(dict(ps.idle_by_span(EVENTS, 20, 50)).values()) == \
        pytest.approx((25 - 20 + 50 - 35) / 1e9)


def test_idle_inside_named_spans():
    assert ps.idle_inside(EVENTS, ("elsa.profile",), 0, 100) == \
        pytest.approx(15e-9)
    # a union: the kl span lies inside profile
    assert ps.idle_inside(EVENTS, ("elsa.profile", "elsa.profile.kl",
                                   "elsa.edge_agg"), 0, 100) == \
        pytest.approx(25e-9)
    assert ps.idle_inside(EVENTS, ("elsa.eval",), 0, 100) is None
    assert ps.idle_inside([e for e in EVENTS if e["plane"] == HOST],
                          ("elsa.profile",), 0, 100) is None


def test_recorded_slice_idle_sums_to_the_window(recorded):
    """On the chip's trace slice, with its ``bench.*`` annotations: the
    per-span idle sums to the slice's idle, and the idle inside every
    annotation is what no annotation leaves uncovered."""
    events, (lo, hi) = recorded["events"], recorded["window"]
    rows = dict(ps.idle_by_span(events, lo, hi, prefix="bench."))
    idle = (hi - lo) / 1e9 - tr.busy_seconds(events, lo, hi)
    assert sum(rows.values()) == pytest.approx(idle, rel=1e-9)
    assert all(k.startswith("bench.") or k == ps.UNCOVERED for k in rows)
    names = {e["name"] for e in events if e["plane"] == HOST}
    inside = ps.idle_inside(events, names, lo, hi)
    assert inside == pytest.approx(idle - rows.get(ps.UNCOVERED, 0.0),
                                   rel=1e-9)


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(HERE / "testdata" / "trace_slice.json.gz", "rt") as f:
        return json.load(f)


# -- span records and the readers --------------------------------------------

def _rec(sid, name, parent, dur):
    return {"name": name, "id": sid, "parent": parent, "t0_s": 0.0,
            "dur_s": dur}


# profile (9) holds profile.warmup (0), with its own draw, stack and
# unstack, and profile.kl (5); local_steps (10) holds a draw, a stack
# and a fetch.
RECORDS = [_rec(1, "data.draw", 0, 0.5),
           _rec(2, "engine.stack", 0, 1.0),
           _rec(3, "engine.unstack", 0, 0.25),
           _rec(0, "profile.warmup", 9, 4.0),
           _rec(5, "profile.kl", 9, 2.0),
           _rec(9, "profile", None, 8.0),
           _rec(11, "data.draw", 10, 0.125),
           _rec(12, "engine.stack", 10, 2.0),
           _rec(13, "engine.fetch", 10, 3.0),
           _rec(10, "local_steps", None, 6.0)]


class _Tel:
    def __init__(self, rounds, counters=None):
        self.rounds, self._spans = rounds, []
        self.counters = counters or {}

    def counters_by_name(self, name):
        return {k: v for k, v in self.counters.items()
                if k == name or k.startswith(name + "{")}


def _ctx(tel, rounds=2):
    cell = harness.find_cell("bert-base.steps16")
    return harness.Context(cell=cell, peaks={}, telemetry=tel,
                           traced_rounds=rounds)


def test_self_time_and_ancestors():
    assert ps.self_seconds(RECORDS, "profile") == [8.0 - 4.0 - 2.0]
    assert ps.self_seconds(RECORDS, "local_steps") == [6.0 - 5.125]
    assert ps.self_seconds(RECORDS, "engine.fetch") == [3.0]
    assert sorted(ps.outside(RECORDS, ("data.draw", "engine.stack"),
                             "profile")) == [0.125, 2.0]


def test_readers_of_the_program_spans():
    tel = _Tel([{"spans": RECORDS}],
               {"host.syncs{site=engine.fetch}": 17.0,
                "host.syncs{site=profile.kl}": 190.0, "other": 5.0})
    ctx = _ctx(tel)
    read = lambda name: harness.load_metric(name).read(ctx)
    assert read("profile.kl_ms_per_job") == pytest.approx(2000.0)
    assert read("local_steps.host_ms_per_round") == pytest.approx(
        1e3 * (0.125 + 2.0) / 2)
    assert read("host.syncs_per_job") == 207.0


@pytest.mark.parametrize("name", ["profile.kl_ms_per_job",
                                  "local_steps.host_ms_per_round",
                                  "host.syncs_per_job"])
def test_readers_find_nothing_in_a_program_without_the_spans(name):
    """A program whose spans carry no ids and that counts no syncs (the
    benchmark laid over an older checkout) reads None, and no reader
    raises without telemetry."""
    old = [{"spans": [{"name": "profile.kl", "dur_s": 1.0},
                      {"name": "data.draw", "dur_s": 1.0}]}]
    reader = harness.load_metric(name)
    assert reader.read(_ctx(_Tel(old, {"engine.clients": 4.0}))) is None
    assert reader.read(_ctx(None)) is None


# -- the program's spans on the profiler's clock ----------------------------

def test_program_spans_land_on_the_trace_clock(tmp_path):
    import jax
    from repro import telemetry as tm
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with tm.session() as tel:
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with tm.span("a"):
                time.sleep(0.003)
                with tm.span("b"):
                    time.sleep(0.005)
                time.sleep(0.002)
        finally:
            jax.profiler.stop_trace()
    events = tr.load_events(tr.find_trace(str(tmp_path)), ("elsa.",))
    spans = {s["name"]: s for s in ps.records(tel)}
    a, b = tr.span_of(events, "elsa.a"), tr.span_of(events, "elsa.b")
    assert a is not None and b is not None
    assert a[0] <= b[0] and b[1] <= a[1]
    for name, (start, end) in (("a", a), ("b", b)):
        assert abs((end - start) / 1e9 - spans[name]["dur_s"]) < 1e-3
    # the trace keeps the gap between the two starts that the
    # collector's clock saw
    assert abs((b[0] - a[0]) / 1e9 - (spans["b"]["t0_s"]
                                      - spans["a"]["t0_s"])) < 1e-3
