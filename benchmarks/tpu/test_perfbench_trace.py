"""``trace_reduce`` on a small trace recorded on a TPU v5e chip
(``testdata/trace_slice.json.gz``: the device and annotation events of
a slice of one traced ``bert-base.steps16`` job, as ``load_events``
keeps them, with the slice's bounds), checked against a brute-force
count on a time grid."""
import gzip
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import trace_reduce as tr  # noqa: E402

GRID_NS = 100.0


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(HERE / "testdata" / "trace_slice.json.gz", "rt") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def events(recorded):
    return recorded["events"]


@pytest.fixture(scope="module")
def window(recorded):
    return tuple(recorded["window"])


def _grid_busy(events, plane, lo, hi):
    """Busy mask of one device plane on a GRID_NS grid over [lo, hi)."""
    n = int(np.ceil((hi - lo) / GRID_NS))
    busy = np.zeros(n, bool)
    for e in events:
        if e["plane"] != plane or e["line"] != tr.OPS_LINE:
            continue
        a = int(np.floor((max(e["start_ns"], lo) - lo) / GRID_NS))
        b = int(np.ceil((min(e["start_ns"] + e["dur_ns"], hi) - lo)
                        / GRID_NS))
        busy[max(a, 0):max(b, 0)] = True
    return busy


def test_slice_is_a_device_trace(events, window):
    assert tr.device_planes(events) == ["/device:TPU:0"]
    lines = {e["line"] for e in events if e["plane"] == "/device:TPU:0"}
    assert lines == {tr.OPS_LINE, tr.MODULES_LINE}
    assert window[0] < window[1]


def test_busy_union_against_grid(events, window):
    lo, hi = window
    busy = tr.busy_seconds(events, lo, hi)
    grid = _grid_busy(events, "/device:TPU:0", lo, hi).sum() * GRID_NS / 1e9
    # each interval edge rounds out to the grid by at most one cell
    n_ops = sum(1 for e in events if e["line"] == tr.OPS_LINE)
    assert busy <= grid + 1e-12
    assert grid - busy <= 2 * n_ops * GRID_NS / 1e9
    assert 0.0 < busy < (hi - lo) / 1e9


def test_idle_share_and_gaps(events, window):
    lo, hi = window
    busy = tr.busy_seconds(events, lo, hi)
    gaps = tr.idle_gaps(events, ("bench.",), lo, hi, k=1000)
    idle = sum(s for _, s in gaps)
    assert idle == pytest.approx((hi - lo) / 1e9 - busy, rel=1e-9)
    longest = tr.idle_gaps(events, ("bench.",), lo, hi, k=3)
    assert [g[1] for g in longest] == sorted((g[1] for g in gaps),
                                             reverse=True)[:3]
    assert all(name.startswith("bench.") or name == "host:none"
               for name, _ in longest)


def test_module_time(events, window):
    lo, hi = window
    mods = [e for e in events if e["line"] == tr.MODULES_LINE
            and e["name"].startswith("jit_round_fn")]
    assert mods
    want = sum(min(e["start_ns"] + e["dur_ns"], hi) - max(e["start_ns"], lo)
               for e in mods) / 1e9
    assert tr.module_seconds(events, "jit_round_fn", lo, hi) == \
        pytest.approx(want, rel=1e-12)
    assert tr.module_count(events, "jit_round_fn", lo, hi) == len(
        [e for e in mods if lo <= e["start_ns"] <= hi])


def test_top_ops(events, window):
    lo, hi = window
    top = tr.top_ops(events, lo, hi, k=5)
    assert len(top) == 5
    assert [t[1] for t in top] == sorted((t[1] for t in top), reverse=True)
    name, secs = top[0]
    want = sum(min(e["start_ns"] + e["dur_ns"], hi) - max(e["start_ns"], lo)
               for e in events if e["line"] == tr.OPS_LINE
               and e["name"] == name and e["start_ns"] < hi
               and e["start_ns"] + e["dur_ns"] > lo) / 1e9
    assert secs == pytest.approx(want, rel=1e-12)


def test_union_merges_overlaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.clip([(0, 10), (12, 14)], 2, 13) == [(2, 10), (12, 13)]
