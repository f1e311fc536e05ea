"""The decoder cell with its profiling warm-up cut: the small decoder
cell of ``test_perfbench_correct`` (OLMo-1B's program at reduced width
against ``olmo-1b-v8.ref.py``) runs through ``harness.run`` on the CPU
while the compiler is made to refuse every warm-up program of more than
two clients, as the v5e compiler refuses OLMo-1B's 20-client warm-up.
The run is ``correct``, and the readers of the cut read it. The cell
goes under the olmo cell's name, so the harness reads that cell's
metrics.
"""
import dataclasses
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import pytest  # noqa: E402

import harness  # noqa: E402
from test_perfbench_correct import SEED, tiny_cell  # noqa: E402

CELL = "olmo-1b-v8.steps16"
REFUSAL = ("RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out "
           "of memory in memory space hbm.")
READERS = ("engine.cut_dispatches_per_job", "engine.fit_clients")


def _refuse_warmup_above(monkeypatch, cap, steps):
    """The compiler's refusal of every round program of ``steps`` local
    steps and more than ``cap`` clients, where the engine dispatches it
    and where its fit compiles it."""
    import jax
    from repro.federation.engine import BatchedEngine
    round_fn, compile_cut = BatchedEngine._round_fn, BatchedEngine._compile_cut

    def check(args):
        toks = args[4]
        if toks.shape[0] == steps and toks.shape[1] > cap:
            raise jax.errors.JaxRuntimeError(REFUSAL)

    def refusing_round_fn(self, split, prox):
        fn = round_fn(self, split, prox)

        def call(*args):
            check(args)
            return fn(*args)
        call._cache_size = fn._cache_size
        return call

    def refusing_compile_cut(self, fn, args):
        check(args)
        return compile_cut(self, fn, args)
    monkeypatch.setattr(BatchedEngine, "_round_fn", refusing_round_fn)
    monkeypatch.setattr(BatchedEngine, "_compile_cut", refusing_compile_cut)


def test_cut_warmup_run_is_correct(monkeypatch):
    cell = dataclasses.replace(tiny_cell("decoder"), name=CELL)
    f = cell.traffic["federation"]
    _refuse_warmup_above(monkeypatch, 2, f["local_warmup_steps"])
    out = harness.run(cell, SEED, 0.0, True, time.perf_counter(),
                      require_tpu=False)
    assert out["correct"], out["checks"]
    metrics = out["metrics"]
    # the traced job's warm-up: 4 clients at 2 a program
    assert metrics["engine.cut_dispatches_per_job"]["value"] == 2.0
    assert metrics["engine.fit_clients"]["value"] == 2.0


class _Tel:
    def __init__(self, counters, gauges):
        self.counters, self.gauges = counters, gauges

    def counters_by_name(self, name):
        return {k: v for k, v in self.counters.items()
                if k == name or k.startswith(name + "{")}


def _ctx(tel):
    return harness.Context(cell=harness.find_cell(CELL), peaks={},
                           telemetry=tel, traced_rounds=2)


def test_readers_of_the_cut():
    tel = _Tel({"engine.cut_dispatches{size=7,split=p5q9o2}": 2.0,
                "engine.cut_dispatches{size=6,split=p5q9o2}": 1.0,
                "engine.clients": 20.0},
               {"engine.fit_clients{prox=False,split=p5q9o2}": 8.0,
                "engine.fit_clients{prox=False,split=p3q11o2}": 10.0,
                "engine.compile_cache{prox=False,split=p5q9o2}": 3.0})
    read = lambda name: harness.load_metric(name).read(_ctx(tel))
    assert read("engine.cut_dispatches_per_job") == 3.0
    assert read("engine.fit_clients") == 8.0


@pytest.mark.parametrize("name", READERS)
def test_readers_read_none_where_nothing_was_cut(name):
    """A job that cut nothing, or a program that keeps neither counter
    nor gauge (the benchmark laid over an older checkout), reads None,
    and no reader raises without telemetry."""
    reader = harness.load_metric(name)
    uncut = _Tel({"engine.clients": 20.0},
                 {"engine.compile_cache{prox=False,split=p5q9o2}": 3.0})
    assert reader.read(_ctx(uncut)) is None
    assert reader.read(_ctx(None)) is None
