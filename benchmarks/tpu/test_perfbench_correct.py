"""``correct`` on a small cell on the CPU: a sound run passes, and the
run fails with the timed path broken underneath (a step that returns
its state unchanged, half of each batch left out, a token altered where
the batch is made, an eval logit altered where it is produced), with
the bfloat16 control in the program's place, and with a basis that is
not a top-r one. The limits are the ``bert-base`` cell's own, for all.

The small cell is the encoder and decoder at reduced width (256 wide, 4
layers, vocabulary 64, sequences of 8) with the same reference files as
the chip cells. The chip's run skips nothing of this but the look for a
chip (``require_tpu=False``).
"""
import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import correctness  # noqa: E402
import harness  # noqa: E402

SEED = 2 ** 31 + 977


def tiny_cell(family: str) -> harness.Cell:
    common = {"hidden_size": 256, "num_hidden_layers": 4,
              "num_attention_heads": 4, "num_key_value_heads": 4,
              "intermediate_size": 512, "vocab_size": 64,
              "layer_norm_eps": 1e-5, "torch_dtype": "float32",
              "per_client_batch": 4}
    if family == "encoder":
        cfg = dict(common, name="tiny-encoder", family="encoder",
                   max_position_embeddings=512, type_vocab_size=2,
                   lora={"rank": 4, "alpha": 8.0, "targets": ["q", "v"]},
                   program={"model": "bert-base", "reduced": True,
                            "dtype": "float32", "layers": 4,
                            "vocab_size": 64})
        ref, limits = "bert-base", "bert-base"
    else:
        cfg = dict(common, name="tiny-decoder", family="decoder",
                   rope_theta=10000.0,
                   lora={"rank": 4, "alpha": 8.0,
                         "targets": ["q", "k", "v", "o"]},
                   program={"model": "olmo-1b", "reduced": True,
                            "dtype": "float32", "layers": 4,
                            "vocab_size": 64})
        ref, limits = "olmo-1b-v8", "bert-base"
    traffic = json.loads((HERE / "traffic" / "steps16.json").read_text())
    traffic.update(global_rounds=1, steps_per_round=3)
    traffic["federation"].update(n_clients=4, n_edges=2, poisoned=[3],
                                 total_examples=200, t_rounds=1, seq_len=8,
                                 local_warmup_steps=2, probe_q=16)
    bench = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
    return harness.Cell(
        name=f"tiny-{family}", chips=1, config=cfg, traffic=traffic,
        limits=harness.load_json(HERE / "configs" / f"{limits}.limits.json"),
        ref_path=HERE / "configs" / f"{ref}.ref.py",
        end_to_end=bench["end_to_end"], per_layer=bench["per_layer"])


@pytest.fixture(scope="module", autouse=True)
def _compile_cache(tmp_path_factory):
    """Runs after the first load their programs from a cache of this
    module's own, so each broken run does not compile again."""
    import jax
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    old = {k: getattr(jax.config, k) for k in keys}
    jax.config.update(keys[0], str(tmp_path_factory.mktemp("jaxcache")))
    jax.config.update(keys[1], 0.0)
    yield
    for k, v in old.items():
        jax.config.update(k, v)


def _run(cell, trace=False):
    return harness.run(cell, SEED, 0.0, trace, time.perf_counter(),
                       require_tpu=False)


def _broken(monkeypatch, fault):
    """Break the engine's round program underneath the harness."""
    from repro.federation.engine import BatchedEngine
    orig = BatchedEngine._round_fn

    def round_fn(self, split, prox):
        fn = orig(self, split, prox)

        def call(frozen, lora, ssop, anchor, toks, labs, wts):
            if fault == "half_batch":
                wts = wts.at[:, :, wts.shape[2] // 2:].set(0.0)
            if fault == "token":
                toks = toks.at[:, :, :, 0].add(1) % 64
            out, losses = fn(frozen, lora, ssop, anchor, toks, labs, wts)
            if fault == "unchanged":
                out = lora
            return out, losses
        call._cache_size = fn._cache_size
        return call
    monkeypatch.setattr(BatchedEngine, "_round_fn", round_fn)


def _broken_eval(monkeypatch):
    """Alter one logit of the eval program's answer."""
    import jax
    from repro.federation.simulation import Federation
    orig = Federation.evaluate

    def evaluate(self, lora):
        if self._eval_fn is None:
            self._eval_fn = jax.jit(lambda fr, lp, toks: self.model.forward(
                fr, lp, toks)[1].at[0, 0].add(1.0))
        return orig(self, lora)
    monkeypatch.setattr(Federation, "evaluate", evaluate)


@pytest.mark.parametrize("family", ["encoder", "decoder"])
def test_sound_run_is_correct(family):
    out = _run(tiny_cell(family), trace=(family == "decoder"))
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] >= 1 and out["failed"] == 0
    for k in correctness.NUMBERS:
        assert out["checks"][k]["value"] < 1e-3


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "token",
                                   "eval_answer"])
def test_broken_step_is_not_correct(monkeypatch, fault):
    if fault == "eval_answer":
        _broken_eval(monkeypatch)
    else:
        _broken(monkeypatch, fault)
    out = _run(tiny_cell("encoder"))
    assert not out["correct"], out["checks"]
    if fault == "eval_answer":
        assert out["checks"]["eval_gap"]["value"] > \
            out["checks"]["eval_gap"]["limit"]


def test_control_is_not_correct():
    """The bfloat16 reference in the program's place fails a limit."""
    cell = tiny_cell("encoder")
    fed = harness.build(cell, SEED)
    _, record = harness.warm_job(cell, fed)
    del fed
    control = harness.reference_readings(cell, record, SEED, "control")
    ref = harness.reference_readings(cell, record, SEED,
                                     basis=control["basis"])
    values = dict(correctness.readings(control, ref),
                  **harness.basis_check(cell, record, SEED,
                                        control["basis"]))
    ok, rows = correctness.verdict(values, cell.limits["limits"])
    assert not ok, rows
    same = correctness.readings(ref, ref)
    assert all(same[k] == 0.0 for k in ("loss_gap", "update_gap",
                                        "edge_agg_gap", "eval_gap"))
    # a basis that is not a top-r one (singular vectors 2 to r+1)
    wrong = dict(values, **harness.basis_check(
        cell, record, SEED, harness.shifted_basis(cell, record, SEED)))
    assert wrong["basis_gap"] > cell.limits["limits"]["basis_gap"]
    assert not correctness.verdict(
        dict(wrong, **correctness.readings(ref, ref)),
        cell.limits["limits"])[0]


def test_leaf_gap_rule():
    ref = {"a": np.ones(4), "b": np.full(4, 2.0), "c": np.full(4, 1e-6)}
    assert correctness.worst_leaf_gap(ref, ref)[0] == 0.0
    unmoved = dict(ref, b=np.zeros(4))
    assert correctness.worst_leaf_gap(unmoved, ref) == (1.0, "b")
    # a leaf nought to rounding in the reference is not compared
    noisy = dict(ref, c=np.full(4, 1.0))
    assert correctness.worst_leaf_gap(noisy, ref)[0] == 0.0
