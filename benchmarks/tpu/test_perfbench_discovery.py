"""Cells and metrics are found by name: a configuration, a traffic mix
and a metric added as files, with entries in ``BENCHMARK.json``, are
run without an edit to any file already there, and so is a
configuration whose layer stack is not uniform, with its own count
module, reference and model checks. And the run command refuses a
machine without the chips, and a directory without the program."""
import dataclasses
import hashlib
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
TOY = HERE / "testdata" / "toy-moe"
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import correctness  # noqa: E402
import harness  # noqa: E402


def _digest(directory: Path) -> str:
    h = hashlib.sha256()
    for p in sorted(directory.rglob("*")):
        if p.is_file() and "__pycache__" not in p.parts:
            h.update(str(p.relative_to(directory)).encode())
            h.update(p.read_bytes())
    return h.hexdigest()


def _copy(tmp_path: Path):
    """A copy of the benchmark, and the digest of its files."""
    root = tmp_path / "checkout"
    bench_dir = root / "benchmarks" / "tpu"
    shutil.copytree(HERE, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root, bench_dir, _digest(bench_dir)


def _checkout(tmp_path: Path) -> Path:
    """A copy of the benchmark with one new configuration, traffic mix,
    metric and cell, each added as files and entries only."""
    root, bench_dir, before = _copy(tmp_path)
    for suffix in (".json", ".ref.py", ".limits.json"):
        shutil.copy(bench_dir / "configs" / f"bert-base{suffix}",
                    bench_dir / "configs" / f"bert-base-l6{suffix}")
    cfg = json.loads((bench_dir / "configs" / "bert-base-l6.json")
                     .read_text())
    cfg["num_hidden_layers"] = 6
    (bench_dir / "configs" / "bert-base-l6.json").write_text(
        json.dumps(cfg))
    traffic = json.loads((bench_dir / "traffic" / "steps16.json")
                         .read_text())
    traffic["steps_per_round"] = 1
    (bench_dir / "traffic" / "steps1.json").write_text(json.dumps(traffic))
    (bench_dir / "metrics" / "jobs_in_window.py").write_text(
        "def read(ctx):\n    return len(ctx.jobs) or None\n")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="bert-base-l6",
                                 file="benchmarks/tpu/configs/"
                                      "bert-base-l6.json"))
    bench["workloads"].append({"name": "bert-base-l6.steps1",
                               "config": "bert-base-l6", "traffic": "steps1",
                               "chips": 1, "why": "test"})
    bench["end_to_end"].append({"name": "jobs_in_window", "unit": "count",
                                "better": "higher", "bound": 0.01,
                                "source": "host_clock",
                                "workloads": ["bert-base-l6.steps1"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root, bench_dir, before


def test_new_files_are_found_without_edits(tmp_path):
    root, bench_dir, before = _checkout(tmp_path)
    cell = harness.find_cell("bert-base-l6.steps1", root, bench_dir)
    assert cell.config["num_hidden_layers"] == 6
    assert cell.traffic["steps_per_round"] == 1
    assert cell.ref_path.name == "bert-base-l6.ref.py"
    assert cell.metric_names(trace=False) == [
        "train_tokens_per_s", "setup_s", "jobs_in_window"]
    reader = harness.load_metric("jobs_in_window", bench_dir)
    assert reader.read(harness.Context(cell=cell, peaks={},
                                       jobs=[{}, {}])) == 2
    # the old cell does not report the new cell's metric
    old = harness.find_cell("bert-base.steps16", root, bench_dir)
    assert "jobs_in_window" not in old.metric_names(trace=False)
    for name in ("train_tokens_per_s", "setup_s"):
        assert harness.load_metric(name, bench_dir)
    # nothing that was there changed
    for p in ("configs/bert-base-l6.json", "configs/bert-base-l6.ref.py",
              "configs/bert-base-l6.limits.json", "traffic/steps1.json",
              "metrics/jobs_in_window.py"):
        (bench_dir / p).unlink()
    assert _digest(bench_dir) == before


TOY_FILES = {"configs/toy-moe.json": TOY / "toy-moe.json",
             "configs/toy-moe.ref.py": TOY / "toy-moe.ref.py",
             "configs/toy-moe.limits.json":
                 HERE / "configs" / "bert-base.limits.json",
             "counts/dense-moe.py": TOY / "dense-moe.py"}


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _toy_arch(**moe):
    """The program's architecture record as it would resolve the toy."""
    from repro.configs.base import ArchConfig, LoRAConfig, MoEConfig
    return ArchConfig(
        name="toy-moe", family="moe", num_layers=3, d_model=32,
        num_heads=4, num_kv_heads=4, d_ff=16, vocab_size=64,
        param_dtype="float32",
        moe=MoEConfig(**dict(dict(num_experts=4, experts_per_token=2,
                                  expert_d_ff=16, first_dense_layers=1,
                                  dense_d_ff=64), **moe)),
        lora=LoRAConfig(rank=4, alpha=8.0, targets=("q", "v")))


def test_a_stack_that_is_not_uniform_enters_as_new_files(tmp_path):
    """A dense first layer before stacked expert layers: a count module,
    a reference with ``run_layers`` and the file's ``program_checks``,
    all new files, and two entries in ``BENCHMARK.json``."""
    import jax
    import numpy as np
    import refkit
    root, bench_dir, before = _copy(tmp_path)
    for dst, src in TOY_FILES.items():
        shutil.copy(src, bench_dir / dst)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy-moe", "source": "test",
                             "file": "benchmarks/tpu/configs/toy-moe.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy-moe.steps16",
                               "config": "toy-moe", "traffic": "steps16",
                               "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.find_cell("toy-moe.steps16", root, bench_dir)
    cfg, fed = cell.config, cell.traffic["federation"]
    assert cell.ref_path == bench_dir / "configs" / "toy-moe.ref.py"

    # counts: the checkout's flops.py finds the family's module by name
    flops = _load(bench_dir / "flops.py", "checkout_flops")
    m = flops.family(cfg)
    assert Path(m.__file__) == bench_dir / "counts" / "dense-moe.py"
    s = 8
    fwd = [m.block_forward(cfg, s, i) for i in range(3)]
    bwd = [m.block_backward(cfg, s, i, lowest=(i == 0)) for i in range(3)]
    assert fwd[0] != fwd[1] == fwd[2]
    assert flops.train_sequence(cfg, fed, s) == sum(fwd) + sum(bwd) \
        + 2 * m.head_forward(cfg, fed, s) \
        + 4 * flops.channel_forward(cfg, fed, s)
    assert flops.forward_sequence(cfg, fed, s, logits=False) == sum(fwd)

    # the file's program_checks add to the seven and replace d_ff
    harness.check_model(SimpleNamespace(cfg=_toy_arch()), cfg)
    for wrong in ({"expert_d_ff": 32}, {"first_dense_layers": 0}):
        with pytest.raises(harness.CellError):
            harness.check_model(SimpleNamespace(cfg=_toy_arch(**wrong)),
                                cfg)
    no_moe = dataclasses.replace(_toy_arch(), moe=None)
    with pytest.raises(harness.CellError):
        harness.check_model(SimpleNamespace(cfg=no_moe), cfg)
    plain = {k: v for k, v in cfg.items() if k != "program_checks"}
    with pytest.raises(harness.CellError):    # d_ff 16 against 64
        harness.check_model(SimpleNamespace(cfg=_toy_arch()), plain)

    # the reference runs the stack through run_layers, on the CPU
    model = refkit.load_model(cell.ref_path)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 64, size=(2, 2, s)).astype(np.int32)
    labs = np.zeros((2, 2), np.int32)
    wts = np.ones((2, 2), np.float32)
    with jax.default_matmul_precision("highest"):
        ref = refkit.Reference(cfg, model, harness.fed_params(cell, 7),
                               refkit.REFERENCE)
        u = ref.semantic_basis(rng.integers(0, 64, size=(16, s)))
        rounds = {split: ref.client_round(0, split, u, toks, labs, wts)
                  for split in ((0, 2, 1), (1, 1, 1), (2, 1, 0))}
        trees = [harness.host_tree(lora) for _, lora in rounds.values()]
        agg = harness.host_tree(refkit.product_mean(trees, [1.0, 2.0, 1.0]))
        logits = ref.eval_logits(trees[0], toks[0])
    losses = [tuple(l) for l, _ in rounds.values()]
    assert np.all(np.isfinite(losses)) and len(set(losses)) == 3
    assert logits.shape == (2, s, 256) and np.all(np.isfinite(logits))

    # leaves: the dense layer's by list index, the stack's by layer
    names = set(correctness.leaf_arrays(trees[0]))
    assert {"/prefix[0]/attn/q_b", "/prefix[0]/attn/v_a",
            "/blocks/attn/q_b[0]", "/blocks/attn/v_a[1]"} <= names
    assert set(correctness.leaf_arrays(agg)) == names
    moved = correctness.change(trees[0], harness.host_tree(ref.lora0))
    assert np.linalg.norm(moved["/prefix[0]/attn/q_b"]) > 0
    assert np.linalg.norm(moved["/blocks/attn/v_b[1]"]) > 0

    # nothing that was there changed
    for dst in TOY_FILES:
        (bench_dir / dst).unlink()
    assert _digest(bench_dir) == before


def test_every_metric_has_a_reader():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert hasattr(harness.load_metric(m["name"]), "read"), m["name"]
    for w in bench["workloads"]:
        cell = harness.find_cell(w["name"])
        assert cell.ref_path.exists()
        assert set(cell.limits["limits"]) >= {"loss_gap", "update_gap",
                                              "edge_agg_gap"}


def _run_cell(cwd: Path, env_extra):
    env = dict(os.environ, **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/tpu/run_cell.py", "--workload",
         "bert-base.steps16", "--seed", "3000000001", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_run_refuses_a_cpu():
    proc = _run_cell(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_run_refuses_without_the_program(tmp_path):
    (tmp_path / "benchmarks").mkdir()
    shutil.copytree(HERE, tmp_path / "benchmarks" / "tpu",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    proc = _run_cell(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
