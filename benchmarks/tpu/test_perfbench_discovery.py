"""Cells and metrics are found by name: a configuration, a traffic mix
and a metric added as files, with entries in ``BENCHMARK.json``, are
run without an edit to any file already there. And the run command
refuses a machine without the chips, and a directory without the
program."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402


def _digest(directory: Path) -> str:
    h = hashlib.sha256()
    for p in sorted(directory.rglob("*")):
        if p.is_file() and "__pycache__" not in p.parts:
            h.update(str(p.relative_to(directory)).encode())
            h.update(p.read_bytes())
    return h.hexdigest()


def _checkout(tmp_path: Path) -> Path:
    """A copy of the benchmark with one new configuration, traffic mix,
    metric and cell, each added as files and entries only."""
    root = tmp_path / "checkout"
    bench_dir = root / "benchmarks" / "tpu"
    shutil.copytree(HERE, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(bench_dir)
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
