"""Benchmark harness: the single entry point over every bench script.

Runs the suite (or a ``--only`` subset), printing the usual
``name,us_per_call,derived`` CSV, then merges everything one run
produced — each ``BENCH_*.json`` record plus the summary line of its
telemetry sidecar (``*.telemetry.jsonl``, docs/observability.md) — into
one ``BENCH_manifest.json`` run manifest: per-bench status/duration,
the full records, and the merged telemetry summaries.

Usage::

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME] \\
        [--out-dir DIR]

``--out-dir`` redirects every fresh record (and the manifest) into a
directory — the CI bench-gate shape, where the directory is both the
regression-gate input and the uploaded artifact.  Without it, full-mode
records land at the repo root as always and the manifest beside them.
"""
import argparse
import glob
import inspect
import json
import os
import sys
import time

from repro.launch.compile_cache import enable_compile_cache
from repro.telemetry import read_jsonl

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

BENCHES = [
    ("fig2_clustering", "benchmarks.bench_clustering"),
    ("table2_accuracy", "benchmarks.bench_accuracy"),
    ("table3_comm_time", "benchmarks.bench_comm_time"),
    ("table4_compression", "benchmarks.bench_compression"),
    ("table5_splitting", "benchmarks.bench_splitting"),
    ("table6_privacy", "benchmarks.bench_privacy"),
    ("kernels", "benchmarks.bench_kernels"),
    ("roofline", "benchmarks.bench_roofline"),
    ("fed_round", "benchmarks.bench_fed_round"),
    ("sharded_round", "benchmarks.bench_sharded_round"),
    ("convergence", "benchmarks.bench_convergence"),
    ("time_to_accuracy", "benchmarks.bench_time_to_accuracy"),
    ("fault_tolerance", "benchmarks.bench_fault_tolerance"),
    ("population_scale", "benchmarks.bench_population_scale"),
]


def _run_kwargs(fn, quick: bool, out_dir: str, mod) -> dict:
    """The kwargs this bench's ``run()`` actually accepts: quick mode
    where supported, and the fresh record redirected into ``out_dir``
    (keeping each script's own BENCH filename)."""
    params = inspect.signature(fn).parameters
    kw = {}
    if quick and "quick" in params:
        kw["quick"] = True
    if out_dir and "out" in params:
        default = getattr(mod, "OUT_PATH", None)
        if default is not None:
            kw["out"] = os.path.join(out_dir,
                                     os.path.basename(default))
            if "write" in params:
                kw["write"] = True
    return kw


def merge_manifest(out_dir: str, benches: dict) -> dict:
    """Fold every ``BENCH_*.json`` in ``out_dir`` (+ its telemetry
    sidecar's summary line, when present) into one manifest dict."""
    records, telemetry = {}, {}
    for path in sorted(glob.glob(os.path.join(out_dir, "BENCH_*.json"))):
        fname = os.path.basename(path)
        if fname == "BENCH_manifest.json":
            continue
        try:
            with open(path) as f:
                records[fname] = json.load(f)
        except (OSError, ValueError) as e:
            records[fname] = {"error": f"{type(e).__name__}: {e}"}
            continue
        sidecar = path[:-5] + ".telemetry.jsonl"
        if os.path.exists(sidecar):
            telemetry[fname] = read_jsonl(sidecar)["summary"]
    return {"benches": benches, "records": records,
            "telemetry": telemetry}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run only benches whose name contains this")
    ap.add_argument("--quick", action="store_true",
                    help="pass quick=True to benches that support it")
    ap.add_argument("--out-dir", default=None,
                    help="directory for fresh BENCH_*.json records + "
                         "the merged BENCH_manifest.json (default: "
                         "records go to the repo root)")
    args = ap.parse_args()
    enable_compile_cache()
    out_dir = os.path.abspath(args.out_dir) if args.out_dir else None
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)

    benches = {}
    print("name,us_per_call,derived")
    t0 = time.time()
    for name, module in BENCHES:
        if args.only and args.only not in name:
            continue
        mod = __import__(module, fromlist=["run"])
        t_b = time.time()
        try:
            mod.run(**_run_kwargs(mod.run, args.quick, out_dir, mod))
            status = "ok"
        except Exception as e:  # noqa: BLE001
            status = f"ERROR:{type(e).__name__}:{e}"
            print(f"{name},0.0,{status}", file=sys.stderr)
            print(f"{name},0.0,ERROR:{type(e).__name__}")
        benches[name] = {"status": status,
                         "seconds": round(time.time() - t_b, 1)}
    print(f"# total {time.time()-t0:.1f}s")

    manifest = merge_manifest(out_dir or ROOT, benches)
    mpath = os.path.join(out_dir or ROOT, "BENCH_manifest.json")
    with open(mpath, "w") as f:
        json.dump(manifest, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# manifest: {mpath} ({len(manifest['records'])} records, "
          f"{len(manifest['telemetry'])} telemetry summaries)")


if __name__ == '__main__':
    main()
