#!/usr/bin/env python3
"""Run one cell of the chip benchmark once and print its result line.

    python3 benchmarks/tpu/run_cell.py --workload bert-base.steps16 \\
        --seed 1234 --seconds 30 --trace 0

Run from the root of a checkout, on a machine that holds the TPU chips
the cell asks for; without them it exits non-zero and prints no result.
The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``, each
number compared with its limit. The same numbers end standard error.
JAX's persistent compilation cache is kept in ``.bench_jax_cache`` at
the root of the checkout, so only a checkout's first run compiles the
programs that take a second or more; the small ones compile each run.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    harness.use_compile_cache()
    try:
        cell = harness.find_cell(args.workload)
        harness.import_program()
    except (harness.CellError, OSError, ImportError, KeyError) as e:
        print(f"run_cell: {e}", file=sys.stderr)
        return 2
    try:
        result = harness.run(cell, args.seed, args.seconds,
                             bool(args.trace), T_START)
    except harness.CellError as e:
        print(f"run_cell: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_STDERR_LOG_LEVEL", "2")
    sys.exit(main())
