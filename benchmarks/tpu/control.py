#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's, the control's and
the planted faults', seed by seed, in one process on the chip.

    python3 benchmarks/tpu/control.py --workload bert-base.steps16 \\
        --seeds 11 12 13 --out readings.jsonl

For each seed it runs the cell's set-up (the federation and its warm
job, as a benchmark run does) and compares the captured round and eval
with the float32 reference at HIGHEST, as ``run_cell.py`` does. It then
puts in the program's place the same reference computed in bfloat16 at
default precision (the control, with its own SS-OP basis, against the
float32 reference on that basis), and the reference with a fault
planted: half of every batch left out with the mean over the rest
(``half_batch``), position 0 of every row altered (``token``), and
every step returning its state unchanged (``unchanged``: it reads 1 on
``update_gap`` and ``edge_agg_gap`` by construction, so only its eval
runs). And ``shifted_basis``: the reference's singular vectors 2 to
r+1 as the SS-OP basis, which ``basis_gap`` has to catch. One JSON line
per seed and variant goes to standard output and to ``--out``; the
lowest reading of each number over the variants that are not the
program ends standard error.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

FAULTS = ("half_batch", "token")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import harness
    import correctness
    harness.use_compile_cache()
    cell = harness.find_cell(args.workload)
    harness.import_program()
    dev = harness.device_record()
    if dev["platform"] != "tpu":
        print(f"control: needs a TPU; JAX found {dev['platform']}",
              file=sys.stderr)
        return 1
    out = open(args.out, "a") if args.out else None
    worst = {}
    for seed in args.seeds:
        t0 = time.perf_counter()
        fed = harness.build(cell, seed)
        hist, record = harness.warm_job(cell, fed)
        del fed
        gc.collect()
        t1 = time.perf_counter()
        ref = harness.reference_readings(cell, record, seed)
        prog = dict(correctness.readings(harness.program_readings(record),
                                         ref),
                    **harness.basis_check(cell, record, seed, ref["basis"]))
        rows = [("program", prog)]
        ctl = harness.reference_readings(cell, record, seed, "control")
        ref_c = harness.reference_readings(cell, record, seed,
                                           basis=ctl["basis"])
        rows.append(("control", dict(
            correctness.readings(ctl, ref_c),
            **harness.basis_check(cell, record, seed, ctl["basis"]))))
        for v in FAULTS:
            alt = harness.reference_readings(cell, record, seed, v)
            rows.append((v, dict(correctness.readings(alt, ref),
                                 basis_gap=prog["basis_gap"],
                                 rotation_gap=prog["rotation_gap"])))
        alt = harness.reference_readings(cell, record, seed, "unchanged",
                                         rounds=False)
        rows.append(("unchanged", {
            "update_gap": 1.0, "edge_agg_gap": 1.0,
            "eval_gap": correctness.eval_gap(alt["eval_logits"],
                                             ref["eval_logits"])}))
        rows.append(("shifted_basis", harness.basis_check(
            cell, record, seed, harness.shifted_basis(cell, record,
                                                      seed))))
        for name, r in rows:
            line = dict(r, seed=seed, variant=name, workload=cell.name,
                        members=len(record["members"]),
                        setup_s=t1 - t0,
                        reference_s=time.perf_counter() - t1,
                        loss=hist["loss"])
            print(json.dumps(line), flush=True)
            if out:
                out.write(json.dumps(line) + "\n")
                out.flush()
            for k in (k for k in correctness.NUMBERS if k in r):
                key = (name, k)
                agg = max if name == "program" else min
                worst[key] = agg(worst.get(key, r[k]), r[k])
    for (name, k), v in sorted(worst.items()):
        kind = "highest" if name == "program" else "lowest"
        print(f"{name} {k} {kind} {v!r}", file=sys.stderr)
    print(f"control: {time.perf_counter() - T_START:.1f} s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
