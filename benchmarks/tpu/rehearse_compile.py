#!/usr/bin/env python3
"""Compile a cell's programs at full size for a described TPU v5e chip,
with no chip attached, and print each one's memory analysis.

    JAX_PLATFORMS=cpu python3 benchmarks/tpu/rehearse_compile.py \\
        --workload olmo-1b-v8.steps16

The programs are the ones a job runs, at the cell's shapes: the local
round (``jit_round_fn``) for the profiling warm-up (all clients, the
default split, the warm-up steps) and for a full edge group at the
round's local steps, the eval forward over the 512-row test set, and
the vmapped probe forward. Shapes only: no weight is made. A program
the chip's compiler refuses for want of memory fails here the same way.
Nothing runs, so this says nothing about time.
"""
import argparse
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

GIB = 2.0 ** 30


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batch", type=int, default=None,
                    help="per-client batch to try instead of the cell's")
    args = ap.parse_args(argv)

    import harness
    cell = harness.find_cell(args.workload)
    harness.import_program()
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.core.sketch import make_plan
    from repro.core.split_training import Split
    from repro.core.ssop import SSOP
    from repro.federation.engine import BatchedEngine
    from repro.models.params import abstract_tree
    from repro.models.split_api import get_split_model

    jax.config.update("jax_enable_compilation_cache", False)
    kw = harness.fed_settings(cell)
    f = cell.traffic["federation"]
    batch = args.batch or kw["batch_size"]
    overrides = {"vocab_size": kw["vocab_size"]} if kw.get("vocab_size") \
        else {}
    model = get_split_model(kw["model"], dtype=kw["dtype"],
                            reduced=kw["reduced"], **overrides)
    c = model.cfg
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def shaped(tree, lead=()):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(lead + s.shape, s.dtype,
                                           sharding=chip), tree)

    specs = model.specs(f["num_classes"])
    frozen = shaped(abstract_tree(specs["frozen"], jnp.dtype(kw["dtype"])))
    lora = abstract_tree(specs["lora"], jnp.dtype(kw["dtype"]))
    d, r, n = c.d_model, f["ssop_r"], f["n_clients"]
    z = f["sketch_z"] or max(4, int(d / (f["rho"] * f["sketch_y"])))
    plan = make_plan(d, f["sketch_y"], z, seed=0)
    eng = BatchedEngine(model, None, plan, lr=f["lr"], batch_size=batch,
                        use_channel=f["use_channel"],
                        use_ssop=f["use_ssop"])
    eng.donate = True
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    ssop = SSOP(u=sds((n, d, r), jnp.float32), v=sds((n, r, r), jnp.float32),
                w=sds((n, r, r), jnp.float32),
                w_inv=sds((n, r, r), jnp.float32))
    p_max = min(5, c.num_layers - 3)
    split = Split(p_max, c.num_layers - p_max - 2, 2)
    s = f["seq_len"]

    def round_args(steps):
        return (frozen, shaped(lora, (n,)), ssop, None,
                sds((steps, n, batch, s), jnp.int32),
                sds((steps, n, batch), jnp.int32),
                sds((steps, n, batch), jnp.float32))

    programs = [
        ("round warm-up", eng._round_fn(split, False),
         round_args(f["local_warmup_steps"])),
        ("round edge group", eng._round_fn(split, False),
         round_args(cell.traffic["steps_per_round"])),
        ("eval", jax.jit(lambda fr, lp, t: model.forward(fr, lp, t)[1]),
         (frozen, shaped(lora), sds((512, s), jnp.int32))),
        ("probe", jax.jit(jax.vmap(model.probe_repr,
                                   in_axes=(None, 0, None))),
         (frozen, shaped(lora, (n,)), sds((f["probe_q"], s), jnp.int32))),
    ]
    rows = []
    for name, fn, a in programs:
        try:
            m = fn.lower(*a).compile().memory_analysis()
        except Exception as e:  # the compiler's refusal is the finding
            rows.append({"program": name, "error": str(e)[:400]})
            print(f"{name}: REFUSED {str(e)[:400]}", flush=True)
            continue
        row = {"program": name,
               "argument_gib": m.argument_size_in_bytes / GIB,
               "output_gib": m.output_size_in_bytes / GIB,
               "temp_gib": m.temp_size_in_bytes / GIB,
               "alias_gib": m.alias_size_in_bytes / GIB}
        rows.append(row)
        print(f"{name}: arguments {row['argument_gib']:.3f} GiB, outputs "
              f"{row['output_gib']:.3f} GiB, temporaries "
              f"{row['temp_gib']:.3f} GiB", flush=True)
    print(json.dumps({"workload": cell.name, "batch": batch,
                      "programs": rows}))
    return 0 if all("error" not in r for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
