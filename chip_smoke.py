#!/usr/bin/env python3
"""Bring-up smoke: ELSA split-federated fine-tuning on a TPU at
BERT-base's published width.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded round on 4 chips

One chip: BERT-base (12 layers, d_model 768, 12 heads, vocab 30522,
float32, random weights from the seed) federated over 8 clients and 2
edges with the SS-OP + count-sketch channel on, through the library
boundary: ``FedConfig`` -> ``Federation`` -> ``profile_clients()`` ->
``run("elsa", global_rounds=2, steps_per_round=4)``.  It prints the
device, the model shape, the wall time of each global round (round 1
includes compilation), XLA compile counts and seconds, peak device
memory, the loss history and the final accuracy, and checks that every
loss is finite and that client 0's first two local steps give the same
mean loss (within ``LOSS_RTOL``) and the same adapter updates (within
``UPDATE_RTOL``) on the batched engine as on the sequential reference
path.

``--chips 4``: only the same ELSA round with the stacked client axis
sharded over a 4-device mesh, and the same round with ``mesh=None`` on
one device of this process as the baseline.  It checks that the two
loss histories agree within ``LOSS_RTOL``, that every dispatch split the
LoRA and batch stacks 4 ways, and that the frozen params are
replicated.

The last line of stdout is ``{"ok": true, "device": {...}}`` only when
every check passed; any failure exits non-zero without it.  Without a
TPU the script refuses to run.  It runs every phase in this one process
and starts no other.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import telemetry as tm  # noqa: E402
from repro.data.pipeline import infinite_batches  # noqa: E402
from repro.federation.simulation import FedConfig, Federation  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_federation_mesh  # noqa: E402

# TPU matmuls on float32 operands run at the default precision: one
# bfloat16 pass, so every product carries a relative rounding error up
# to 2^-8.  Two programs that compute the same math but fuse and round
# at different points (the batched vmap/scan engine vs the eager
# reference; a sharded vs an unsharded round) may therefore disagree by
# a few such units; 4 units of 2^-8 is the bound.  A loss that differs
# by more comes from different math, not from rounding.
LOSS_RTOL = 2.0 ** -6
# The adapters' update after PARITY_STEPS local SGD steps is lr times
# the summed gradients, and a gradient reaches an adapter through the
# backward matmuls of up to all 12 layers plus the channel, each adding
# up to 2^-8 relative rounding: 12 * 2^-8 ~ 2^-4.4 in norm, bounded at
# 2^-3 per leaf.  A wrong gradient, learning rate or update rule moves a
# leaf's update by O(1) of its norm.
UPDATE_RTOL = 2.0 ** -3
PARITY_STEPS = 2

SMOKE_CONFIG = dict(model="bert-base", reduced=False, n_clients=8,
                    n_edges=2, poisoned=(3,), seq_len=128, batch_size=16,
                    use_channel=True, use_ssop=True)
SMOKE_RUN = dict(global_rounds=2, steps_per_round=4)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


class _RoundClock(tm.Sink):
    """Host time at every round boundary.  The round loop closes a round
    only after its eval accuracy reached the host, so the device work of
    the round is done by then."""

    def __init__(self):
        self.stamps = []

    def emit_round(self, rec):
        self.stamps.append(time.perf_counter())


class _CompileLog:
    """XLA backend compiles (or persistent-cache loads) in this process."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def line(self) -> str:
        return (f"{self.count} XLA programs, {self.seconds:.3f} s compiling "
                f"or loading, {self.cache_hits} persistent-cache hits")


def device_record() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def describe_model(fed: Federation) -> None:
    c = fed.cfg
    n_frozen = sum(x.size for x in jax.tree_util.tree_leaves(fed.frozen))
    n_lora = sum(x.size for x in jax.tree_util.tree_leaves(fed.lora0))
    print(f"model: {c.name} layers={c.num_layers} d_model={c.d_model} "
          f"heads={c.num_heads} d_ff={c.d_ff} vocab={c.vocab_size} "
          f"lora_rank={c.lora.rank} dtype={c.param_dtype} "
          f"frozen_params={n_frozen} lora_params={n_lora}")
    fc = fed.fed
    print(f"federation: clients={fc.n_clients} edges={fc.n_edges} "
          f"seq_len={fc.seq_len} batch={fc.batch_size} "
          f"channel={fc.use_channel} ssop={fc.use_ssop} "
          f"sketch=(Y={fed.plan.y}, Z={fed.plan.z}) "
          f"splits={sorted(set(map(tuple, fed.splits)))}")


def timed_run(fed: Federation, label: str):
    """``run("elsa")`` with the host time of each global round; returns
    (history, telemetry collector)."""
    clock = _RoundClock()
    with tm.session(meta={"smoke": label}, sink=clock) as tel:
        t0 = time.perf_counter()
        hist = fed.run("elsa", **SMOKE_RUN)
        jax.block_until_ready(fed.last_theta)
        t_end = time.perf_counter()
    bounds = [t0] + clock.stamps[:len(hist["round"])]
    for i, (a, b) in enumerate(zip(bounds, bounds[1:])):
        note = (" (includes the run's profiling phase and compiling the "
                "round programs)" if i == 0 else "")
        print(f"[{label}] global round {i + 1}: {b - a:.3f} s wall{note}")
    print(f"[{label}] run total: {t_end - t0:.3f} s wall")
    compiled = tel.histograms.get(
        tm.flat_key("engine.dispatch_s", {"compiled": True}))
    if compiled is not None:
        print(f"[{label}] engine dispatches that compiled: {compiled.count}, "
              f"{compiled.sum:.3f} s wall including their first execution")
    sizes = fed.engine.compile_cache_sizes()
    print(f"[{label}] engine round executables (jit cache): "
          f"{sum(sizes.values())} over {len(sizes)} (split, prox) keys")
    return hist, tel


def check_finite(hist, label: str) -> None:
    losses = [float(x) for x in hist["loss"]]
    print(f"[{label}] loss history: {losses}")
    print(f"[{label}] final accuracy: {hist['final_accuracy']:.6f}")
    check(all(math.isfinite(x) for x in losses),
          f"{label}: non-finite round loss {losses}")
    for n, ls in hist["client_losses"].items():
        check(all(math.isfinite(x) for x in ls),
              f"{label}: client {n} has a non-finite local loss {ls}")


def local_step_parity(fed: Federation, client: int = 0) -> None:
    """``PARITY_STEPS`` local steps of ``client`` from the initial
    adapters, on the batched engine and on the sequential reference path
    (``client_steps``, what ``backend="reference"`` runs), from the same
    batches: the mean loss (step 1's forward and step 2's, after the
    first update) and every adapter leaf's update."""
    data = fed.data[client]

    def batches():
        return infinite_batches(data.tokens, data.labels,
                                fed.fed.batch_size, seed=4242)

    lora_b, loss_b = fed.group_steps([client], fed.lora0, PARITY_STEPS,
                                     {client: batches()})[client]
    lora_r, loss_r = fed.client_steps(client, fed.lora0, PARITY_STEPS,
                                      batches())
    delta = abs(loss_b - loss_r)
    bound = LOSS_RTOL * abs(loss_r)
    print(f"{PARITY_STEPS}-step mean loss, client {client}: "
          f"batched={loss_b!r} reference={loss_r!r} |delta|={delta!r} "
          f"bound={bound!r} (LOSS_RTOL={LOSS_RTOL})")
    check(math.isfinite(loss_b) and math.isfinite(loss_r),
          "local-step loss is not finite")
    check(delta <= bound,
          f"batched vs reference local-step loss differ by {delta} > {bound}")

    worst, worst_leaf, n_moved = 0.0, None, 0
    flat0 = jax.tree_util.tree_leaves_with_path(fed.lora0)
    for (path, p0), pb, pr in zip(flat0, jax.tree_util.tree_leaves(lora_b),
                                  jax.tree_util.tree_leaves(lora_r)):
        p0 = np.asarray(p0, np.float64)
        ub = np.asarray(pb, np.float64) - p0
        ur = np.asarray(pr, np.float64) - p0
        nr = np.linalg.norm(ur)
        check(np.all(np.isfinite(ub)),
              f"non-finite batched update at {jax.tree_util.keystr(path)}")
        if nr == 0.0:
            check(np.linalg.norm(ub) == 0.0,
                  f"batched moved {jax.tree_util.keystr(path)}, which the "
                  f"reference left unchanged")
            continue
        n_moved += 1
        rel = float(np.linalg.norm(ub - ur) / nr)
        if rel >= worst:
            worst, worst_leaf = rel, jax.tree_util.keystr(path)
    print(f"{PARITY_STEPS}-step adapter update, client {client}: "
          f"max per-leaf ||batched - reference|| / ||reference|| = "
          f"{worst!r} at {worst_leaf} over {n_moved} moved leaves "
          f"(UPDATE_RTOL={UPDATE_RTOL})")
    check(n_moved > 0, "the reference steps moved no adapter")
    check(worst <= UPDATE_RTOL,
          f"batched vs reference update differ by {worst} at {worst_leaf}")


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def smoke_one_chip(config: dict) -> None:
    compiles = _CompileLog()
    t0 = time.perf_counter()
    fed = Federation(FedConfig(**config), backend="batched")
    print(f"setup (params, data, channel plan): "
          f"{time.perf_counter() - t0:.3f} s wall")
    describe_model(fed)

    t0 = time.perf_counter()
    _, trust, cres, warm = fed.profile_clients()
    jax.block_until_ready(warm)
    print(f"profile_clients: {time.perf_counter() - t0:.3f} s wall "
          f"(includes compiling warm-up and probe programs); groups="
          f"{ {k: v for k, v in cres.groups.items() if v} } "
          f"trust={np.round(trust, 4).tolist()}")

    hist, _ = timed_run(fed, "1 device")
    print(f"XLA compiles through the run: {compiles.line()}")
    check_finite(hist, "1 device")
    local_step_parity(fed)
    print(f"XLA compiles in total: {compiles.line()}")
    print(f"peak_bytes_in_use: {peak_bytes()}")


def smoke_four_chips(config: dict) -> None:
    compiles = _CompileLog()
    n = len(jax.devices())
    check(n >= 4, f"--chips 4 needs 4 devices, JAX sees {n}")
    mesh = make_federation_mesh(4)
    fed_s = Federation(FedConfig(**config), backend="batched", mesh=mesh)
    describe_model(fed_s)
    hist_s, tel = timed_run(fed_s, "4-device mesh")
    check_finite(hist_s, "4-device mesh")
    for stack in ("lora", "batch"):
        h = tel.histograms.get(tm.flat_key("engine.client_axis_shards",
                                           {"stack": stack}))
        check(h is not None and h.count > 0,
              f"no dispatch recorded the {stack} stack placement")
        print(f"[4-device mesh] {stack} stack client axis split into "
              f"min {h.min:g} / max {h.max:g} pieces over {h.count} "
              f"dispatches")
        check(h.min == 4 and h.max == 4,
              f"{stack} stacks were not split across all 4 devices")
    frozen = jax.tree_util.tree_leaves(fed_s.engine.frozen)
    replicated = all(x.sharding.is_fully_replicated
                     and len(x.sharding.device_set) == 4 for x in frozen)
    print(f"[4-device mesh] frozen params replicated on 4 devices: "
          f"{replicated} ({len(frozen)} leaves)")
    check(replicated, "frozen params are not replicated on all 4 devices")
    del fed_s

    fed_1 = Federation(FedConfig(**config), backend="batched")
    hist_1, _ = timed_run(fed_1, "1 device")
    check_finite(hist_1, "1 device")
    a = np.asarray(hist_s["loss"], np.float64)
    b = np.asarray(hist_1["loss"], np.float64)
    diff = np.abs(a - b)
    print(f"loss history |sharded - one device| per round: "
          f"{diff.tolist()} bound {(LOSS_RTOL * np.abs(b)).tolist()} "
          f"(LOSS_RTOL={LOSS_RTOL})")
    check(a.shape == b.shape and bool(np.all(diff <= LOSS_RTOL * np.abs(b))),
          "sharded and one-device loss histories differ beyond LOSS_RTOL")
    print(f"XLA compiles in total: {compiles.line()}")
    print(f"peak_bytes_in_use (device 0): {peak_bytes()}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded round and its one-device "
                         "baseline")
    args = ap.parse_args()

    dev = device_record()
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']} jax={jax.__version__}")
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev['platform']}",
              file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            smoke_four_chips(SMOKE_CONFIG)
        else:
            smoke_one_chip(SMOKE_CONFIG)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"smoke wall time: {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
