"""Chip benchmark of whole ELSA fine-tuning jobs.

One run of one cell: build a ``Federation`` from the cell's
configuration and traffic files and the seed, run one whole warm job
(set-up: everything the window runs gets compiled or loaded from the
compile cache), then run whole jobs back to back for the window, then
check what the warm job produced against the plain reference
(``correctness``). Everything that belongs to one configuration, one
traffic mix or one metric lives in its own file and is found by name:

- ``configs/<config>.json``: the configuration as run, with its plain
  reference ``configs/<config>.ref.py`` and its limits
  ``configs/<config>.limits.json``;
- ``counts/<family>.py``: the operations and bytes of the layers and
  head of the configuration's ``family`` (``flops.py``);
- ``traffic/<traffic>.json``: the job's parameters;
- ``metrics/<metric>.py``: a reader ``read(ctx)`` returning a number,
  or ``None`` when it finds nothing to read.
"""
from __future__ import annotations

import contextlib
import gc
import json
import math
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

CACHE_DIR = ROOT / ".bench_jax_cache"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
ANNOTATION = "bench."


class CellError(Exception):
    """The cell cannot be run as described."""


# ---------------------------------------------------------------------------
# discovery
# ---------------------------------------------------------------------------

def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    ref_path: Path
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: Path = HERE

    def metric_names(self, trace: bool) -> List[str]:
        group = self.per_layer if trace else self.end_to_end
        return [m["name"] for m in group
                if "workloads" not in m or self.name in m["workloads"]]


def find_cell(workload: str, root: Path = ROOT,
              bench_dir: Path = HERE) -> Cell:
    """The workload's entry in ``BENCHMARK.json`` and the files it
    names."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise CellError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[w["config"]]
    cfg_path = root / entry["file"]
    config = load_json(cfg_path)
    stem = cfg_path.name[:-len(".json")]
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=load_json(bench_dir / "traffic" /
                                  f"{w['traffic']}.json"),
                limits=load_json(cfg_path.with_name(stem + ".limits.json")),
                ref_path=cfg_path.with_name(stem + ".ref.py"),
                end_to_end=bench["end_to_end"], per_layer=bench["per_layer"],
                bench_dir=bench_dir)


def load_metric(name: str, bench_dir: Path = HERE):
    import importlib.util
    path = bench_dir / "metrics" / f"{name}.py"
    if not path.exists():
        raise CellError(f"metric {name!r} has no reader {path}")
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------

def use_compile_cache(path: Path = CACHE_DIR) -> None:
    """JAX's persistent compilation cache in ``path``, set before the
    program is imported so that no compile opens another directory
    first (JAX opens the cache once, at the first compile).

    A directory of the benchmark's own, with no size cap: a cap that
    the environment sets (``JAX_COMPILATION_CACHE_MAX_SIZE``) evicts in
    least-recent order, and a cell whose programs outgrow it misses
    every one of them in every run; an entry another writer left
    without the access time a capped cache keeps makes every write
    fail. JAX's one-second threshold stands: the small programs compile
    again in each run (PERF.md)."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_compilation_cache_max_size", -1)


def import_program(root: Path = ROOT):
    src = root / "src"
    if not (src / "repro").is_dir():
        raise CellError(f"the program is not there: {src}/repro")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    from repro.federation.simulation import FedConfig, Federation
    return FedConfig, Federation


def fed_settings(cell: Cell) -> dict:
    """FedConfig keywords: the traffic's federation, the config's
    program keys and per-client batch. ``FedConfig.seed`` is the
    traffic's ``deployment_seed``: it draws the topology (hence every
    client's split), the data partition, the probe and test sets and
    the sketch hash, and with them the clustering, so every run of a
    cell trains the same groups at the same splits."""
    prog = dict(cell.config["program"])
    kw = dict(cell.traffic["federation"])
    kw["poisoned"] = tuple(kw.get("poisoned", ()))
    kw.update(model=prog.pop("model"), reduced=prog.pop("reduced"),
              dtype=prog.pop("dtype"),
              batch_size=cell.config["per_client_batch"],
              seed=cell.traffic["deployment_seed"])
    kw.update(prog)
    return kw


def _attribute(obj, dotted: str):
    """``obj.a.b`` for ``"a.b"``; ``None`` where the path is not
    there."""
    for name in dotted.split("."):
        obj = getattr(obj, name, None)
    return obj


def check_model(fed, config: dict) -> None:
    """The program's resolved architecture is the configuration file's.

    Seven checks hold for every file. A file's ``program_checks``,
    ``{"<dotted ArchConfig attribute>": "<config key>"}``, adds to them
    or replaces one, e.g. ``{"moe.expert_d_ff":
    "moe_intermediate_size"}``."""
    c = fed.cfg
    want = {"num_layers": config["num_hidden_layers"],
            "d_model": config["hidden_size"],
            "num_heads": config["num_attention_heads"],
            "num_kv_heads": config.get("num_key_value_heads",
                                       config["num_attention_heads"]),
            "d_ff": config["intermediate_size"],
            "vocab_size": config["vocab_size"],
            "param_dtype": config["torch_dtype"]}
    for attr, key in config.get("program_checks", {}).items():
        want[attr] = config[key]
    lora = config["lora"]
    got = {k: _attribute(c, k) for k in want}
    got_lora = (c.lora.rank, c.lora.alpha, tuple(c.lora.targets))
    if got != want or got_lora != (lora["rank"], lora["alpha"],
                                   tuple(lora["targets"])):
        raise CellError(f"program runs {got} {got_lora}, the configuration "
                        f"file states {want} {lora}")


def run_kwargs(cell: Cell) -> dict:
    t = cell.traffic
    return {"method": t["method"], "global_rounds": t["global_rounds"],
            "steps_per_round": t["steps_per_round"],
            "eval_every": t["eval_every"]}


# ---------------------------------------------------------------------------
# recording what the timed path produced, and where its time went
# ---------------------------------------------------------------------------

class _Recorded:
    """A round program that notes each call's losses and output."""

    def __init__(self, fn, split, sink):
        self._fn, self._split, self._sink = fn, split, sink

    def __call__(self, *args):
        out = self._fn(*args)
        self._sink(self._split, args, out)
        return out

    def _cache_size(self):
        return self._fn._cache_size()


class Instrument:
    """Wraps a Federation's layer entry points on the instance.

    ``capture=True`` (the set-up's warm job) keeps the first edge round
    after profiling: each member's per-step losses and final adapters,
    its batches, and the edge aggregate; and the job's second eval (its
    first, where it has one): the global adapters it was given and the
    logits it returned. ``annotate=True`` (the traced job) opens a
    profiler annotation around each layer call and notes each
    round-program dispatch's shape.
    """

    def __init__(self, fed, capture: bool = False, annotate: bool = False):
        self.fed, self.capture, self.annotate = fed, capture, annotate
        self.in_profile = False
        self.first: Optional[dict] = None
        self.agg: Optional[dict] = None
        self.eval: Optional[dict] = None
        self._eval_fn = None
        self.dispatches: List[dict] = []
        self._calls: Optional[list] = None
        self._patched_module = None

    def _ann(self, name):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(ANNOTATION + name)

    def install(self):
        fed, eng = self.fed, self.fed.engine
        orig = {"profile_clients": fed.profile_clients,
                "screened_aggregate": fed.screened_aggregate,
                "evaluate": fed.evaluate,
                "run_clients": eng.run_clients, "_round_fn": eng._round_fn}
        self._orig = orig

        def profile_clients(*a, **k):
            self.in_profile = True
            try:
                with self._ann("profile"):
                    return orig["profile_clients"](*a, **k)
            finally:
                self.in_profile = False

        def evaluate(*a, **k):
            with self._ann("eval"):
                out = orig["evaluate"](*a, **k)
            if self.capture and self._eval_fn is None:
                # the eval program exists once the first eval has run;
                # the next eval's inputs and logits are recorded
                self._eval_fn = fn = fed._eval_fn
                self._first_eval = host_tree(a[0])

                def recorded(frozen, lora, toks):
                    logits = fn(frozen, lora, toks)
                    if self.eval is None:
                        self.eval = {"lora": host_tree(lora),
                                     "logits": np.asarray(logits)}
                    return logits
                fed._eval_fn = recorded
            return out

        def screened_aggregate(clients, trees, weights, base):
            with self._ann("edge_agg"):
                out = orig["screened_aggregate"](clients, trees, weights,
                                                 base)
            if self.capture and self.agg is None:
                self.agg = {"clients": list(clients),
                            "weights": [float(w) for w in weights],
                            "out": out}
            return out

        def run_clients(theta, clients, splits, channels, batches, **kw):
            take = (self.capture and self.first is None
                    and not self.in_profile)
            if take:
                self._calls = []
            with self._ann("local_steps"):
                res = orig["run_clients"](theta, clients, splits, channels,
                                          batches, **kw)
            if take:
                self.first = {"clients": list(clients),
                              "splits": {n: splits[n] for n in clients},
                              "channels": {n: channels[n] for n in clients},
                              "batches": {n: list(batches[n])
                                          for n in clients},
                              "calls": self._calls, "results": res}
                self._calls = None
            return res

        def note(split, args, out):
            toks = args[4]
            self.dispatches.append({
                "profile": self.in_profile, "steps": int(toks.shape[0]),
                "clients": int(toks.shape[1]), "batch": int(toks.shape[2]),
                "seq": int(toks.shape[3])})
            if self._calls is not None:
                self._calls.append((split, out[1], out[0]))

        def round_fn(split, prox):
            return _Recorded(orig["_round_fn"](split, prox), split, note)

        fed.profile_clients = profile_clients
        fed.evaluate = evaluate
        fed.screened_aggregate = screened_aggregate
        eng.run_clients = run_clients
        eng._round_fn = round_fn
        if self.annotate:
            from repro.core import aggregation
            cloud = aggregation.cloud_aggregate

            def cloud_aggregate(*a, **k):
                with self._ann("cloud_agg"):
                    return cloud(*a, **k)
            aggregation.cloud_aggregate = cloud_aggregate
            self._patched_module = (aggregation, cloud)
        return self

    def uninstall(self):
        for name in ("profile_clients", "evaluate", "screened_aggregate"):
            delattr(self.fed, name)
        if self._eval_fn is not None:
            self.fed._eval_fn = self._eval_fn
        for name in ("run_clients", "_round_fn"):
            delattr(self.fed.engine, name)
        if self._patched_module is not None:
            mod, fn = self._patched_module
            mod.cloud_aggregate = fn

    def record(self, batch_size: int) -> dict:
        """The captured round on the host: members' padded batches,
        per-step losses and adapters, the aggregate and its weights."""
        import jax
        first, agg = self.first, self.agg
        if first is None or agg is None:
            raise CellError("the warm job ran no edge round to compare")
        if self.eval is None and self._eval_fn is None:
            raise CellError("the warm job ran no eval to compare")
        if self.eval is None:
            # a job with one eval: its program runs once more on that
            # eval's inputs
            lora = self._first_eval
            self.eval = {"lora": lora, "logits": np.asarray(self._eval_fn(
                self.fed.frozen, lora, np.asarray(self.fed.test_tokens)))}
        members = {}
        for split, losses, stack in first["calls"]:
            bucket = [n for n in first["clients"]
                      if first["splits"][n] == split]
            losses = np.asarray(jax.device_get(losses), np.float64)
            for i, n in enumerate(bucket):
                toks, labs, wts = pad_batches(first["batches"][n],
                                              batch_size)
                ssop = first["channels"][n].ssop
                members[n] = {"split": (split.p, split.q, split.o),
                              "rotation": np.asarray(ssop.v, np.float64),
                              "basis": np.asarray(ssop.u, np.float64),
                              "losses": losses[:, i],
                              "lora": host_tree(first["results"][n][0]),
                              "tokens": toks, "labels": labs,
                              "weights": wts}
        return {"members": members, "agg_clients": agg["clients"],
                "agg_weights": agg["weights"], "agg": host_tree(agg["out"]),
                "lora0": host_tree(self.fed.lora0),
                "probe": np.asarray(self.fed.probe),
                "eval_lora": self.eval["lora"],
                "eval_logits": np.asarray(self.eval["logits"], np.float64),
                "test_tokens": np.asarray(self.fed.test_tokens),
                "round_dispatches": sorted(
                    (d["clients"], d["steps"]) for d in self.dispatches
                    if not d["profile"])}


def host_tree(tree):
    import jax
    return jax.tree_util.tree_map(lambda a: np.asarray(a, np.float32),
                                  jax.device_get(tree))


def pad_batches(batches, batch_size):
    """(K, B, S) tokens, (K, B) labels, (K, B) weights: rows past a
    short batch are zero with weight 0."""
    k = len(batches)
    s = batches[0][0].shape[1]
    toks = np.zeros((k, batch_size, s), np.int32)
    labs = np.zeros((k, batch_size), np.int32)
    wts = np.zeros((k, batch_size), np.float32)
    for i, (t, l) in enumerate(batches):
        toks[i, :len(t)], labs[i, :len(l)], wts[i, :len(t)] = t, l, 1.0
    return toks, labs, wts


class CompileLog:
    """XLA backend compiles (or persistent-cache loads) in this process."""

    def __init__(self):
        import jax
        self.count, self.seconds, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1


# ---------------------------------------------------------------------------
# counting work
# ---------------------------------------------------------------------------

def job_work(cell: Cell, fed, hist) -> dict:
    """Tokens trained and model operations of one finished job."""
    import flops
    t, cfg = cell.traffic, cell.config
    f = t["federation"]
    b, s, k = cell.config["per_client_batch"], f["seq_len"], \
        t["steps_per_round"]
    sizes = [len(fed.data[n].tokens) for n in range(f["n_clients"])]
    round_rows = sum(flops.rows_drawn(sizes[n], b, len(ls) * k)
                     for n, ls in hist["client_losses"].items())
    warm_rows = sum(flops.rows_drawn(sizes[n], b, f["local_warmup_steps"])
                    for n in range(f["n_clients"]))
    train = flops.train_sequence(cfg, f, s)
    ops = (round_rows + warm_rows) * train \
        + f["n_clients"] * f["probe_q"] * flops.forward_sequence(
            cfg, f, s, logits=False) \
        + len(hist["round"]) * len(fed.test_tokens) * \
        flops.forward_sequence(cfg, f, s)
    finite = all(math.isfinite(x) for x in hist["loss"]) and all(
        math.isfinite(x) for ls in hist["client_losses"].values()
        for x in ls)
    return {"tokens": round_rows * s, "flops": float(ops),
            "rounds": len(hist["round"]), "finite": finite}


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

@dataclass
class Context:
    """What metric readers read."""
    cell: Cell
    peaks: dict
    setup_s: float = 0.0
    window_s: float = 0.0
    jobs: List[dict] = field(default_factory=list)
    memory_peak_bytes: Optional[int] = None
    telemetry: object = None
    traced_rounds: int = 0
    dispatches: List[dict] = field(default_factory=list)
    events: List[dict] = field(default_factory=list)
    trace_lo: float = 0.0
    trace_hi: float = 0.0

    @property
    def tokens(self) -> int:
        return sum(j["tokens"] for j in self.jobs)

    @property
    def flops(self) -> float:
        return sum(j["flops"] for j in self.jobs)

    def spans(self, name: str) -> List[float]:
        tel = self.telemetry
        if tel is None:
            return []
        recs = [s for r in tel.rounds for s in r["spans"]] + tel._spans
        return [s["dur_s"] for s in recs if s["name"] == name]


def device_record():
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peaks_for(kind: str, bench_dir: Path = HERE) -> dict:
    table = load_json(bench_dir / "peaks.json")
    if kind not in table:
        raise CellError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def memory_peak() -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def build(cell: Cell, seed: int):
    """The Federation of this cell, with its weights drawn from ``seed``.

    The program draws its weights with ``init_tree`` from
    ``PRNGKey(FedConfig.seed)``, leaf by leaf. Here the same function
    runs as one jitted call on the device from ``PRNGKey(seed)``, so the
    run's seed sets the weights and the traffic's deployment seed the
    rest (``fed_settings``)."""
    import jax
    FedConfig, Federation = import_program()
    from repro.federation import simulation
    init = simulation.init_tree

    def seeded_init(specs, _key, dtype):
        return jax.jit(lambda k: init(specs, k, dtype))(
            jax.random.PRNGKey(seed))
    simulation.init_tree = seeded_init
    try:
        fed = Federation(FedConfig(**fed_settings(cell)), backend="batched")
    finally:
        simulation.init_tree = init
    check_model(fed, cell.config)
    return fed


def warm_job(cell: Cell, fed):
    """The set-up's whole job, with the first edge round captured."""
    import jax
    inst = Instrument(fed, capture=True).install()
    try:
        hist = fed.run(**run_kwargs(cell))
        jax.block_until_ready(fed.last_theta)
    finally:
        inst.uninstall()
    return hist, inst.record(cell.config["per_client_batch"])


def traced_job(cell: Cell, ctx: Context, fed):
    """One more job with telemetry on and the profiler tracing."""
    import tempfile
    import jax
    import shutil
    from repro import telemetry as tm
    import trace_reduce as tr
    log_dir = tempfile.mkdtemp(prefix="elsa_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    inst = Instrument(fed, annotate=True).install()
    try:
        with tm.session(meta={"bench": cell.name}) as tel:
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation(ANNOTATION + "job"):
                    hist = fed.run(**run_kwargs(cell))
                    jax.block_until_ready(fed.last_theta)
            finally:
                jax.profiler.stop_trace()
    finally:
        inst.uninstall()
    ctx.telemetry, ctx.traced_rounds = tel, len(hist["round"])
    ctx.dispatches = inst.dispatches
    ctx.events = tr.load_events(tr.find_trace(log_dir), (ANNOTATION,))
    shutil.rmtree(log_dir, ignore_errors=True)
    span = tr.span_of(ctx.events, ANNOTATION + "job")
    if span is None:
        raise CellError("the trace holds no job annotation")
    ctx.trace_lo, ctx.trace_hi = span


def fed_params(cell: Cell, seed: int) -> dict:
    """What the reference needs of the federation: the weights' seed,
    the deployment seed (the sketch hash), the channel and the step."""
    f = cell.traffic["federation"]
    return {"seed": seed, "deployment_seed": cell.traffic["deployment_seed"],
            "ssop_r": f["ssop_r"], "sketch_y": f["sketch_y"],
            "sketch_z": f["sketch_z"], "rho": f["rho"], "lr": f["lr"],
            "num_classes": f["num_classes"]}


def reference_readings(cell: Cell, record: dict, seed: int, variant=None,
                       basis=None, rounds: bool = True):
    """The reference's evaluation of the captured round and eval.

    ``variant`` is ``None`` (float32 at HIGHEST), ``"control"`` (the
    same in bfloat16 at default precision, with its own SS-OP basis from
    its own probe forward), or a fault planted in the reference:
    ``"half_batch"`` (the second half of every batch left out, the mean
    over the rest), ``"token"`` (position 0 of every row altered, in the
    round's batches and in the eval's test rows), ``"unchanged"`` (every
    step returns its state unchanged: the members end at, and the eval
    is given, the initial adapters). ``rounds=False`` evaluates the eval
    alone.

    The SS-OP basis U is the top-r right singular subspace of the probe
    embeddings. Where singular values r and r+1 lie close, rounding
    alone turns it out of that subspace, and every number after it
    moves by 1e-2 to 2e-1 (measured on the chip and the CPU, PERF.md).
    So the round is recomputed with the basis the run used (``basis``,
    default the program's), and ``basis_check`` holds that basis to the
    reference's own singular values. The eval has no channel, so it
    takes nothing of the program but its adapters and test rows.
    """
    import jax
    import refkit
    model = refkit.load_model(cell.ref_path)
    num = refkit.CONTROL if variant == "control" else refkit.REFERENCE
    prec = "highest" if num is refkit.REFERENCE else "default"
    vocab = cell.config["vocab_size"]
    out = {}
    with jax.default_matmul_precision(prec):
        ref = refkit.Reference(cell.config, model, fed_params(cell, seed),
                               num)
        if variant == "control":
            u = ref.semantic_basis(record["probe"])
        else:
            u = jax.numpy.asarray(next(iter(record["members"].values()))[
                "basis"] if basis is None else basis, jax.numpy.float32)
        members = {}
        for n, m in (record["members"].items() if rounds else ()):
            toks, wts = m["tokens"], m["weights"]
            if variant == "half_batch":
                wts = wts.copy()
                wts[:, wts.shape[1] // 2:] = 0.0
            elif variant == "token":
                toks = toks.copy()
                toks[:, :, 0] = (toks[:, :, 0] + 1) % vocab
            losses, lora = ref.client_round(n, m["split"], u, toks,
                                            m["labels"], wts)
            members[n] = (losses, host_tree(
                ref.lora0 if variant == "unchanged" else lora))
        if rounds:
            agg = refkit.product_mean(
                [members[n][1] for n in record["agg_clients"]],
                record["agg_weights"])
            out.update(members=members, agg=host_tree(agg))
        test = record["test_tokens"]
        if variant == "token":
            test = test.copy()
            test[:, 0] = (test[:, 0] + 1) % vocab
        lora = ref.lora0 if variant == "unchanged" else record["eval_lora"]
        out.update(lora0=host_tree(ref.lora0),
                   basis=np.asarray(u, np.float64),
                   eval_logits=ref.eval_logits(lora, test))
    del ref
    gc.collect()
    return out


def probe_spectrum(cell: Cell, record: dict, seed: int):
    """The reference's own probe embeddings J (float32 at HIGHEST) and
    their singular values and right singular vectors, in float64."""
    import jax
    import refkit
    model = refkit.load_model(cell.ref_path)
    with jax.default_matmul_precision("highest"):
        ref = refkit.Reference(cell.config, model, fed_params(cell, seed),
                               refkit.REFERENCE)
        j = np.asarray(ref.probe_embeddings(record["probe"]), np.float64)
    del ref
    _, sigma, vt = np.linalg.svd(j, full_matrices=False)
    return j, sigma, vt


def shifted_basis(cell: Cell, record: dict, seed: int):
    """A wrong basis, for the fault that ``basis_gap`` has to catch:
    the reference's singular vectors 2 to r+1 in place of 1 to r."""
    _, _, vt = probe_spectrum(cell, record, seed)
    r = cell.traffic["federation"]["ssop_r"]
    return vt[1:r + 1].T


def basis_check(cell: Cell, record: dict, seed: int, basis) -> dict:
    """How far ``basis`` is from a top-r singular basis of the
    reference's own probe embeddings: ``basis_gap`` is the larger of
    the energy it misses, ``1 - |J U|^2 / sum of the top r sigma^2``,
    and its departure from orthonormality. That number does not change
    when U turns inside the top-r subspace, nor much when it swaps
    direction r for a close direction r+1. And ``rotation_gap``: the
    largest difference between each member's SS-OP rotation and the
    reference's ``V_n``. ``sigma_ratio`` (logged, not compared) is
    sigma_r / sigma_(r+1), how well the top-r subspace is defined."""
    import refkit
    j, sigma, _ = probe_spectrum(cell, record, seed)
    u = np.asarray(basis, np.float64)
    r = u.shape[1]
    miss = 1.0 - float(np.sum((j @ u) ** 2) / np.sum(sigma[:r] ** 2))
    ortho = float(np.max(np.abs(u.T @ u - np.eye(r))))
    rot = max(float(np.max(np.abs(m["rotation"] - refkit.rotation(n, r))))
              for n, m in record["members"].items())
    return {"basis_gap": max(miss, ortho), "rotation_gap": rot,
            "sigma_ratio": (float(sigma[r - 1] / sigma[r])
                            if len(sigma) > r else math.inf)}


def program_readings(record: dict) -> dict:
    return {"members": {n: (m["losses"], m["lora"])
                        for n, m in record["members"].items()},
            "agg": record["agg"], "lora0": record["lora0"],
            "eval_logits": record["eval_logits"]}


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, require_tpu: bool = True) -> dict:
    """One run of one cell; returns the result line's object."""
    import jax
    import correctness
    dev = device_record()
    if require_tpu and (dev["platform"] != "tpu" or
                        dev["count"] < cell.chips):
        raise CellError(f"needs {cell.chips} TPU chip(s); JAX found "
                        f"{dev['count']} {dev['platform']} device(s)")
    ctx = Context(cell=cell, peaks=(peaks_for(dev["kind"]) if require_tpu
                                    else {}))
    compiles = CompileLog()
    fed = build(cell, seed)
    hist, record = warm_job(cell, fed)
    ctx.setup_s = time.perf_counter() - t_start
    warm = job_work(cell, fed, hist)
    log(f"set-up: {ctx.setup_s:.3f} s, {compiles.count} XLA compiles or "
        f"cache loads ({compiles.seconds:.3f} s), {compiles.cache_hits} "
        f"persistent-cache hits; warm job {warm['tokens']} tokens, round "
        f"dispatches (clients, steps) {record['round_dispatches']}")

    before = compiles.count
    t0 = time.perf_counter()
    while True:
        h = fed.run(**run_kwargs(cell))
        ctx.jobs.append(job_work(cell, fed, h))
        if time.perf_counter() - t0 >= seconds:
            break
    ctx.window_s = time.perf_counter() - t0
    in_window = compiles.count - before
    log(f"window: {len(ctx.jobs)} jobs in {ctx.window_s:.3f} s, "
        f"{ctx.tokens} tokens, {in_window} XLA compiles inside the window")
    if trace:
        traced_job(cell, ctx, fed)
    ctx.memory_peak_bytes = memory_peak()
    del fed
    gc.collect()

    metrics = {}
    for name in cell.metric_names(trace):
        mod = load_metric(name, cell.bench_dir)
        value = mod.read(ctx)
        if value is not None and hasattr(mod, "bound"):
            log(f"{name}: {mod.bound(ctx)}-bound at the chip's peaks")
        if value is not None:
            unit = next(m["unit"] for m in cell.end_to_end + cell.per_layer
                        if m["name"] == name)
            metrics[name] = {"value": value, "unit": unit}

    ref = reference_readings(cell, record, seed)
    values = correctness.readings(program_readings(record), ref)
    values.update(basis_check(cell, record, seed, ref["basis"]))
    log(f"sigma_r / sigma_r+1 of the probe embeddings: "
        f"{values['sigma_ratio']!r}")
    values["window_compiles"] = float(in_window)
    limits = dict(cell.limits["limits"], window_compiles=0.0)
    ok, rows = correctness.verdict(values, limits)
    rows.append(("window_compiles", values["window_compiles"], 0.0))
    ok = ok and in_window == 0 and all(j["finite"] for j in ctx.jobs)
    device = dict(dev, memory_peak_bytes=ctx.memory_peak_bytes)
    out = {"correct": bool(ok), "attempted": len(ctx.jobs),
           "failed": sum(not j["finite"] for j in ctx.jobs),
           "metrics": metrics, "device": device}
    if trace:
        import trace_reduce as tr
        lo, hi = ctx.trace_lo, ctx.trace_hi
        device["busy_s"] = tr.busy_seconds(ctx.events, lo, hi)
        device["window_s"] = (hi - lo) / 1e9
        out["breakdown"] = {
            "device_ops": tr.top_ops(ctx.events, lo, hi),
            "idle_gaps": tr.idle_gaps(ctx.events, (ANNOTATION,), lo, hi)}
    out["checks"] = {k: {"value": v, "limit": lim} for k, v, lim in rows}
    log(f"compared at: update {values['update_gap_at']}; edge aggregate "
        f"{values['edge_agg_gap_at']}")
    for k, v, lim in rows:
        log(f"check {k} = {v!r} limit {lim!r}")
    return out
