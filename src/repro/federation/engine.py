"""Batched federation engine: vmap-over-clients split training.

The sequential reference in :mod:`repro.federation.simulation` simulates
one client at a time with un-jitted autodiff — wall-clock scales as
clients × rounds × steps with a host sync per client-step.  This engine
compiles one whole local round per split configuration:

- per-client LoRA pytrees are stacked along a leading client axis and the
  split-training gradient step (including the SS-OP∘sketch channel) is
  ``jax.vmap``-ed across every active client in the group;
- per-client SS-OP bases stack the same way (``SSOP`` is a pytree, so a
  stacked ``SSOP(u, v, w, w_inv)`` vmaps straight into the channel) while
  the ``SketchPlan`` — shared by all clients — is closed over once with
  its precomputed signed-selection tensor;
- the ``steps_per_round`` local-step loop is a ``jax.lax.scan`` over
  pre-gathered batch stacks from :mod:`repro.data.pipeline` (ragged
  epoch-tail batches are padded with zero-weight rows so every client
  shares one compiled shape);
- the round function is jit-compiled with the LoRA stack donated (on
  accelerators), so per-client losses come back as a single
  ``(steps, N)`` device array — one host sync per round instead of one
  per client-step.

Clients are bucketed by their ``Split`` configuration; each bucket
compiles once and is reused every round.  Cohorts are additionally
padded up to a small ladder of fixed sizes (:data:`BUCKET_LADDER`) with
zero-weight phantom clients, so schedulers that dispatch varying-size
ready sets (the deadline policy's straggler carry-over, churny async
rounds) reuse one compiled executable per (split, bucket size) instead
of recompiling for every distinct cohort size.  The FedProx anchor term
vectorizes by broadcasting the shared anchor tree against the
client-stacked parameters (:func:`repro.optim.fedprox_gradient`).

The client update supports the convergence stack (docs/convergence.md):
per-client global-norm gradient clipping (``clip_norm`` — vmapped along
the stacked client axis, so each client's cap is its own) and per-group
learning rates (``head_lr`` for every leaf outside the ``blocks`` /
``prefix`` adapter subtrees).  Both default off, in which case the
update is bit-identical to the historical ``p - lr * g``.

Passing ``mesh=`` (see :func:`repro.launch.mesh.make_federation_mesh`)
shards the stacked client axis across the mesh's ``("clients",)`` (or
``("pod", "clients")``) axes via :class:`jax.sharding.NamedSharding`:
the LoRA stacks, SS-OP stacks, and ``(steps, N, ...)`` batch stacks are
placed with their client dimension split across devices while the
frozen split-model parameters (and the FedProx anchor) stay replicated.
Because per-client computation is independent along the vmapped axis,
the round partitions without any cross-device collectives; cohorts pad
to bucket sizes divisible by the mesh's client-axis extent so the shard
split is even.  Sharding only changes array placement — the compiled
math, the compile count (one per (split, ladder size)), and the
single-device history are unchanged.

The engine is model-agnostic: it dispatches on the
:class:`~repro.models.split_api.SplitModel` protocol, so any registered
architecture (BERT encoder, dense causal LMs, ...) runs through the same
compiled path.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro import telemetry as tm
from repro.core.sketch import SketchPlan
from repro.core.split_training import Channel, Split, weighted_split_loss
from repro.core.ssop import SSOP
from repro.data.pipeline import stack_padded_batches
from repro.launch.mesh import client_axes
from repro.models.split_api import as_split_model
from repro.optim import (adapter_head_lr_tree, clip_by_global_norm,
                         fedprox_gradient)

PROX_MU = 0.01   # matches the reference path's hardcoded FedProx weight

#: Cohort sizes the engine compiles for.  Every size <= 8 is exact (small
#: federations and parity tests see zero padding); above that the ladder
#: grows geometrically (<= 25% padding waste), bounding the number of
#: compiled executables per split at O(log N) instead of O(N distinct
#: cohort sizes).
BUCKET_LADDER = (1, 2, 3, 4, 5, 6, 7, 8, 10, 12, 14, 16,
                 20, 24, 28, 32, 40, 48, 56, 64)


def bucket_size(n: int, multiple: int = 1) -> int:
    """Smallest ladder size >= n that is a multiple of ``multiple``
    (the mesh's client-axis extent, so shards split evenly).

    Beyond the top ladder entry the cohort rounds up to the next
    shard-multiple of ``n`` itself.  The old lcm(16, multiple) stepping
    over-padded large cohorts badly — e.g. 65 clients on a 3-shard mesh
    padded to 96 (48% phantom work) where 66 suffices — and population
    cohorts routinely exceed max(BUCKET_LADDER).
    """
    for s in BUCKET_LADDER:
        if s >= n and s % multiple == 0:
            return s
    return -(-n // multiple) * multiple


def placement_platform(mesh: Optional[Mesh] = None) -> str:
    """Platform the engine's arrays actually live on: the mesh's devices
    when sharding, the process default backend otherwise."""
    if mesh is not None:
        return mesh.devices.flat[0].platform
    return jax.default_backend()


def donate_buffers(platform: str) -> bool:
    """Whether to donate the LoRA stacks on this placement — CPU XLA has
    no donation support, so donating there only emits per-call
    warnings."""
    return platform != "cpu"


# ---------------------------------------------------------------------------
# stacked-pytree helpers
# ---------------------------------------------------------------------------

def is_client_map(theta) -> bool:
    """True when ``theta`` is a {client-id: tree} map (integer keys —
    Python or numpy ints, e.g. cohorts sampled via ``rng.choice``)
    rather than a single LoRA pytree (whose dict nodes have string
    keys)."""
    return isinstance(theta, dict) and bool(theta) and \
        all(isinstance(k, (int, np.integer)) and not isinstance(k, bool)
            for k in theta)


def stack_trees(trees: Sequence):
    """[tree, ...] -> one tree with a leading client axis on every leaf."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def broadcast_tree(tree, n: int):
    """Replicate a tree n times along a new leading client axis."""
    return jax.tree_util.tree_map(
        lambda p: jnp.broadcast_to(p[None], (n,) + p.shape), tree)


def index_tree(tree, i: int):
    """Slice client i out of a stacked tree (stays on device, lazy)."""
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def unstack_tree(tree, n: int) -> List:
    return [index_tree(tree, i) for i in range(n)]


def stack_ssops(ssops: Sequence[SSOP]) -> SSOP:
    """Stack per-client SS-OPs into one vmappable SSOP of (N, ...) leaves."""
    def field(name):
        vals = [getattr(s, name) for s in ssops]
        return None if vals[0] is None else jnp.stack(vals)
    return SSOP(u=field("u"), v=field("v"), w=field("w"),
                w_inv=field("w_inv"))


@jax.jit
def _screen_stats(stack, base, weights):
    """Per-client delta statistics for the screening stage: for each
    stacked client update vs the shared dispatch model ``base``, whether
    every leaf is finite, the global delta norm, and the cosine against
    the finite-masked weighted-mean delta of the cohort."""
    deltas = jax.tree_util.tree_map(
        lambda s, b: s.astype(jnp.float32) - b.astype(jnp.float32)[None],
        stack, base)
    leaves = jax.tree_util.tree_leaves(deltas)
    axes = lambda l: tuple(range(1, l.ndim))
    fin = jnp.ones(leaves[0].shape[0], bool)
    for l in leaves:
        fin = fin & jnp.all(jnp.isfinite(l), axis=axes(l))
    sq = sum(jnp.sum(l * l, axis=axes(l)) for l in leaves)
    norms = jnp.sqrt(sq)
    # cohort mean delta over finite updates only (NaN leaves zeroed so
    # one poisoned client can't poison the reference direction)
    wmask = jnp.asarray(weights, jnp.float32) * fin
    wsum = jnp.maximum(wmask.sum(), 1e-12)
    mean = [jnp.einsum("n,n...->...",
                       wmask, jnp.where(jnp.isfinite(l), l, 0.0)) / wsum
            for l in leaves]
    dot = sum(jnp.sum(l * m[None], axis=axes(l))
              for l, m in zip(leaves, mean))
    mnorm = jnp.sqrt(sum(jnp.sum(m * m) for m in mean))
    cos = dot / jnp.maximum(norms * mnorm, 1e-12)
    return fin, norms, cos


def screen_stats(base, trees: Sequence, weights: Sequence[float]):
    """Host-side wrapper of :func:`_screen_stats`: returns numpy
    ``(finite bool[N], delta_norm f64[N], cos f64[N])`` for a cohort of
    update trees against their dispatch model."""
    fin, norms, cos = _screen_stats(stack_trees(trees), base,
                                    jnp.asarray(list(weights), jnp.float32))
    return (np.asarray(fin), np.asarray(norms, np.float64),
            np.asarray(cos, np.float64))


def _axis_pieces(arr: jax.Array, axis: int) -> int:
    """Number of shards ``arr``'s placement splits ``axis`` into."""
    return arr.shape[axis] // arr.sharding.shard_shape(arr.shape)[axis]


def _pad_axis1(arr: np.ndarray, pad: int) -> np.ndarray:
    """Append ``pad`` zero rows along the client axis (axis 1)."""
    z = np.zeros((arr.shape[0], pad) + arr.shape[2:], arr.dtype)
    return np.concatenate([arr, z], axis=1)


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

class BatchedEngine:
    """Compiled vmap/scan executor for one federation's local rounds.

    One instance per :class:`~repro.federation.simulation.Federation`;
    round functions are cached per (Split, prox) and shape-specialized by
    jit, so steady-state rounds run with zero retracing.
    """

    def __init__(self, model, frozen, plan: Optional[SketchPlan], *,
                 lr: float, batch_size: int, use_channel: bool,
                 use_ssop: bool, prox_mu: float = PROX_MU,
                 pad_cohorts: bool = True, mesh: Optional[Mesh] = None,
                 head_lr: Optional[float] = None, clip_norm: float = 0.0):
        self.model = as_split_model(model)
        self.cfg = self.model.cfg
        self.frozen = frozen
        self.plan = plan
        self.lr = lr
        self.head_lr = head_lr       # None -> lr (single-group legacy)
        self.clip_norm = clip_norm   # 0 -> no per-client gradient clipping
        self.batch_size = batch_size
        self.use_channel = use_channel
        self.use_ssop = use_ssop
        self.prox_mu = prox_mu
        self.pad_cohorts = pad_cohorts
        self.mesh = mesh
        self.platform = placement_platform(mesh)
        self.donate = donate_buffers(self.platform)
        self.n_shards = 1
        if mesh is not None:
            if "clients" not in mesh.shape:
                # a pod-only match (e.g. the multi-pod production mesh)
                # would silently replicate every stack across the other
                # axes' devices, so require the real federation axis
                raise ValueError(
                    "federation mesh needs a 'clients' axis; got axes "
                    f"{tuple(mesh.shape)} — build it with "
                    "repro.launch.mesh.make_federation_mesh")
            axes = client_axes(mesh)
            for a in axes:
                self.n_shards *= mesh.shape[a]
            spec = axes[0] if len(axes) == 1 else axes
            # leading client axis split across devices; step axis of the
            # (steps, N, ...) batch stacks stays unsharded
            self._shard_clients = NamedSharding(mesh, PartitionSpec(spec))
            self._shard_batches = NamedSharding(mesh,
                                                PartitionSpec(None, spec))
            self._replicate = NamedSharding(mesh, PartitionSpec())
            # frozen split-model params are read-only every round:
            # replicate them once up front
            self.frozen = jax.device_put(frozen, self._replicate)
        self._round_fns: Dict = {}
        if tm.enabled():
            tm.set_gauge("engine.donate_buffers", float(self.donate),
                         platform=self.platform)
            tm.set_gauge("engine.n_shards", float(self.n_shards),
                         platform=self.platform)

    # -- compiled round function per split configuration -------------------
    def _round_fn(self, split: Split, prox: bool):
        key = (split, prox)
        if key in self._round_fns:
            return self._round_fns[key]

        model, plan = self.model, self.plan
        lr, mu = self.lr, self.prox_mu
        head_lr, clip_norm = self.head_lr, self.clip_norm
        with_ssop = self.use_channel and self.use_ssop
        chan_plan = plan if self.use_channel else None

        def per_client(frozen, lora, ssop, tok, lab, wt):
            channel = Channel(ssop if with_ssop else None, chan_plan)
            batch = {"tokens": tok, "labels": lab, "weights": wt}
            return jax.value_and_grad(
                lambda lp: weighted_split_loss(model, frozen, lp, batch,
                                               split, channel))(lora)

        def round_fn(frozen, lora_stack, ssop_stack, anchor,
                     tokens, labels, weights):
            ssop_axis = 0 if ssop_stack is not None else None
            # per-leaf python-float lrs (adapter vs head groups); with
            # head_lr=None every leaf is exactly `lr`, so the update
            # below stays bit-identical to the historical `p - lr * g`
            lrs = adapter_head_lr_tree(lora_stack, lr, head_lr)

            def step(stack, xs):
                tok, lab, wt = xs
                losses, grads = jax.vmap(
                    per_client,
                    in_axes=(None, 0, ssop_axis, 0, 0, 0))(
                        frozen, stack, ssop_stack, tok, lab, wt)
                if prox:
                    grads = fedprox_gradient(grads, stack, anchor, mu)
                if clip_norm > 0:
                    # per-client global-norm clip along the stacked axis
                    grads = jax.vmap(
                        lambda g: clip_by_global_norm(g, clip_norm))(grads)
                stack = jax.tree_util.tree_map(
                    lambda p, g, s: p - s * g, stack, grads, lrs)
                return stack, losses

            final, losses = jax.lax.scan(step, lora_stack,
                                         (tokens, labels, weights))
            return final, losses          # losses: (steps, N)

        # donate the stacked LoRA buffers (in-place round update) when the
        # arrays' actual placement supports it — gate on where the stacks
        # live (mesh devices when sharding), not the process default
        # backend, which can disagree with the placement
        fn = jax.jit(round_fn, donate_argnums=(1,) if self.donate else ())
        self._round_fns[key] = fn
        return fn

    def compile_cache_sizes(self) -> Dict[Tuple[Split, bool], int]:
        """Compiled-executable count per (split, prox) round function —
        how many distinct cohort shapes each has specialized for."""
        return {k: fn._cache_size() for k, fn in self._round_fns.items()}

    # -- public API --------------------------------------------------------
    def run_clients(self, theta, clients: Sequence[int],
                    splits: Dict[int, Split], channels: Dict[int, Channel],
                    batches: Dict[int, List[Tuple[np.ndarray, np.ndarray]]],
                    prox_anchor=None,
                    per_client_theta: Optional[bool] = None
                    ) -> Dict[int, Tuple[object, float]]:
        """Run one local round for every client, batched per split bucket.

        ``theta`` is one shared LoRA tree broadcast to every client, or
        a ``{client: tree}`` dict of per-client starting points (the
        fused cross-group dispatch stacks clients that carry different
        edge models into one round).  Callers that know which form they
        pass should say so via ``per_client_theta``; the default sniffs
        the dict's key types (:func:`is_client_map`), which is only safe
        while no registered model's LoRA pytree is integer-keyed.
        ``batches[n]`` is the client's pre-drawn list of ``steps``
        (tokens, labels) batches (its iterator order is preserved).
        ``channels`` maps each cohort slot to the channel of the
        *identity* occupying it this round (``Federation.group_steps``
        resolves occupants through the population's identity-keyed
        channel LRU; without a population, identity == slot) — the
        engine stacks whatever per-slot SS-OPs it is handed, so the
        privacy rotation inside a compiled bucket follows the client,
        not the slot index.
        Returns ``{client: (updated lora tree, mean local loss)}``; the
        loss arrays of all buckets are fetched in a single host sync.
        Buckets are padded up to the next :data:`BUCKET_LADDER` size with
        zero-weight phantom clients (exactly-zero loss and gradients),
        so varying cohort sizes hit a bounded set of compiled shapes.
        With a mesh, bucket sizes are additionally multiples of the
        client-axis extent and every client-stacked input is placed with
        its leading axis sharded across the mesh.
        """
        per_client = (is_client_map(theta) if per_client_theta is None
                      else per_client_theta)
        buckets: Dict[Split, List[int]] = {}
        for n in clients:
            buckets.setdefault(splits[n], []).append(n)
        if self.mesh is not None and prox_anchor is not None:
            prox_anchor = jax.device_put(prox_anchor, self._replicate)

        pending = []
        for split, members in buckets.items():
            # padding and stacking the batches, LoRA and SS-OP trees, and
            # their copies to the device
            with tm.span("engine.stack", n_clients=len(members)):
                toks, labs, wts = stack_padded_batches(
                    [batches[n] for n in members], self.batch_size)
                n_real = len(members)
                size = (bucket_size(n_real, self.n_shards)
                        if self.pad_cohorts
                        else -(-n_real // self.n_shards) * self.n_shards)
                if size > n_real:
                    pad = size - n_real
                    toks = _pad_axis1(toks, pad)
                    labs = _pad_axis1(labs, pad)
                    wts = _pad_axis1(wts, pad)   # zero weights: inert rows
                if per_client:
                    # per-client starting points; phantom rows repeat the
                    # last member (zero weights keep them inert)
                    trees = [theta[n] for n in members]
                    trees += [theta[members[-1]]] * (size - n_real)
                    lora_stack = stack_trees(trees)
                else:
                    lora_stack = broadcast_tree(theta, size)
                ssop_stack = None
                if self.use_channel and self.use_ssop:
                    ssops = [channels[n].ssop for n in members]
                    ssops += [ssops[-1]] * (size - n_real)   # phantom rows
                    ssop_stack = stack_ssops(ssops)
                if self.mesh is not None:
                    lora_stack = jax.device_put(lora_stack,
                                                self._shard_clients)
                    if ssop_stack is not None:
                        ssop_stack = jax.device_put(ssop_stack,
                                                    self._shard_clients)
                    toks, labs, wts = jax.device_put(
                        (toks, labs, wts), self._shard_batches)
                else:
                    toks, labs, wts = (jnp.asarray(toks), jnp.asarray(labs),
                                       jnp.asarray(wts))
            fn = self._round_fn(split, prox_anchor is not None)
            tel = tm.get()
            before = fn._cache_size() if tel is not None else 0
            # the call only enqueues the round on an asynchronous
            # backend: the host waits for it in engine.fetch
            with tm.span("engine.dispatch"):
                t0 = time.perf_counter()
                out_stack, losses = fn(self.frozen, lora_stack, ssop_stack,
                                       prox_anchor, toks, labs, wts)
                dur = time.perf_counter() - t0
            if tel is not None:
                # the jit cache growing across this dispatch means a
                # fresh trace+compile for this (split, cohort-bucket)
                # shape; steady state stays at one executable per
                # (split, bucket)
                lbl = f"p{split.p}q{split.q}o{split.o}"
                prox_l = prox_anchor is not None
                compiled = fn._cache_size() > before
                if compiled:
                    tm.inc("engine.jit_compiles", 1, split=lbl,
                           bucket=size, prox=prox_l)
                tm.observe("engine.dispatch_s", dur, compiled=compiled)
                tm.inc("engine.clients", n_real)
                tm.inc("engine.phantom_rows", size - n_real)
                # placement as dispatched: how many pieces the client
                # axis of each stack is really split into (min/max over
                # every dispatch)
                tm.observe("engine.client_axis_shards",
                           _axis_pieces(jax.tree_util.tree_leaves(
                               lora_stack)[0], 0), stack="lora")
                tm.observe("engine.client_axis_shards",
                           _axis_pieces(toks, 1), stack="batch")
                tm.set_gauge("engine.compile_cache", fn._cache_size(),
                             split=lbl, prox=prox_l)
            pending.append((members, out_stack, losses))

        # one host sync for every bucket's (steps, N) loss array
        with tm.span("engine.fetch"):
            loss_host = jax.device_get([l for (_, _, l) in pending])
        tm.inc("host.syncs", 1, site="engine.fetch")
        results: Dict[int, Tuple[object, float]] = {}
        with tm.span("engine.unstack"):
            for (members, out_stack, _), ls in zip(pending, loss_host):
                per_client = ls.mean(axis=0)                     # (N,)
                for i, n in enumerate(members):
                    results[n] = (index_tree(out_stack, i),
                                  float(per_client[i]))
        return results
