"""End-to-end ELSA federation simulation (Alg. 1) plus FL baselines.

Runs the *real* machinery end to end on a reduced model (or, with
``FedConfig(reduced=False)``, at the published config's widths): behavioral
fingerprinting on a public probe set, trust scoring, latency-aware spectral
clustering, per-client dynamic splits, split training through the
SS-OP∘sketch channel, edge FedAvg, and coherence/trust-weighted cloud
fusion with the Eq. 16 stopping rule.

The harness is model-agnostic: ``FedConfig.model`` names any architecture
registered in :mod:`repro.models.split_api` (the paper's ``"bert-base"``
encoder by default, or a dense causal LM such as ``"llama3-8b"``), and
every phase — warmup, fingerprinting, split training, evaluation —
dispatches through the :class:`~repro.models.split_api.SplitModel`
protocol.

Two execution backends share this harness (``Federation(...,
backend=...)``):

- ``"batched"`` (default): the :mod:`repro.federation.engine` compiled
  path — clients stacked along a leading axis, ``vmap``-ed gradient
  steps, ``lax.scan`` over local steps, one host sync per round;
- ``"reference"``: the original one-client-at-a-time eager loop, kept
  bit-comparable for parity tests and as the benchmark baseline.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry as tm
from repro.core import aggregation as agg
from repro.core import clustering as clus
from repro.core import splitting as split_mod
from repro.core.fingerprint import divergence_matrix, fingerprint
from repro.core.sketch import make_plan
from repro.core.split_training import Channel, Split, split_loss
from repro.core.ssop import make_ssop, make_ssop_from_basis, semantic_subspace
from repro.core.trust import trust_scores
from repro.data.pipeline import infinite_batches
from repro.data.probe import make_probe_set
from repro.data.synthetic import SyntheticTaskConfig, make_federation_data, make_test_set
from repro.federation.engine import (BatchedEngine, is_client_map,
                                     stack_trees)
from repro.federation.topology import make_topology
from repro.models.params import init_tree
from repro.models.split_api import get_split_model
from repro.optim import (SGD, AdamW, FedAdam, FedProx, FedAMS,
                         adapter_head_lr_tree, clip_by_global_norm,
                         fedprox_gradient)


@dataclasses.dataclass
class FedConfig:
    n_clients: int = 20
    n_edges: int = 4
    alpha: float = 0.1                   # Dirichlet concentration
    poisoned: tuple = (3, 8, 12, 17)     # 4 unreliable clients (§IV.A)
    total_examples: int = 4000
    batch_size: int = 16
    t_rounds: int = 2                    # client-edge rounds per global agg
    probe_q: int = 32
    tau_max: float = 200.0
    gamma: float = 1.0
    w_min: float = 0.25
    lr: float = 5e-3
    ssop_r: int = 8
    sketch_y: int = 3
    sketch_z: int = 0                    # 0 -> derive from rho
    rho: float = 2.1
    xi: float = 1e-4                     # Eq. 16 threshold
    local_warmup_steps: int = 10         # steps before fingerprinting
    seed: int = 0
    num_classes: int = 4
    use_channel: bool = True
    use_ssop: bool = True
    model: str = "bert-base"             # split-model registry name
    reduced: bool = True                 # False: the published config's
                                         # widths instead of reduced()
    layers: Optional[int] = None         # model depth; None -> 8 when
                                         # reduced, else the config's own
    bert_layers: Optional[int] = None    # DEPRECATED: use ``layers=``
    seq_len: int = 24                    # synthetic-task sequence length
    class_sharpness: float = 4.0         # synthetic-task separability
    background_frac: float = 0.5         # synthetic-task noise fraction
    cls_token: int = -1                  # >= 0: constant [CLS] at pos 0
    constrained_frac: float = 0.0        # fraction of slow/throttled devices
                                         # (paper §IV.A heterogeneity setup)
    dtype: str = "float32"               # params+activations; parity tests
                                         # use float64 (needs jax x64 mode)
    # -- convergence stack (docs/convergence.md) -------------------------
    aggregate: str = "product"           # LoRA aggregation space:
                                         # "product" (weight-delta mean,
                                         # anchored pinv re-fit) or
                                         # "factor" (legacy leafwise
                                         # mean, golden-pinned)
    clip_norm: float = 0.0               # >0: per-client global-norm clip
    head_lr: float = 0.0                 # >0: readout-head lr (adapters
                                         # keep ``lr``); 0 -> ``lr``
    server_opt: str = "none"             # cloud pseudo-gradient step:
                                         # "none" | "fedadam" | "fedams"
                                         # (overrides the method default)
    server_lr: float = 0.05              # server-opt lr (FedAdam tuning)
    pooling: str = "cls"                 # encoder readout: "cls" | "mean"
    vocab_size: int = 0                  # >0: override the model vocab
                                         # (small-vocab synthetic tasks)
    # -- update screening (docs/robustness.md); off by default and
    #    bit-inert when disabled (golden-pinned) ------------------------
    screen: bool = False                 # server-side update screening
    screen_norm_k: float = 4.0           # reject ||delta|| > k * median
    screen_cos_min: float = -0.5         # reject cos(delta, cohort mean)
                                         # below this (sign-flip catch)
    screen_trust_beta: float = 0.7       # trust-EMA retention
    screen_trust_floor: float = 0.15     # exclude trust EMA below this
    screen_min_cohort: int = 2           # fewer survivors -> trimmed mean
    screen_trim_frac: float = 0.25       # fallback per-side trim fraction

    def __post_init__(self):
        if self.aggregate not in ("product", "factor"):
            raise ValueError(f"unknown aggregate mode {self.aggregate!r}")
        if not 0.0 <= self.screen_trust_beta <= 1.0:
            raise ValueError("screen_trust_beta must be in [0, 1], "
                             f"got {self.screen_trust_beta}")
        if not 0.0 <= self.screen_trim_frac < 0.5:
            raise ValueError("screen_trim_frac must be in [0, 0.5), "
                             f"got {self.screen_trim_frac}")
        if self.server_opt not in ("none", "fedadam", "fedams"):
            raise ValueError(f"unknown server_opt {self.server_opt!r}")
        if self.pooling not in ("cls", "mean"):
            raise ValueError(f"unknown pooling {self.pooling!r}")
        # warn only when the deprecated spelling actually carries intent:
        # after resolution bert_layers mirrors layers, so reconstruction
        # round-trips (dataclasses.replace / FedConfig(**asdict(...)))
        # stay warning-free
        if self.bert_layers is not None and self.layers != self.bert_layers:
            warnings.warn(
                "FedConfig.bert_layers is deprecated; use FedConfig.layers "
                "(the federation is model-agnostic now)",
                DeprecationWarning, stacklevel=3)
            if self.layers is None:
                self.layers = self.bert_layers
        if self.layers is None and self.reduced:
            self.layers = 8
        self.bert_layers = self.layers   # keep legacy readers consistent


class Federation:
    """Simulation harness; ``run(method)`` with method in
    {'elsa', 'elsa-fixed', 'elsa-nocluster', 'fedavg', 'fedavg-random',
    'fedprox', 'fedams', 'vanilla'}.

    ``backend="batched"`` runs local training through the compiled
    vmap/scan engine; ``backend="reference"`` keeps the sequential eager
    path (parity baseline).  ``mesh=`` (built with
    :func:`repro.launch.mesh.make_federation_mesh`) shards the engine's
    stacked client axis across a device mesh; the default ``None`` keeps
    every round single-device.
    """

    def __init__(self, fed: FedConfig = FedConfig(),
                 backend: str = "batched", mesh=None):
        if backend not in ("batched", "reference"):
            raise ValueError(f"unknown backend {backend!r}")
        if mesh is not None and backend != "batched":
            raise ValueError("mesh sharding requires backend='batched'")
        self.backend = backend
        self.mesh = mesh
        self.fed = fed
        overrides = {}
        if fed.vocab_size:
            overrides["vocab_size"] = fed.vocab_size
        self.model = get_split_model(fed.model, num_layers=fed.layers,
                                     dtype=fed.dtype, reduced=fed.reduced,
                                     pooling=(fed.pooling
                                              if fed.pooling != "cls"
                                              else None),
                                     **overrides)
        self.cfg = self.model.cfg
        self.task = SyntheticTaskConfig(vocab_size=self.cfg.vocab_size,
                                        num_classes=fed.num_classes,
                                        seq_len=fed.seq_len,
                                        class_sharpness=fed.class_sharpness,
                                        background_frac=fed.background_frac,
                                        cls_token=fed.cls_token,
                                        seed=fed.seed)
        self.topo = make_topology(fed.n_clients, fed.n_edges,
                                  constrained_frac=fed.constrained_frac,
                                  seed=fed.seed)
        self.data = make_federation_data(
            self.task, fed.n_clients, fed.total_examples, fed.alpha,
            poisoned_clients=fed.poisoned, seed=fed.seed,
            task_kind=self.model.task)
        self.test_tokens, self.test_labels = make_test_set(self.task, 512,
                                                           seed=fed.seed + 7)
        self.probe = make_probe_set(self.task, fed.probe_q, seed=fed.seed + 3)
        self.policy = split_mod.SplitPolicy(
            num_blocks=self.cfg.num_layers, o_fix=2, p_min=1,
            p_max=min(5, self.cfg.num_layers - 3))
        self.splits = split_mod.splits_for_population(
            self.topo.capacity, self.topo.bandwidth, self.policy)

        key = jax.random.PRNGKey(fed.seed)
        specs = self.model.specs(fed.num_classes)
        tree = init_tree(specs, key, jnp.dtype(fed.dtype))
        self.frozen, self.lora0 = tree["frozen"], tree["lora"]

        d = self.cfg.d_model
        z = fed.sketch_z or max(4, int(d / (fed.rho * fed.sketch_y)))
        self.plan = make_plan(d, fed.sketch_y, z, seed=fed.seed + 11)

        self._loss_grad_cache: Dict = {}
        # identity-keyed channels (identity == slot without a bound
        # population; with one, channel_for routes through the
        # population's identity LRU and this dict stays empty)
        self._channels: Dict[int, Channel] = {}
        self._ref_basis = None
        self._engine: Optional[BatchedEngine] = None
        self._probe_fn = None
        self._eval_fn = None

        # update screening (docs/robustness.md): the ledger always
        # exists (cheap, checkpointed), the screening stage only runs
        # when fed.screen is on — the off path stays golden bit-inert
        from repro.core.screening import ScreeningConfig, TrustLedger
        self.screening = ScreeningConfig(
            norm_k=fed.screen_norm_k, cos_min=fed.screen_cos_min,
            trust_floor=fed.screen_trust_floor,
            min_cohort=fed.screen_min_cohort,
            trim_frac=fed.screen_trim_frac)
        self.trust_ledger = TrustLedger(fed.n_clients,
                                        beta=fed.screen_trust_beta)
        self.screen_log: List = []
        # registry-backed population binding (docs/population.md);
        # installed by run(population=...) / the runtime schedulers
        self._population = None

    @property
    def engine(self) -> BatchedEngine:
        """Lazily-built compiled round executor (batched backend)."""
        if self._engine is None:
            self._engine = BatchedEngine(
                self.model, self.frozen, self.plan, lr=self.fed.lr,
                batch_size=self.fed.batch_size,
                use_channel=self.fed.use_channel,
                use_ssop=self.fed.use_ssop, mesh=self.mesh,
                head_lr=self.fed.head_lr or None,
                clip_norm=self.fed.clip_norm)
        return self._engine

    def server_optimizer(self, method: str):
        """Cloud pseudo-gradient optimizer, shared by the round loop and
        every runtime scheduler (so `policy="sync"` parity holds under
        any server-opt config).  ``FedConfig.server_opt`` overrides the
        method default; the legacy ``method="fedams"`` baseline keeps
        its historical untuned FedAMS(lr=1.0)."""
        fed = self.fed
        if fed.server_opt == "fedadam":
            return FedAdam(lr=fed.server_lr)
        if fed.server_opt == "fedams":
            return FedAMS(lr=fed.server_lr)
        return FedAMS(lr=1.0) if method == "fedams" else None

    def _default_split(self) -> Split:
        return Split(self.policy.p_max,
                     self.cfg.num_layers - self.policy.p_max - 2, 2)

    def split_for(self, client: int, use_split: bool = True) -> Split:
        """The tripartite split client ``client`` trains (and is billed
        for, in the event-driven runtime's cost model)."""
        return (Split(*self.splits[client]) if use_split
                else self._default_split())

    def client_weight(self, client: int) -> int:
        """FedAvg weight: the example count of the client currently
        occupying slot ``client`` (with a bound population the occupant
        is whatever registered id the round's cohort mapped there)."""
        if self._population is not None:
            return self._population.slot_weight(client)
        return len(self.data[client].tokens)

    def _bind_population(self, population):
        """Attach a registry-backed population for this run.  Accepts a
        :class:`~repro.population.PopulationConfig` (builds the runtime)
        or a prebuilt :class:`~repro.population.PopulationRuntime`;
        ``None`` detaches (the bit-inert legacy dict path)."""
        if population is None:
            self._population = None
            return None
        from repro.population import PopulationConfig, PopulationRuntime
        if isinstance(population, PopulationConfig):
            population = PopulationRuntime(self, population)
        elif not isinstance(population, PopulationRuntime):
            raise TypeError(
                f"population must be a PopulationConfig or "
                f"PopulationRuntime, got {type(population).__name__}")
        if population.federation is not self:
            raise ValueError("population is bound to a different federation")
        self._population = population
        return population

    # ------------------------------------------------------------------
    def channel_for(self, client: int, lora, emb=None) -> Channel:
        """Lazily build the client's SS-OP∘sketch channel.

        Channels are keyed by client *identity*: with a bound population
        ``client`` is a slot index and the call resolves through the
        population's identity-keyed channel LRU (the slot's occupant,
        :meth:`~repro.population.PopulationRuntime.channel_for_slot`);
        without one, identity == slot and the channel lives in the
        legacy ``_channels`` dict.

        ``emb`` lets callers share one probe forward across clients that
        create their channels from the same lora (the probe embeddings
        depend only on (lora, probe), not the client; only the seeded
        V_n rotation is per-client).
        """
        if not self.fed.use_channel:
            return Channel(None, None)
        if self._population is not None:
            return self._population.channel_for_slot(client)
        if client not in self._channels:
            if emb is None:
                emb = self._probe_embeddings(lora)
            ss = (make_ssop(emb, self.fed.ssop_r, "elsa-salt", client)
                  if self.fed.use_ssop else None)
            self._channels[client] = Channel(ss, self.plan)
        return self._channels[client]

    def _probe_embeddings(self, lora):
        return self.model.probe_repr(self.frozen, lora,
                                     jnp.asarray(self.probe))

    def _reference_basis(self):
        """Shared semantic basis for identity-keyed channels: top-r SVD
        of the *reference model's* probe embeddings, computed once.  In
        every golden-pinned path legacy channels are built from
        ``lora0`` embeddings too (elsa profiles from ``lora0``; the
        plain loops build lazily at round 0 where theta == ``lora0``),
        so the fixed basis is what makes an identity cohort bit-inert —
        and what makes an evicted identity's channel regenerate
        bit-exactly regardless of when it returns."""
        if self._ref_basis is None:
            self._ref_basis = semantic_subspace(
                self._probe_embeddings(self.lora0), self.fed.ssop_r)
        return self._ref_basis

    def _build_identity_channel(self, cid: int) -> Channel:
        """One registered identity's channel: shared reference basis +
        its own seeded rotation (Eq. 18 keyed on the id)."""
        ss = (make_ssop_from_basis(self._reference_basis(), "elsa-salt",
                                   cid)
              if self.fed.use_ssop else None)
        return Channel(ss, self.plan)

    # ------------------------------------------------------------------
    def _grad_fn(self, client: int, split: Split):
        # keyed on (client, split, use_ssop, use_channel) — NOT id(channel):
        # id() of a collected Channel can be reused by a new object, which
        # would silently pair a client with a stale cached loss
        key = (client, split.p, split.q, split.o,
               self.fed.use_ssop, self.fed.use_channel)
        if key not in self._loss_grad_cache:
            def loss(lora, batch, channel):
                return split_loss(self.model, self.frozen, lora, batch,
                                  split, channel)
            self._loss_grad_cache[key] = jax.value_and_grad(loss)
        return self._loss_grad_cache[key]

    def client_steps(self, client: int, lora, n_steps: int,
                     it, use_split=True, prox_anchor=None):
        """Run local training steps; returns (lora, mean loss).

        Sequential reference path: eager autodiff, one host sync per
        step.  The batched backend runs :meth:`group_steps` instead.
        """
        fed = self.fed
        split = (Split(*self.splits[client]) if use_split
                 else self._default_split())
        channel = self.channel_for(client, lora)
        gfn = self._grad_fn(client, split)
        lrs = adapter_head_lr_tree(lora, fed.lr, fed.head_lr or None)
        losses = []
        for _ in range(n_steps):
            tok, lab = next(it)
            batch = {"tokens": jnp.asarray(tok), "labels": jnp.asarray(lab)}
            lv, g = gfn(lora, batch, channel)
            if prox_anchor is not None:
                g = fedprox_gradient(g, lora, prox_anchor, 0.01)
            if fed.clip_norm > 0:
                g = clip_by_global_norm(g, fed.clip_norm)
            lora = jax.tree_util.tree_map(
                lambda p, gg, s: p - s * gg, lora, g, lrs)
            losses.append(float(lv))
        return lora, float(np.mean(losses))

    def group_steps(self, clients, theta, n_steps: int, iters,
                    use_split=True, prox_anchor=None, per_client=None):
        """Run one local round for a client group on the active backend.

        ``theta`` is either one shared LoRA tree or — for the fused
        cross-group dispatch of the sharded engine — a ``{client: tree}``
        dict of per-client starting points (clients of different edge
        groups carry their own edge model into one stacked round).
        Callers that know which form they pass should say so via
        ``per_client``; the default sniffs the dict's key types
        (:func:`~repro.federation.engine.is_client_map`), which is only
        safe while no registered model's LoRA pytree is integer-keyed.
        Returns ``{client: (lora, mean loss)}``.  The batched backend
        stacks the group per split bucket and runs the compiled
        vmap/scan round; the reference backend loops ``client_steps``.
        """
        if per_client is None:
            per_client = is_client_map(theta)
        if self.backend != "batched":
            return {n: self.client_steps(n, theta[n] if per_client
                                         else theta, n_steps, iters[n],
                                         use_split=use_split,
                                         prox_anchor=prox_anchor)
                    for n in clients}
        splits = {n: (Split(*self.splits[n]) if use_split
                      else self._default_split()) for n in clients}
        # all missing channels derive from the same theta -> one probe
        # forward shared across clients instead of N identical ones
        # (per-client thetas share it too when they are one object, the
        # fused first-dispatch case)
        emb = None
        shared = (theta if not per_client
                  else (theta[clients[0]]
                        if len({id(theta[n]) for n in clients}) == 1
                        else None))
        if self.fed.use_channel and self._population is None and \
                shared is not None and \
                any(n not in self._channels for n in clients):
            emb = self._probe_embeddings(shared)
        channels = {n: self.channel_for(n, theta[n] if per_client
                                        else theta, emb=emb)
                    for n in clients}
        with tm.span("data.draw"):
            batches = {n: [next(iters[n]) for _ in range(n_steps)]
                       for n in clients}
        return self.engine.run_clients(theta, clients, splits, channels,
                                       batches, prox_anchor=prox_anchor,
                                       per_client_theta=per_client)

    # ------------------------------------------------------------------
    def evaluate(self, lora) -> float:
        if self._eval_fn is None:
            # params and tokens stay arguments (not closures): a closed-
            # over array is baked into the program as a constant, which
            # at published widths copies every frozen weight into the
            # executable and makes XLA constant-fold the test set
            self._eval_fn = jax.jit(lambda fr, lp, toks: self.model.forward(
                fr, lp, toks)[1])
        logits = self._eval_fn(self.frozen, lora,
                               jnp.asarray(self.test_tokens))
        tm.inc("host.syncs", 1, site="eval")
        return self.model.accuracy(logits, self.test_tokens,
                                   self.test_labels)

    # ------------------------------------------------------------------
    def _batched_probe_embeddings(self, loras):
        """Probe embeddings for a list of lora trees: (N, Q, D)."""
        if self._probe_fn is None:
            # frozen params as an argument, as in evaluate()
            self._probe_fn = jax.jit(jax.vmap(self.model.probe_repr,
                                              in_axes=(None, 0, None)))
        return self._probe_fn(self.frozen, stack_trees(loras),
                              jnp.asarray(self.probe))

    def profile_clients(self):
        """Phase 1: warmup locally, fingerprint, trust, cluster.

        On the batched backend the warmup of all clients goes through
        the engine as one split bucket (they share the default split):
        one vmap/scan round program, or, where the compiler refuses that
        many clients at once for want of device memory, several cuts of
        the bucket.  Each client is its own row either way, so the
        warmed adapters do not depend on the cut.  The probe forwards
        batch through a single vmapped jit call.
        """
        fed = self.fed
        iters = {n: infinite_batches(self.data[n].tokens,
                                     self.data[n].labels, fed.batch_size,
                                     seed=fed.seed + n)
                 for n in range(fed.n_clients)}
        clients = list(range(fed.n_clients))
        fps, norms, warm_loras = [], [], {}
        if self.backend == "batched":
            with tm.span("profile.warmup"):
                res = self.group_steps(clients, self.lora0,
                                       fed.local_warmup_steps, iters,
                                       use_split=False)
            warm_loras = {n: res[n][0] for n in clients}
            with tm.span("profile.probe"):
                embs = self._batched_probe_embeddings(
                    [warm_loras[n] for n in clients])
                for n in clients:
                    fps.append(fingerprint(embs[n]))
                    norms.append(np.asarray(jnp.linalg.norm(embs[n],
                                                            axis=-1)))
                tm.inc("host.syncs", len(clients), site="profile.probe")
        else:
            for n in clients:
                lora_n, _ = self.client_steps(n, self.lora0,
                                              fed.local_warmup_steps,
                                              iters[n], use_split=False)
                warm_loras[n] = lora_n
                emb = self._probe_embeddings(lora_n)
                fps.append(fingerprint(emb))
                norms.append(np.asarray(jnp.linalg.norm(emb, axis=-1)))
                tm.inc("host.syncs", 1, site="profile.probe")
        with tm.span("profile.kl"):
            div = divergence_matrix(fps)
            # one host read of the whole matrix
            tm.inc("host.syncs", 1, site="profile.kl")
        with tm.span("profile.cluster"):
            trust = trust_scores(div, np.stack(norms))
            result = clus.cluster_clients(div, trust, self.topo.latency,
                                          tau_max=fed.tau_max,
                                          gamma=fed.gamma, w_min=fed.w_min,
                                          seed=fed.seed)
        return div, trust, result, warm_loras

    # ------------------------------------------------------------------
    def _assign_groups(self, method: str, rng):
        """Phase-1 edge assignment shared by the round loop and the
        event-driven runtime: returns ``(groups, div, trust)``."""
        fed = self.fed
        use_cluster = method in ("elsa", "elsa-fixed")
        if method in ("elsa", "elsa-fixed", "elsa-nocluster"):
            div, trust, cres, _ = (self.profile_clients() if use_cluster
                                   else (None, None, None, None))
            if not use_cluster:   # random assignment ablation
                groups = {k: [] for k in range(fed.n_edges)}
                for n in range(fed.n_clients):
                    groups[rng.integers(0, fed.n_edges)].append(n)
                div = np.ones((fed.n_clients, fed.n_clients))
                np.fill_diagonal(div, 0)
                trust = np.ones(fed.n_clients)
            else:
                groups = {k: v for k, v in cres.groups.items()}
                if cres.escalated:
                    # Stage 4(ii): escalate to cloud-level aggregation
                    groups[-1] = list(cres.escalated)
                if not any(groups.values()):
                    # degenerate clustering: fall back to latency assignment
                    groups = {k: [] for k in range(fed.n_edges)}
                    for n in range(fed.n_clients):
                        groups[int(np.argmin(self.topo.latency[n]))].append(n)
        else:
            groups = {0: list(range(fed.n_clients))}
            div = np.zeros((fed.n_clients, fed.n_clients))
            trust = np.ones(fed.n_clients)
        # screening starts from the clustering-time
        # prediction-consistency trust as its EMA seed
        self.trust_ledger.seed(trust)
        return groups, div, trust

    def _edge_round(self, active, theta_k, steps: int, iters, *,
                    use_split: bool = True, prox_anchor=None):
        """One local round for ``active`` clients from edge model
        ``theta_k``; returns ``(locals_, weights, {client: loss})``."""
        res = self.group_steps(active, theta_k, steps, iters,
                               use_split=use_split,
                               prox_anchor=prox_anchor)
        locals_ = [res[n][0] for n in active]
        weights = [self.client_weight(n) for n in active]
        losses = {n: res[n][1] for n in active}
        return locals_, weights, losses

    def _fused_edge_round(self, actives, theta_ks, steps: int, iters, *,
                          use_split: bool = True, prox_anchor=None):
        """One local round for *every* edge group in a single dispatch:
        each client carries its group's edge model into one stacked
        (and, with a mesh, sharded) engine round instead of one
        ``run_clients`` call per group.  Returns
        ``(new_theta_ks, {client: loss})`` with each group's FedAvg
        applied over its own members."""
        thetas = {n: theta_ks[k] for k, act in actives.items() for n in act}
        all_active = [n for act in actives.values() for n in act]
        res = self.group_steps(all_active, thetas, steps, iters,
                               use_split=use_split, prox_anchor=prox_anchor,
                               per_client=True)
        if self._population is not None:
            for k, act in actives.items():
                self._population.note_updates(
                    act, [res[n][0] for n in act], theta_ks[k])
        new_ks = {k: self.screened_aggregate(
                      act, [res[n][0] for n in act],
                      [self.client_weight(n) for n in act], theta_ks[k])
                  for k, act in actives.items()}
        return new_ks, {n: res[n][1] for n in all_active}

    # -- update screening (docs/robustness.md) -------------------------
    def _screen_identities(self, clients):
        """(ledger, keys) for one screening pass.  With a bound
        population, verdicts are recorded against client *identities* —
        each slot resolves to its pinned dispatch-time id, so a
        straggler arriving after a cohort swap credits/blames the
        identity that actually trained, never the slot's new occupant —
        through the identity-keyed ledger facade.  Without one,
        identity == slot and the slot ledger is used directly."""
        if self._population is None:
            return self.trust_ledger, list(clients)
        pop = self._population
        return pop.ledger_view, [pop.pinned(int(n)) for n in clients]

    def screened_aggregate(self, clients, trees, weights, base):
        """Edge aggregation with the optional screening stage.

        With ``FedConfig.screen`` off this IS
        ``agg.aggregate_adapters(trees, weights)`` — same call, same
        floats, golden bit-inert.  With it on, updates are screened
        against ``base`` (the model they were dispatched from), the
        trust EMA is updated from the verdicts, survivors are
        trust-down-weighted, and an over-screened cohort falls back to
        the trimmed mean (:mod:`repro.core.screening`).
        """
        if not self.fed.screen:
            return agg.aggregate_adapters(trees, weights,
                                          mode=self.fed.aggregate)
        from repro.core.screening import screen_and_aggregate
        from repro.federation.engine import screen_stats
        ledger, keys = self._screen_identities(clients)
        out, report = screen_and_aggregate(
            base, trees, weights, keys, ledger,
            self.screening, mode=self.fed.aggregate, stats_fn=screen_stats)
        self.screen_log.append(report)
        return out

    def screen_cohort(self, clients, trees, weights, base):
        """Screening without aggregation, for schedulers that combine
        arrivals with an anchor term (the deadline policy): returns the
        surviving ``(trees, weights)`` with trust-scaled weights.  A
        fully-screened-out cohort returns empty lists — the caller's
        anchor then carries the round."""
        if not self.fed.screen:
            return list(trees), list(weights)
        from repro.core.screening import screen_updates
        from repro.federation.engine import screen_stats
        ledger, keys = self._screen_identities(clients)
        report = screen_updates(base, trees, weights, keys,
                                ledger, self.screening,
                                stats_fn=screen_stats)
        self.screen_log.append(report)
        kept_trees = [trees[i] for i in report.kept]
        kept_wts = [float(weights[i]) * ledger.weight(keys[i])
                    for i in report.kept]
        return kept_trees, kept_wts

    def fusion_trust(self, trust, members) -> float:
        """Mean trust feeding an edge's cloud-fusion weight (Eq. 14):
        the live screening EMA when screening is on, the static
        clustering-time scores otherwise (bit-inert default)."""
        if self.fed.screen:
            return float(np.mean(self.trust_ledger.scores[list(members)]))
        return float(np.mean(trust[list(members)]))

    # ------------------------------------------------------------------
    def run(self, method: str = "elsa", global_rounds: int = 10,
            steps_per_round: int = 4, eval_every: int = 1,
            log: bool = False, runtime=None, checkpoint=None,
            resume_from: Optional[str] = None, population=None) -> Dict:
        """Run the federation.

        ``runtime=None`` keeps the historical round-synchronous loop
        (no wall-clock model).  Passing a
        :class:`repro.runtime.RuntimeConfig` delegates to the
        event-driven :class:`repro.runtime.EdgeRuntime` — histories gain
        a simulated ``time`` axis and an event ``trace``; with
        ``policy="sync"`` and no churn the training math (and therefore
        the history) is identical to the historical loop.

        ``checkpoint`` (a :class:`repro.checkpoint.CheckpointConfig`)
        snapshots the full federation state on a rolling cadence;
        ``resume_from`` (a checkpoint file or its directory) restores
        one and continues — bit-identically to the uninterrupted run on
        this loop and the sync runtime policy (docs/robustness.md).

        ``population`` (a :class:`repro.population.PopulationConfig`)
        decouples the registered client population from the
        ``n_clients`` slots: each round samples a cohort of registered
        ids into the slots (docs/population.md).  With
        ``registered == n_clients`` the run is bit-identical to
        ``population=None``.
        """
        if runtime is not None:
            from repro.runtime import EdgeRuntime
            return EdgeRuntime(self, runtime).run(
                method, global_rounds=global_rounds,
                steps_per_round=steps_per_round, eval_every=eval_every,
                log=log, checkpoint=checkpoint, resume_from=resume_from,
                population=population)
        from repro.checkpoint import federation as fedckpt
        from repro.data.pipeline import CountingIterator
        fed = self.fed
        rng = np.random.default_rng(fed.seed + 5)
        history = {"round": [], "accuracy": [], "loss": [], "delta": []}

        use_split_dyn = method not in ("elsa-fixed",)
        pop = self._bind_population(population)
        iters = pop.iters if pop is not None else \
            {n: CountingIterator(
                 infinite_batches(self.data[n].tokens,
                                  self.data[n].labels, fed.batch_size,
                                  seed=fed.seed + 100 + n))
             for n in range(fed.n_clients)}
        server_opt = self.server_optimizer(method)

        start_round, last_delta = 0, float("inf")
        if resume_from is not None:
            state = fedckpt.load_state(fedckpt.resolve(resume_from))
            res = fedckpt.restore_run(self, state, method=method,
                                      steps_per_round=steps_per_round,
                                      iters=iters, rng=rng, population=pop)
            groups, div, trust = res.groups, res.div, res.trust
            theta, server_state = res.theta, res.server_state
            history, client_losses = res.history, res.client_losses
            start_round, last_delta = res.round_idx + 1, res.delta
        else:
            with tm.span("profile", method=method):
                groups, div, trust = self._assign_groups(method, rng)
            if pop is not None:
                pop.after_assign(groups)
            theta = self.lora0
            server_state = server_opt.init(theta) if server_opt else None
            client_losses: Dict[int, List[float]] = {
                n: [] for n in range(fed.n_clients)}
        ckpt = fedckpt.Checkpointer(checkpoint) if checkpoint else None
        if last_delta <= fed.xi:
            # the checkpointed run had already converged (Eq. 16)
            history["final_accuracy"] = history["accuracy"][-1]
            history["client_losses"] = client_losses
            self.last_theta = theta
            return history
        # with a mesh, all edge groups dispatch as one sharded round per
        # edge-round index (devices see one big stacked cohort, not one
        # small dispatch per group); single-device keeps the historical
        # per-group dispatch so default runs stay bit-identical
        fuse = self.backend == "batched" and self.mesh is not None
        for g in range(start_round, global_rounds):
            if pop is not None:
                pop.begin_round(g)
            edge_thetas, edge_alphas, losses = {}, {}, []
            actives = {}
            for k, members in groups.items():
                if not members:
                    continue
                active = members
                if method == "fedavg-random":
                    m = max(1, len(members) // 2)
                    active = list(rng.choice(members, m, replace=False))
                actives[k] = active
            anchor = theta if method == "fedprox" else None
            if fuse:
                theta_ks = {k: theta for k in actives}
                round_maps = []
                for _ in range(fed.t_rounds):
                    with tm.span("local_steps", round=g,
                                 n_clients=sum(len(a) for a
                                               in actives.values())):
                        theta_ks, loss_map = self._fused_edge_round(
                            actives, theta_ks, steps_per_round, iters,
                            use_split=use_split_dyn, prox_anchor=anchor)
                    round_maps.append(loss_map)
                # record group-major (all of group k's edge rounds, then
                # the next group), matching the per-group path exactly —
                # np.mean over `losses` is order-sensitive in the last
                # ulp, and the 1-device mesh history is pinned bitwise
                for k, act in actives.items():
                    for loss_map in round_maps:
                        for n in act:
                            losses.append(loss_map[n])
                            client_losses[n].append(loss_map[n])
                edge_thetas = theta_ks
            else:
                for k, active in actives.items():
                    theta_k = theta
                    for _ in range(fed.t_rounds):
                        with tm.span("local_steps", round=g, edge=k,
                                     n_clients=len(active)):
                            locals_, weights, loss_map = self._edge_round(
                                active, theta_k, steps_per_round, iters,
                                use_split=use_split_dyn,
                                prox_anchor=anchor)
                        for n in active:
                            losses.append(loss_map[n])
                            client_losses[n].append(loss_map[n])
                        if pop is not None:
                            pop.note_updates(active, locals_, theta_k)
                        with tm.span("edge_agg", round=g, edge=k,
                                     n_updates=len(active)):
                            theta_k = self.screened_aggregate(
                                active, locals_, weights, theta_k)
                    edge_thetas[k] = theta_k
            for k, active in actives.items():
                edge_alphas[k] = agg.edge_weight(
                    agg.mean_pairwise_kld(div, active),
                    self.fusion_trust(trust, active))

            with tm.span("cloud_agg", round=g, n_edges=len(edge_thetas)):
                if method in ("elsa", "elsa-fixed", "elsa-nocluster"):
                    theta_new = agg.cloud_aggregate(edge_thetas,
                                                    edge_alphas,
                                                    mode=fed.aggregate)
                else:
                    ws = {k: 1.0 for k in edge_thetas}
                    theta_new = agg.cloud_aggregate(edge_thetas, ws,
                                                    mode=fed.aggregate)

                if server_opt is not None:
                    pseudo = jax.tree_util.tree_map(lambda a, b: a - b,
                                                    theta, theta_new)
                    theta_new, server_state = server_opt.update(
                        theta, pseudo, server_state)
                with tm.span("agg.delta"):
                    delta = agg.global_delta(theta_new, theta)
                if tm.enabled():
                    # one host read per adapter leaf
                    tm.inc("host.syncs", len(jax.tree_util.tree_leaves(
                        theta_new)), site="agg.delta")
            theta = theta_new
            if g % eval_every == 0 or g == global_rounds - 1:
                with tm.span("eval", round=g):
                    acc = self.evaluate(theta)
                history["round"].append(g)
                history["accuracy"].append(acc)
                history["loss"].append(float(np.mean(losses)))
                history["delta"].append(delta)
                if log:
                    print(f"[{method}] round {g}: acc={acc:.4f} "
                          f"loss={np.mean(losses):.4f} delta={delta:.2e}")
            if pop is not None:
                # write the round's outcomes back before any snapshot so
                # a resume sees the post-round registry
                pop.end_round(g)
            if ckpt is not None and ckpt.due(g, global_rounds - 1, delta,
                                            fed.xi):
                ckpt.save(g, fedckpt.build_state(
                    self, method=method, steps_per_round=steps_per_round,
                    round_idx=g, theta=theta, server_state=server_state,
                    rng=rng, iters=iters, history=history,
                    client_losses=client_losses, groups=groups, div=div,
                    trust=trust, delta=delta, population=pop))
            tm.end_round(g)
            if delta <= fed.xi:
                break
        history["final_accuracy"] = history["accuracy"][-1]
        history["client_losses"] = client_losses
        self.last_theta = theta           # final aggregated LoRA (parity)
        return history
