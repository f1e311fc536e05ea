"""Batched-engine parity vs the sequential reference backend.

Why float64 + small lr: the split-BERT gradient map is chaotic — a 1e-6
relative parameter perturbation changes the eager gradient by ~1e-1
(measured parameter-Lipschitz ~1e5 on the q_b LoRA leaf), and the
count-sketch median's subgradient is discontinuous.  Any fp-level
discrepancy between two compilation strategies (eager per-client loop vs
vmap/scan jit) therefore amplifies by roughly ``lr * 1e5`` per local
step.  Running parity in x64 with a small lr keeps backend discrepancies
at the 1e-12 level where trajectories stay glued for the whole run —
which is exactly what we want to verify: that the batched engine
computes the *same math* as the reference, the one thing a vmap/scan
rewrite can silently get wrong.  At the training lr we additionally
check single-step gradient parity (before chaos can amplify).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sketch import (SketchPlan, channel, compress, decompress,
                               make_plan, selection_matrices)

# small-lr / f64 parity configuration; total_examples=300 gives client 0
# a 14-example dataset so every one of its batches is a ragged, padded one
PARITY_KW = dict(n_clients=6, n_edges=2, alpha=0.2, poisoned=(4,),
                 total_examples=300, probe_q=8, local_warmup_steps=2,
                 lr=1e-4, layers=4, t_rounds=1, batch_size=16,
                 dtype="float64", seed=0)


def _max_tree_diff(a, b):
    return max(float(jnp.abs(x - y).max()) for x, y in
               zip(jax.tree_util.tree_leaves(a),
                   jax.tree_util.tree_leaves(b)))


@pytest.fixture(scope="module")
def x64_feds():
    from repro.federation.simulation import FedConfig, Federation
    with jax.enable_x64(True):
        fb = Federation(FedConfig(**PARITY_KW), backend="batched")
        fr = Federation(FedConfig(**PARITY_KW), backend="reference")
        yield fb, fr


def _assert_run_parity(fb, fr, method, rounds=2, steps=2):
    hb = fb.run(method, global_rounds=rounds, steps_per_round=steps)
    hr = fr.run(method, global_rounds=rounds, steps_per_round=steps)
    assert abs(hb["final_accuracy"] - hr["final_accuracy"]) <= 1e-4
    for n in range(fb.fed.n_clients):
        a = np.asarray(hb["client_losses"][n])
        b = np.asarray(hr["client_losses"][n])
        assert a.shape == b.shape
        if a.size:
            assert np.abs(a - b).max() <= 1e-5, f"client {n}"
    # end-of-run theta: the backends reassociate fp differently and the
    # chaotic map amplifies that over rounds*steps local steps, so how
    # far the trajectories sit apart at the end varies with XLA codegen
    # (~2e-5 under the elsa channel here); the single-step parity test
    # below holds the 1e-8-level same-math line
    assert _max_tree_diff(fb.last_theta, fr.last_theta) <= 1e-4


def test_engine_matches_reference_elsa(x64_feds):
    """Full Alg. 1 (clustered, SS-OP∘sketch channel on): batched == ref."""
    with jax.enable_x64(True):
        _assert_run_parity(*x64_feds, "elsa")


def test_engine_matches_reference_fedprox(x64_feds):
    """FedProx anchor term vectorizes identically (broadcast anchor)."""
    with jax.enable_x64(True):
        _assert_run_parity(*x64_feds, "fedprox")


def test_engine_single_step_parity_at_training_lr(x64_feds):
    """One local step at the real lr: gradient math identical to 1e-8
    (before chaotic trajectory amplification can kick in)."""
    from repro.data.pipeline import infinite_batches
    with jax.enable_x64(True):
        fb, fr = x64_feds
        lr0 = fb.fed.lr
        clients = list(range(fb.fed.n_clients))

        def its(f):
            return {n: infinite_batches(f.data[n].tokens, f.data[n].labels,
                                        f.fed.batch_size, seed=777 + n)
                    for n in clients}

        rb = fb.group_steps(clients, fb.lora0, 1, its(fb))
        rr = fr.group_steps(clients, fr.lora0, 1, its(fr))
        for n in clients:
            lb, sb = rb[n]
            lrr, sr = rr[n]
            assert abs(sb - sr) <= 1e-9
            # updates are lr-scaled; compare the implied gradient
            assert _max_tree_diff(lb, lrr) / lr0 <= 1e-6


def test_make_plan_selection_cache_regression():
    """Precomputing the signed-selection tensor on the plan must not
    change compress/decompress/channel outputs (bit-identical)."""
    plan = make_plan(64, 3, 16, seed=5)
    assert plan.selection is not None
    plain = SketchPlan(plan.bucket, plan.sign, plan.z)     # no cache
    assert plain.selection is None
    h = jax.random.normal(jax.random.PRNGKey(2), (7, 5, 64))
    np.testing.assert_array_equal(np.asarray(compress(h, plan)),
                                  np.asarray(compress(h, plain)))
    u = compress(h, plan)
    np.testing.assert_array_equal(np.asarray(decompress(u, plan)),
                                  np.asarray(decompress(u, plain)))
    np.testing.assert_array_equal(np.asarray(channel(h, plan)),
                                  np.asarray(channel(h, plain)))
    # cached tensor == rebuilt tensor, and scatter path stays bit-equal
    np.testing.assert_array_equal(np.asarray(selection_matrices(plan)),
                                  np.asarray(selection_matrices(plain)))
    np.testing.assert_allclose(
        np.asarray(compress(h, plan, via_matmul=False)),
        np.asarray(compress(h, plan)), atol=1e-6)


def test_weighted_loss_padding_matches_unpadded():
    """Zero-weight padded rows contribute exactly nothing to loss/grad."""
    from repro.configs import get_config
    from repro.core.split_training import (Channel, Split, split_loss,
                                           weighted_split_loss)
    from repro.models import bert as bert_mod
    from repro.models.params import init_tree

    cfg = get_config("bert-base").reduced().with_(num_layers=4)
    tree = init_tree(bert_mod.bert_specs(cfg, 4), jax.random.PRNGKey(0),
                     jnp.float32)
    frozen, lora = tree["frozen"], tree["lora"]
    toks = jax.random.randint(jax.random.PRNGKey(1), (9, 12), 0,
                              cfg.vocab_size)
    labels = jax.random.randint(jax.random.PRNGKey(2), (9,), 0, 4)
    split = Split(1, 1, 2)
    ref = {"tokens": toks, "labels": labels}
    pad_t = jnp.concatenate([toks, jnp.zeros((7, 12), toks.dtype)])
    pad_l = jnp.concatenate([labels, jnp.zeros((7,), labels.dtype)])
    w = jnp.concatenate([jnp.ones(9), jnp.zeros(7)])
    padded = {"tokens": pad_t, "labels": pad_l, "weights": w}

    l_ref, g_ref = jax.value_and_grad(
        lambda lp: split_loss(cfg, frozen, lp, ref, split))(lora)
    l_pad, g_pad = jax.value_and_grad(
        lambda lp: weighted_split_loss(cfg, frozen, lp, padded, split))(lora)
    assert abs(float(l_ref) - float(l_pad)) <= 1e-6
    assert _max_tree_diff(g_ref, g_pad) <= 1e-5


# ---------------------------------------------------------------------------
# buckets cut to what the compiler accepts
# ---------------------------------------------------------------------------

#: the v5e compiler's words for a program that does not fit, and the
#: runtime's for an allocation that fails once a program is loaded
REFUSAL = ("RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out "
           "of memory in memory space hbm. Used 22.18G of 15.75G hbm.")
RUNTIME_OOM = ("RESOURCE_EXHAUSTED: Error allocating device buffer: "
               "Attempting to allocate 1.00G. That was not possible. "
               "There are 512.00M free.")


def refuse_above(monkeypatch, cap, message=REFUSAL):
    """Make every round program of more than ``cap`` clients fail with
    ``message``, both where the engine dispatches it and where its fit
    compiles it ahead of time."""
    from repro.federation.engine import BatchedEngine
    round_fn, compile_cut = BatchedEngine._round_fn, BatchedEngine._compile_cut

    def check(args):
        if args[4].shape[1] > cap:
            raise jax.errors.JaxRuntimeError(message)

    def refusing_round_fn(self, split, prox):
        fn = round_fn(self, split, prox)

        def call(*args):
            check(args)
            return fn(*args)
        call._cache_size = fn._cache_size
        return call

    def refusing_compile_cut(self, fn, args):
        check(args)
        return compile_cut(self, fn, args)
    monkeypatch.setattr(BatchedEngine, "_round_fn", refusing_round_fn)
    monkeypatch.setattr(BatchedEngine, "_compile_cut", refusing_compile_cut)


@pytest.mark.parametrize("n,cap,sizes", [(20, 8, [7, 7, 6]), (6, 2, [2, 2, 2]),
                                         (5, 8, [5]), (9, 4, [3, 3, 3])])
def test_cut_members_keeps_order_in_near_equal_cuts(n, cap, sizes):
    from repro.federation.engine import cut_members
    members = [10 * i for i in range(n)]
    cuts = cut_members(members, cap)
    assert [len(c) for c in cuts] == sizes
    assert [m for c in cuts for m in c] == members


@pytest.mark.parametrize("message,refused", [
    (REFUSAL, True), (RUNTIME_OOM, False),
    ("RESOURCE_EXHAUSTED: TPU backend error: Error loading program: "
     "Attempting to reserve 12.5G at the bottom of memory.", False),
    ("INTERNAL: compile failed", False)])
def test_compile_refused_is_the_compilers_refusal_alone(message, refused):
    from repro.federation.engine import compile_refused
    assert compile_refused(jax.errors.JaxRuntimeError(message)) is refused
    assert not compile_refused(RuntimeError(message))


class _Analysis:
    """A memory analysis of ``n`` clients: 4 GB of weights, 16 MB of
    arguments and 1.4 GB of temporaries per client over a 4.5 GB part
    that does not grow (the shape of OLMo-1B's warm-up on a v5e)."""

    def __init__(self, n):
        self.argument_size_in_bytes = 4.0e9 + n * 16e6
        self.output_size_in_bytes = self.alias_size_in_bytes = n * 16e6
        self.temp_size_in_bytes = 4.5e9 + n * 1.4e9


@pytest.mark.parametrize("limit,accepts,cap,tried", [
    (16.9e9, 8, 8, [1, 2, 8]),            # the guess compiles
    (16.9e9, 7, 7, [1, 2, 8, 7]),         # one refusal, one size down
    (None, 5, 5, [16, 14, 12, 10, 8, 7, 6, 5]),   # no limit: step down
    (3.0e9, 8, 1, [1, 2]),                # the guess is below two
    (16.9e9, 1, 1, [1, 2]),               # two clients are refused
])
def test_fit_clients_guesses_then_steps_down(limit, accepts, cap, tried):
    from repro.federation.engine import fit_clients
    compiled, refusals = [], []

    def compile_cut(n):
        compiled.append(n)
        if n > accepts:
            raise jax.errors.JaxRuntimeError(REFUSAL)
        return _Analysis(n)
    got = fit_clients(compile_cut, 20, None, 1, limit, refusals.append)
    assert (got, compiled) == (cap, tried)
    assert refusals == [n for n in tried if n > accepts]


def test_fit_clients_raises_what_is_not_a_refusal():
    from repro.federation.engine import fit_clients
    refusal = jax.errors.JaxRuntimeError(REFUSAL)

    def compile_cut(n):
        raise jax.errors.JaxRuntimeError(RUNTIME_OOM if n < 4 else REFUSAL)
    with pytest.raises(jax.errors.JaxRuntimeError, match="allocating"):
        fit_clients(compile_cut, 10, refusal)
    with pytest.raises(jax.errors.JaxRuntimeError, match="compile"):
        fit_clients(compile_cut, 1, refusal)   # no smaller size


def _warmup(fed):
    """Every client's warm-up round from the shared start, as
    ``profile_clients`` runs it (the default split: one bucket)."""
    from repro.data.pipeline import infinite_batches
    clients = list(range(fed.fed.n_clients))
    iters = {n: infinite_batches(fed.data[n].tokens, fed.data[n].labels,
                                 fed.fed.batch_size, seed=fed.fed.seed + n)
             for n in clients}
    return fed.group_steps(clients, fed.lora0, fed.fed.local_warmup_steps,
                           iters, use_split=False)


def test_cut_warmup_matches_whole_and_is_counted(x64_feds, monkeypatch):
    """A warm-up the compiler will not take whole goes in cuts of the
    largest size it takes, with one host sync per call; each client's
    adapters and loss are what the whole program gives."""
    from repro import telemetry as tm
    fb, _ = x64_feds
    with jax.enable_x64(True):
        whole = _warmup(fb)
        refuse_above(monkeypatch, 2)
        monkeypatch.setattr(fb.engine, "_caps", {})
        before = sum(fb.engine.compile_cache_sizes().values())
        with tm.session() as tel:
            cut = _warmup(fb)
            again = _warmup(fb)
    n = fb.fed.n_clients                  # 6: cuts of 2, 2 and 2
    for res in (cut, again):
        for c in range(n):
            # each client is its own row of a vmapped program: the cut
            # changes only the program's width, which may reorder XLA's
            # float64 sums (1e-16), and two steps at lr 1e-4 of this
            # chaotic map (parameter-Lipschitz ~1e5) grow that ~10x a step
            assert abs(res[c][1] - whole[c][1]) <= 1e-10
            assert _max_tree_diff(res[c][0], whole[c][0]) <= 1e-10
    # refused once, whole at 6, then 5, 4 and 3 in the fit (no device
    # memory limit on the CPU), and kept: the second call cuts at once
    refusals = tel.counters_by_name("engine.compile_refusals")
    assert sorted(int(k.split("bucket=")[1].split(",")[0])
                  for k in refusals) == [3, 4, 5, 6]
    assert all(v == 1.0 for v in refusals.values())
    assert sum(tel.counters_by_name("engine.cut_dispatches").values()) == 6
    assert all("size=2" in k for k in
               tel.counters_by_name("engine.cut_dispatches"))
    fits = {k: v for k, v in tel.gauges.items()
            if k.startswith("engine.fit_clients")}
    assert list(fits.values()) == [2.0]
    spans = [s["name"] for s in tel._spans]
    assert spans.count("engine.fit") == 1
    assert spans.count("engine.fetch") == 2
    assert tel.counter("host.syncs", site="engine.fetch") == 2.0
    # a refused compile is no compile: the counter still equals the cache
    compiles = sum(tel.counters_by_name("engine.jit_compiles").values())
    assert compiles == sum(fb.engine.compile_cache_sizes().values()) - before


def test_engine_matches_reference_elsa_in_cuts(x64_feds, monkeypatch):
    """Alg. 1 with every bucket of more than two clients cut: batched ==
    reference, as whole."""
    from repro import telemetry as tm
    fb, fr = x64_feds
    refuse_above(monkeypatch, 2)
    monkeypatch.setattr(fb.engine, "_caps", {})
    with jax.enable_x64(True), tm.session() as tel:
        _assert_run_parity(fb, fr, "elsa")
    assert sum(tel.counters_by_name("engine.cut_dispatches").values()) > 0


@pytest.mark.parametrize("error", ["runtime_oom", "other"])
def test_runtime_error_is_not_swallowed(x64_feds, monkeypatch, error):
    """Only the compiler's refusal is retried smaller: an allocation that
    fails in a running program, or any other error, ends the call."""
    fb, _ = x64_feds
    if error == "runtime_oom":
        refuse_above(monkeypatch, 0, RUNTIME_OOM)
        expect = jax.errors.JaxRuntimeError
    else:
        from repro.federation.engine import BatchedEngine

        def round_fn(self, split, prox):
            raise ValueError("not a compile")
        monkeypatch.setattr(BatchedEngine, "_round_fn", round_fn)
        expect = ValueError
    monkeypatch.setattr(fb.engine, "_caps", {})
    with jax.enable_x64(True), pytest.raises(expect):
        _warmup(fb)
    assert fb.engine._caps == {}
